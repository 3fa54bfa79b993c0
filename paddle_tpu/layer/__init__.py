"""The layer library: ~90 layer constructors building a lazy DAG.

Surface parity with python/paddle/v2/layer.py + trainer_config_helpers/
layers.py (reference `__all__` at layers.py:33); execution is pure-JAX via
paddle_tpu.topology.Topology. Families:

  io.py        data
  basic.py     fc, embedding, concat, addto, dropout, scaling, bias, ...
  conv.py      img_conv, img_pool, batch_norm, img_cmrnorm, spp, maxout, ...
  sequence.py  pooling, first/last_seq, expand, seq_* , context_projection,
               row_conv, block_expand, max_id, sampling_id, eos_id, print
  recurrent.py lstmemory, grumemory, recurrent
  rnn_group.py recurrent_group, memory, beam_search, get_output
  cost.py      classification_cost, cross_entropy, square_error, rank, ...
  mixed.py     mixed + projections/operators
  extra.py     nce, hsigmoid, crf, crf_decoding, ctc, warp_ctc, detection
  decoder.py   rms_norm, layer_norm, gated_mlp, moe, mamba2, mamba1, gmu,
               short_conv, gated_delta_net, gqa_attention, lm_head,
               recompute (their token-level cost, lm_cost, is in cost.py)
"""

from paddle_tpu.graph import LayerNode, LayerOutput, reset_name_counters
from paddle_tpu.layer.base import layer_registry

from paddle_tpu.layer.io import data
from paddle_tpu.layer.basic import (
    addto,
    bias,
    concat,
    cos_sim,
    dropout,
    embedding,
    fc,
    interpolation,
    linear_comb,
    power,
    repeat,
    resize,
    scaling,
    slope_intercept,
    sum_to_one_norm,
    trans,
)
from paddle_tpu.layer.conv import (
    batch_norm,
    bilinear_interp,
    conv_shift,
    crop,
    img_cmrnorm,
    img_conv,
    img_pool,
    maxout,
    pad,
    rotate,
    spp,
)
from paddle_tpu.layer.sequence import (
    block_expand,
    context_projection_layer,
    eos_id,
    expand,
    first_seq,
    last_seq,
    max_id,
    maxid,
    pooling,
    print_layer,
    row_conv,
    sampling_id,
    seq_concat,
    seq_reshape,
    seq_slice,
    sub_seq,
)
from paddle_tpu.layer.cost import (
    classification_cost,
    cross_entropy,
    cross_entropy_with_selfnorm,
    huber_classification_cost,
    huber_regression_cost,
    lambda_cost,
    lm_cost,
    mse_cost,
    multi_binary_label_cross_entropy,
    rank_cost,
    regression_cost,
    soft_binary_class_cross_entropy,
    smooth_l1_cost,
    square_error_cost,
    sum_cost,
)
from paddle_tpu.layer.recurrent import (grumemory, lstmemory,
                                        mdlstmemory, recurrent)
from paddle_tpu.layer.extra import (
    crf,
    crf_decoding,
    ctc,
    data_norm,
    featmap_expand,
    hsigmoid,
    nce,
    warp_ctc,
)
from paddle_tpu.layer.rnn_group import (
    BeamSearchControlCallbacks,
    BeamSearchGenerator,
    GeneratedInput,
    StaticInput,
    SubsequenceInput,
    beam_search,
    get_output,
    memory,
    recurrent_group,
)
from paddle_tpu.layer.mixed import (
    BaseProjection,
    context_projection,
    conv_operator,
    conv_projection,
    dotmul_operator,
    dotmul_projection,
    full_matrix_projection,
    identity_projection,
    mixed,
    scaling_projection,
    table_projection,
    trans_full_matrix_projection,
)
from paddle_tpu.layer.misc import (
    gated_unit,
    multiplex,
    out_prod,
    prelu,
    selective_fc,
    tensor,
)
from paddle_tpu.layer.decoder import (
    gated_delta_net,
    gated_mlp,
    gmu,
    gqa_attention,
    layer_norm,
    lm_head,
    mamba1,
    mamba2,
    moe,
    recompute,
    rms_norm,
    short_conv,
)
from paddle_tpu.layer.step import gru_step, gru_step_naive, lstm_step
from paddle_tpu.layer.detection import (
    cross_channel_norm,
    detection_output,
    multibox_loss,
    priorbox,
)

# aliases matching v2 naming
pooling_layer = pooling
embedding_layer = embedding
fc_layer = fc
data_layer = data

# aliases matching the v1 DSL (trainer_config_helpers/layers.py __all__)
convex_comb = linear_comb          # reference: convex_comb_layer = deprecated
eos = eos_id                       # reference: eos_layer
printer = print_layer              # reference: printer_layer
huber_cost = huber_classification_cost

# ---------------------------------------------------------------------------
# reference REGISTER_LAYER type-name aliases (gserver/layers REGISTER_LAYER
# audit): reference config type names resolve to the equivalent constructor
# here. agent/gather_agent/scatter_agent/recurrent_layer_group plumbing is
# subsumed by the recurrent_group scan design (see docs/DELTAS.md).
# ---------------------------------------------------------------------------
import functools as _functools

from paddle_tpu.layer.base import layer_registry as _registry

for _ref_name, _our_name in {
    "exconv": "img_conv", "cudnn_conv": "img_conv",
    "cudnn_batch_norm": "batch_norm",
    "seqlastins": "last_seq", "seqconcat": "seq_concat",
    "seqreshape": "seq_reshape", "subseq": "sub_seq",
    "blockexpand": "block_expand", "maxid": "max_id",
    "cos": "cos_sim", "cos_vm": "cos_sim",
    "convex_comb": "linear_comb", "concat2": "concat",
    "huber": "huber_classification_cost",
    "square_error": "square_error_cost", "smooth_l1": "smooth_l1_cost",
    "gated_recurrent": "grumemory",
    "multi_class_cross_entropy_with_selfnorm": "cross_entropy_with_selfnorm",
    "recurrent_layer_group": "recurrent_group",
    "warp_ctc": "ctc",
}.items():
    if _ref_name not in _registry:
        _registry.register(_ref_name, _registry.get(_our_name))

# names that select behavior in the reference must bind it here too
from paddle_tpu import pooling as _pooling

for _ref_name, _bound in {
    "exconvt": _functools.partial(img_conv, trans=True),
    "cudnn_convt": _functools.partial(img_conv, trans=True),
    "average": _functools.partial(pooling,
                                  pooling_type=_pooling.AvgPooling()),
    "max": _functools.partial(pooling,
                              pooling_type=_pooling.MaxPooling()),
}.items():
    if _ref_name not in _registry:
        _registry.register(_ref_name, _bound)
