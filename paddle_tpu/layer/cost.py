"""Cost (loss) layers.

Parity inventory: gserver/layers/CostLayer.cpp — MultiClassCrossEntropy,
SumOfSquaresCostLayer (square_error), RankingCost, LambdaCost,
MultiBinaryLabelCrossEntropy, HuberTwoClassification/HuberRegression,
SmoothL1Cost, SumCostLayer, CrossEntropyOverBeam era-adjacent; plus
classification_cost (softmax + CE composite, v2 layer.classification_cost).

Convention: every cost node outputs a per-sample cost vector [B] (sequence
costs are summed over valid timesteps per sequence). The trainer takes the
mean (and so does jax.grad), matching the reference's sum-over-batch /
batch-size normalization (TrainerInternal cost accounting).
"""

import jax
import jax.numpy as jnp

from paddle_tpu.activation import Softmax
from paddle_tpu.core.dtype import upcast_f32
from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.layer.base import (data_of, is_seq, layer_registry,
                                  make_node, register_layer)
from paddle_tpu.utils.error import enforce

_EPS = 1e-8


def _per_sample(cost_bt, label_or_input):
    """Reduce a per-timestep cost [B, T] to per-sample [B] with masking."""
    if is_seq(label_or_input):
        mask = label_or_input.mask(cost_bt.dtype)
        return jnp.sum(cost_bt * mask, axis=1)
    return cost_bt


def _maybe_weight(cost_b, values, has_weight):
    if has_weight:
        w = data_of(values[-1])
        return cost_b * w.reshape(cost_b.shape)
    return cost_b


@register_layer("cross_entropy")
def cross_entropy(input, label, name=None, weight=None, layer_attr=None):
    """-log(p[label]); input carries probabilities (post-softmax), matching
    the reference where cost sits on top of a softmax-activated layer."""
    inputs = [input, label] + ([weight] if weight is not None else [])

    def forward(params, values, ctx):
        p, y = values[0], values[1]
        pd, yd = upcast_f32(data_of(p)), data_of(y)
        picked = jnp.take_along_axis(pd, yd[..., None].astype(jnp.int32), axis=-1)[..., 0]
        cost = -jnp.log(picked + _EPS)
        cost = _per_sample(cost, y)
        return _maybe_weight(cost, values, weight is not None)

    return make_node("cross_entropy", forward, inputs, name=name, size=1,
                     layer_attr=layer_attr)


@register_layer("classification_cost")
def classification_cost(input, label, name=None, weight=None, layer_attr=None):
    """softmax (if needed) + CE, computed in log space for stability
    (v2 layer.classification_cost). Works on plain [B, C] and sequence
    [B, T, C] inputs (per-timestep classification, e.g. tagging)."""
    inputs = [input, label] + ([weight] if weight is not None else [])

    is_probs = getattr(input, "output_activation", None) in (
        "softmax", "sequence_softmax")

    def forward(params, values, ctx):
        logits_in, y = values[0], values[1]
        x = upcast_f32(data_of(logits_in))
        # Softmax-activated input: work from log(p) (subtracting logsumexp of
        # log-probs is an exact no-op, so both branches share one formula
        # conceptually); logits input: standard log-softmax.
        logp = jnp.log(x + _EPS) if is_probs else x - jax_logsumexp(x)
        yd = data_of(y).astype(jnp.int32)
        picked = jnp.take_along_axis(logp, yd[..., None], axis=-1)[..., 0]
        cost = -picked
        cost = _per_sample(cost, y)
        return _maybe_weight(cost, values, weight is not None)

    return make_node("classification_cost", forward, inputs, name=name, size=1,
                     layer_attr=layer_attr)


def jax_logsumexp(x):
    m = jnp.max(x, axis=-1, keepdims=True)
    return m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))


@register_layer("square_error_cost")
def square_error_cost(input, label, name=None, weight=None, layer_attr=None):
    """0.5 * sum((x - y)^2) per sample (reference: SumOfSquaresCostLayer)."""
    inputs = [input, label] + ([weight] if weight is not None else [])

    def forward(params, values, ctx):
        x, y = upcast_f32(data_of(values[0])), upcast_f32(data_of(values[1]))
        cost = 0.5 * jnp.sum((x - y) ** 2, axis=-1)
        cost = _per_sample(cost, values[1])
        return _maybe_weight(cost, values, weight is not None)

    return make_node("square_error_cost", forward, inputs, name=name, size=1,
                     layer_attr=layer_attr)


mse_cost = square_error_cost
regression_cost = square_error_cost


@register_layer("multi_binary_label_cross_entropy")
def multi_binary_label_cross_entropy(input, label, name=None, layer_attr=None):
    """Independent per-class sigmoid CE against a multi-hot label
    (reference: MultiBinaryLabelCrossEntropy)."""

    def forward(params, values, ctx):
        p, y = upcast_f32(data_of(values[0])), upcast_f32(data_of(values[1]))
        cost = -(y * jnp.log(p + _EPS) + (1.0 - y) * jnp.log(1.0 - p + _EPS))
        return jnp.sum(cost, axis=-1)

    return make_node("multi_binary_label_cross_entropy", forward,
                     [input, label], name=name, size=1, layer_attr=layer_attr)


@register_layer("cross_entropy_with_selfnorm")
def cross_entropy_with_selfnorm(input, label, softmax_selfnorm_alpha=0.1,
                                name=None, layer_attr=None):
    """CE + alpha * log(Z)^2 self-normalization penalty (reference:
    CostLayer.cpp CrossEntropyWithSelfNorm)."""

    def forward(params, values, ctx):
        p, y = data_of(values[0]), data_of(values[1]).astype(jnp.int32)
        z = jnp.sum(p, axis=-1)
        picked = jnp.take_along_axis(p, y[..., None], axis=-1)[..., 0]
        cost = -jnp.log(picked / (z + _EPS) + _EPS)
        return cost + softmax_selfnorm_alpha * jnp.log(z + _EPS) ** 2

    return make_node("cross_entropy_with_selfnorm", forward, [input, label],
                     name=name, size=1, layer_attr=layer_attr)


@register_layer("rank_cost")
def rank_cost(left, right, label, weight=None, name=None, layer_attr=None):
    """Pairwise ranking cost (reference: RankingCost):
    C = (1-label)*o + log(1 + exp(-o)), o = left - right."""
    inputs = [left, right, label] + ([weight] if weight is not None else [])

    def forward(params, values, ctx):
        o = (data_of(values[0]) - data_of(values[1]))[..., 0]
        y = data_of(values[2]).reshape(o.shape)
        cost = (1.0 - y) * o + jnp.log1p(jnp.exp(-jnp.abs(o))) + jnp.maximum(-o, 0.0)
        return _maybe_weight(cost, values, weight is not None)

    return make_node("rank_cost", forward, inputs, name=name, size=1,
                     layer_attr=layer_attr)


@register_layer("lambda_cost")
def lambda_cost(input, score, NDCG_num=5, max_sort_size=-1, name=None,
                layer_attr=None):
    """LambdaRank NDCG cost over a sequence of documents (reference:
    LambdaCost, CostLayer.cpp). Input is a SequenceBatch of model scores,
    score a SequenceBatch of relevance labels. Produces per-list cost via a
    pairwise lambda weighting with NDCG@NDCG_num gains."""

    def forward(params, values, ctx):
        s_pred, s_rel = values[0], values[1]
        x = data_of(s_pred)[..., 0]        # [B, T]
        rel = data_of(s_rel)[..., 0]       # [B, T]
        mask = s_pred.mask(x.dtype) if is_seq(s_pred) else jnp.ones_like(x)
        # ideal DCG from top-NDCG_num relevances
        gains = (2.0 ** rel - 1.0) * mask
        sorted_gains = -jnp.sort(-gains, axis=-1)
        k = min(NDCG_num, x.shape[-1])
        discounts = 1.0 / jnp.log2(jnp.arange(2, k + 2).astype(x.dtype))
        idcg = jnp.sum(sorted_gains[..., :k] * discounts, axis=-1)
        # pairwise logistic surrogate weighted by |delta gain|
        diff = x[..., :, None] - x[..., None, :]
        gd = gains[..., :, None] - gains[..., None, :]
        pair_mask = mask[..., :, None] * mask[..., None, :]
        loss = jnp.log1p(jnp.exp(-jnp.abs(diff))) + jnp.maximum(-diff, 0.0)
        lam = jnp.abs(gd) * pair_mask * (gd > 0)
        cost = jnp.sum(loss * lam, axis=(-1, -2)) / jnp.maximum(idcg, 1.0)
        return cost

    return make_node("lambda_cost", forward, [input, score], name=name, size=1,
                     layer_attr=layer_attr)


@register_layer("huber_regression_cost")
def huber_regression_cost(input, label, delta=1.0, name=None, layer_attr=None):
    def forward(params, values, ctx):
        x, y = upcast_f32(data_of(values[0])), upcast_f32(data_of(values[1]))
        a = jnp.abs(x - y)
        cost = jnp.where(a <= delta, 0.5 * a * a, delta * (a - 0.5 * delta))
        return jnp.sum(cost, axis=-1)

    return make_node("huber_regression_cost", forward, [input, label],
                     name=name, size=1, layer_attr=layer_attr)


@register_layer("huber_classification_cost")
def huber_classification_cost(input, label, name=None, layer_attr=None):
    """Two-class huber (reference: HuberTwoClassification): label in {0,1}
    mapped to {-1,+1}; cost 0 if y*f>1, (1-y*f)^2 if -1<=y*f<=1, -4*y*f else."""

    def forward(params, values, ctx):
        f = data_of(values[0])[..., 0]
        y = 2.0 * data_of(values[1]).reshape(f.shape).astype(f.dtype) - 1.0
        z = y * f
        cost = jnp.where(z > 1.0, 0.0, jnp.where(z >= -1.0, (1.0 - z) ** 2, -4.0 * z))
        return cost

    return make_node("huber_classification_cost", forward, [input, label],
                     name=name, size=1, layer_attr=layer_attr)


@register_layer("smooth_l1_cost")
def smooth_l1_cost(input, label, coeff=1.0, name=None, layer_attr=None):
    def forward(params, values, ctx):
        x, y = upcast_f32(data_of(values[0])), upcast_f32(data_of(values[1]))
        a = jnp.abs(x - y)
        cost = jnp.where(a < 1.0, 0.5 * a * a, a - 0.5)
        return coeff * jnp.sum(cost, axis=-1)

    return make_node("smooth_l1_cost", forward, [input, label], name=name,
                     size=1, layer_attr=layer_attr)


@register_layer("sum_cost")
def sum_cost(input, name=None, layer_attr=None):
    """Sum of the input as a cost (reference: SumCostLayer)."""

    def forward(params, values, ctx):
        v = values[0]
        x = data_of(v)
        if is_seq(v):  # mask padding before reducing
            x = x * v.mask(x.dtype).reshape(
                v.mask().shape + (1,) * (x.ndim - 2))
        return jnp.sum(x, axis=tuple(range(1, x.ndim)))

    return make_node("sum_cost", forward, [input], name=name, size=1,
                     layer_attr=layer_attr)


# reference SoftBinaryClassCrossEntropy (CostLayer.cpp): identical math to
# the multi-binary-label CE — the label is per-unit probabilities there too
soft_binary_class_cross_entropy = multi_binary_label_cross_entropy
layer_registry.register("soft_binary_class_cross_entropy",
                        multi_binary_label_cross_entropy)


@register_layer("lm_cost")
def lm_cost(input, label, name=None, layer_attr=None):
    """Token cross entropy of logits [B, T, V] against targets [B, T],
    from float32 logits (``layer.lm_head`` gives them so under any compute
    dtype): the mean over the batch's valid positions. Gives a per-row
    vector [B] whose mean is that number (row i holds its tokens' summed
    cost times B over the batch's valid tokens), as the trainer takes the
    mean of every cost."""

    def forward(params, values, ctx):
        logits, y = values
        enforce(is_seq(y), "lm_cost needs sequence targets")
        with jax.named_scope("paddle_tpu.lm_cost"):
            x = upcast_f32(data_of(logits))
            picked = jnp.take_along_axis(
                x, data_of(y)[..., None].astype(jnp.int32), axis=-1)[..., 0]
            mask = y.mask(x.dtype)
            rows = jnp.sum((jax_logsumexp(x)[..., 0] - picked) * mask, axis=1)
            return rows * (rows.shape[0] / jnp.maximum(jnp.sum(mask), 1.0))

    return make_node("lm_cost", forward, [input, label], name=name, size=1,
                     layer_attr=layer_attr)


# Layer types whose non-first inputs are supervision targets (labels,
# scores, weights) — the mixed-precision policy must NOT quantize those
# feeds to bfloat16 (topology._run_nodes keeps them float32 so the f32
# cost math sees full-precision targets).
COST_LAYER_TYPES = frozenset({
    "cross_entropy", "classification_cost", "square_error_cost",
    "multi_binary_label_cross_entropy", "cross_entropy_with_selfnorm",
    "rank_cost", "lambda_cost", "huber_regression_cost",
    "huber_classification_cost", "smooth_l1_cost", "sum_cost",
    "crf", "crf_decoding", "ctc", "warp_ctc", "lm_cost",
})
