"""Lazy layer graph traced into one pure, jit-compiled XLA function.

This replaces the reference's whole config->engine pipeline: the Python DSL
built a ModelConfig proto (reference: python/paddle/trainer/config_parser.py,
parse_config :3616) which C++ `GradientMachine::create` turned into a vector
of `Layer` objects executed one virtual call at a time
(gserver/gradientmachines/NeuralNetwork.cpp:235-285, Layer.h:376-452), with a
hand-written backward per layer. Here each `paddle_tpu.layer.*` call creates a
:class:`LayerNode` — a named DAG node carrying parameter specs and a pure
``forward(params, inputs, ctx)`` — and :class:`paddle_tpu.topology.Topology`
evaluates the DAG inside ``jax.jit``, so XLA fuses the entire
forward+backward+update into a single TPU program and jax.grad supplies every
backward (GradOpBuilder parity, reference: paddle/framework/grad_op_builder.cc).

Values flowing along edges are jnp arrays, SequenceBatch, or
NestedSequenceBatch. Parameters are keyed by *parameter name* (not layer
name) so ParamAttr(name=...) shares weights between layers exactly like the
reference.
"""

import itertools
import threading

import jax
import jax.numpy as jnp

from paddle_tpu.attr import ExtraAttr, ParamAttr
from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.observe import step_counts
from paddle_tpu.utils.error import enforce, layer_scope

_name_lock = threading.Lock()
_name_counters = {}
_creation_counter = itertools.count()


# Auto-name templates aligned with the reference's wrap_name_default tags
# (trainer_config_helpers/layers.py) so configs, checkpoints, and the
# protostr cross-check (tests/test_config_corpus.py) agree on generated
# layer names: e.g. img_conv -> "__conv_0__", pooling -> "__seq_pooling_0__".
# Keys are OUR layer-type tags; anything absent keeps its tag verbatim.
_REF_NAME_TAGS = {
    "conv_layer": "conv",
    "img_pool": "pool",
    "batch_norm_layer": "batch_norm",
    "img_cmrnorm": "crmnorm",
    "embedding_layer": "embedding",
    "classification_cost": "cost",
    "square_error_cost": "mse_cost",
    "huber_classification_cost": "huber_cost",
    "grumemory": "gru",
    "trans": "trans_layer",
    "expand": "expand_layer",
    "hsigmoid_layer": "hsigmoid",
    "maxout": "maxout_layer",
    "block_expand": "block_expand_layer",
    "multiplex": "multiplex_layer",
    "interpolation": "interpolation_layer",
    "power": "power_layer",
    "scaling": "scaling_layer",
    "sum_to_one_norm": "sum_to_one_norm_layer",
    "conv_shift": "conv_shift_layer",
    "linear_comb": "linear_comb_layer",
    "slope_intercept": "slope_intercept_layer",
    "addto_layer": "addto",
    "repeat": "repeat_layer",
    "seq_concat": "seqconcat",
    "seq_reshape": "seqreshape",
    "pooling": "seq_pooling",
    "sampling_id": "sampling_id_layer",
    "bilinear_interp": "bilinear_interp_layer",
    "ctc": "ctc_layer",
}


def auto_name(layer_type):
    tag = _REF_NAME_TAGS.get(layer_type, layer_type)
    with _name_lock:
        idx = _name_counters.get(tag, 0)
        _name_counters[tag] = idx + 1
    return "__%s_%d__" % (tag, idx)


def reset_name_counters():
    with _name_lock:
        _name_counters.clear()


class ParamSpec:
    """Declaration of one named parameter buffer (cf. ParameterConfig proto +
    Parameter, reference: paddle/parameter/Parameter.h:46)."""

    __slots__ = ("name", "shape", "initializer", "attr", "dtype", "is_state",
                 "sharding_hint")

    def __init__(self, name, shape, initializer, attr=None, dtype=None, is_state=False):
        self.name = name
        self.shape = tuple(int(s) for s in shape)
        self.initializer = initializer
        self.attr = attr or ParamAttr()
        self.dtype = dtype
        self.is_state = is_state  # non-trainable running state (e.g. BN stats)
        self.sharding_hint = None  # e.g. ("vocab", mesh_axis) for EP tables

    def materialize(self, rng, default_dtype):
        dtype = self.dtype or default_dtype
        return self.initializer(rng, self.shape, dtype)

    def __repr__(self):
        return "ParamSpec(%s, shape=%s%s)" % (
            self.name,
            self.shape,
            ", state" if self.is_state else "",
        )


class Context:
    """Per-trace evaluation context: train/test mode, RNG stream for
    stochastic layers, and a sink for running-state updates (BN moving
    stats) and auxiliary observations."""

    def __init__(self, mode="train", rng=None):
        self.mode = mode
        self.rng = rng
        self._rng_counter = itertools.count()
        self.state_updates = {}
        self.aux = {}
        # layer/decoder.py recompute: the names the block being traced
        # keeps for backward, and the bytes this trace's blocks keep
        self.recompute_keeping = frozenset()
        self.recompute_kept_bytes = 0
        # the bytes of the values this trace's blocks hand out to later
        # blocks beside the stream, and the key blocks its attention
        # layers visit of those at or under the diagonal (layer/decoder.py)
        self.shared_across_blocks_bytes = 0
        self.attention_key_blocks = {"visited": 0, "possible": 0}
        # this trace's Mamba-1 scans by the form they took
        # (ops/ssm.py selective_scan_form)
        self.selective_scans = {"fused": 0, "plain": 0}
        # this trace's attention calls by the form they took
        # (ops/attention.py attention_form)
        self.attentions = {"fused": 0, "plain": 0}
        # this trace's expert layers: the experts each holds of how many,
        # the rows of their sorted buffers, all layers, and the layers by
        # the form their row passes took (layer.moe, ops/moe.py
        # experts_form)
        self.moe = {"held": 0, "total": 0, "rows_bound": 0, "fused": 0,
                    "plain": 0}
        # the step's counters whose values are data, folded by name
        # (observe/step_counts.py)
        self.counts = {}
        # streaming-decode carry threading (serve/export.py decode step):
        # when ``decode_state`` is a dict, recurrent layers read their
        # initial carry from it (decode_state[layer_name] = [leaf, ...];
        # missing = zeros) and write their final carry to
        # ``decode_state_out`` — the serving scheduler threads the carry
        # across window dispatches so sequences stream through a
        # fixed-capacity slot matrix (docs/serving.md).
        self.decode_state = None
        self.decode_state_out = None

    @property
    def is_train(self):
        return self.mode == "train"

    def count(self, name, value):
        """Folds a traced scalar into the step's counter ``name`` as
        ``step_counts.COUNTS`` says: summed or the largest."""
        fold = {"sum": jnp.add, "max": jnp.maximum}[
            step_counts.COUNTS[name][0]]
        self.counts[name] = fold(self.counts[name], value) \
            if name in self.counts else value

    def next_rng(self):
        enforce(
            self.rng is not None,
            "this network uses stochastic layers (dropout/sampling); pass rng=",
        )
        return jax.random.fold_in(self.rng, next(self._rng_counter))

    def group_rng(self, key):
        """Stable per-group RNG base: a recurrent_group and its get_output
        siblings re-run the same scan and must draw IDENTICAL streams (so
        XLA CSE merges them and stochastic layers stay consistent)."""
        cache = getattr(self, "_group_rng", None)
        if cache is None:
            cache = self._group_rng = {}
        if key not in cache:
            cache[key] = None if self.rng is None else self.next_rng()
        return cache[key]

    def update_state(self, name, value):
        self.state_updates[name] = value

    def observe(self, name, value):
        self.aux[name] = value


class LayerNode:
    """One node of the layer DAG. ``forward_fn(params, inputs, ctx)`` is pure
    in (params, inputs) given a ctx; ``size`` is the feature width exposed to
    downstream layers (cf. LayerConfig.size, proto/ModelConfig.proto:314)."""

    def __init__(
        self,
        layer_type,
        forward_fn,
        inputs=(),
        name=None,
        size=0,
        param_specs=(),
        extra_attr=None,
        seq_level=None,
    ):
        self.layer_type = layer_type
        self.name = name or auto_name(layer_type)
        self.inputs = list(inputs)
        self.size = size
        self.param_specs = list(param_specs)
        self.extra_attr = extra_attr or ExtraAttr()
        self.seq_level = seq_level  # None=unknown, 0=plain, 1=seq, 2=nested
        self.build_spec = None  # (type, bound ctor args) via register_layer
        self._forward_fn = forward_fn
        # declaration order: the default feeding maps reader tuple columns to
        # data layers in the order the user declared them (v2 semantics)
        self.creation_index = next(_creation_counter)

    def forward(self, params, input_values, ctx):
        with layer_scope(self.name):
            out = self._forward_fn(params, input_values, ctx)
        return out

    # graph sugar (v1 layer_math parity, reference:
    # trainer_config_helpers/math.py — +,-,* on LayerOutput): layer+layer
    # builds addto, layer±const slope_intercept, layer*const a scale,
    # layer*layer a row-wise scaling when either side is width-1.
    def __add__(self, other):
        from paddle_tpu import layer as L

        if isinstance(other, LayerNode):
            a, b = self, other
            if a.size != b.size:
                # width-1 operand broadcasts (reference layer_math.add
                # repeats it; addto's elementwise sum broadcasts [B,1]
                # natively, so no repeat node is needed)
                if a.size == 1:
                    a, b = b, a
                enforce(b.size == 1, "layer + layer needs equal sizes or a "
                        "width-1 side (%s vs %s)", a.size, b.size)
            return L.addto(input=[a, b])
        return L.slope_intercept(input=self, intercept=float(other))

    __radd__ = __add__

    def __sub__(self, other):
        from paddle_tpu import layer as L

        if isinstance(other, LayerNode):
            return L.addto(
                input=[self, L.slope_intercept(input=other, slope=-1.0)])
        return L.slope_intercept(input=self, intercept=-float(other))

    def __rsub__(self, other):
        from paddle_tpu import layer as L

        return L.slope_intercept(input=self, slope=-1.0,
                                 intercept=float(other))

    def __mul__(self, other):
        from paddle_tpu import layer as L

        if isinstance(other, LayerNode):
            if self.size == 1:
                return L.scaling(input=other, weight=self)
            if other.size == 1:
                return L.scaling(input=self, weight=other)
            raise TypeError(
                "layer * layer needs one side of width 1 (reference "
                "layer_math.mul contract); use dotmul for elementwise")
        return L.slope_intercept(input=self, slope=float(other))

    __rmul__ = __mul__

    def __repr__(self):
        return "LayerNode(%s:%s, size=%d)" % (self.name, self.layer_type, self.size)


LayerOutput = LayerNode  # v2-API name parity (python/paddle/v2 LayerOutput)


def topo_sort(outputs):
    """Post-order topological sort of the DAG reachable from ``outputs``."""
    order, seen = [], set()
    on_path = set()

    def visit(node):
        if id(node) in seen:
            return
        enforce(id(node) not in on_path, "cycle in layer graph at %r", node.name)
        on_path.add(id(node))
        for parent in node.inputs:
            visit(parent)
        on_path.discard(id(node))
        seen.add(id(node))
        order.append(node)

    for out in outputs:
        visit(out)
    return order
