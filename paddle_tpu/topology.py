"""Topology: the compiled view of a layer DAG.

Parity with python/paddle/v2/topology.py (which serialized the cost subgraph
to a ModelConfig proto) and with the C++ NeuralNetwork executor
(gserver/gradientmachines/NeuralNetwork.cpp:235): here the "executor" is just
a Python loop over topologically-sorted nodes executed *inside a jax trace*,
so the runtime artifact is a single fused XLA program, not a per-layer
interpreter.
"""

import contextlib
import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.core.sequence import NestedSequenceBatch, SequenceBatch
from paddle_tpu.data_type import DENSE, INDEX, SEQ_NESTED, SEQ_NONE, SEQ_SINGLE, SPARSE_BINARY, SPARSE_FLOAT
from paddle_tpu.graph import Context, LayerNode, topo_sort
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import spans as observe_spans
from paddle_tpu.utils import flags
from paddle_tpu.utils.error import enforce

# sparse slots at/above this dim feed as SparseRows (padded id lists);
# below it they densify at the boundary (cheap at quick_start scale)
flags.define_flag("sparse_feed_threshold", 4096,
                  "sparse_binary/float_vector slots with dim >= this use "
                  "the gather/weighted-sum sparse path instead of dense "
                  "[B, dim] conversion")


def _external(value):
    """Values crossing the topology boundary keep the reference's flat
    NCHW contract: NHWC-resident intermediates (layer/base.py ImageValue)
    materialize their flat view here."""
    from paddle_tpu.layer.base import ImageValue

    return value.flat() if isinstance(value, ImageValue) else value


def _layer_sharding_constraint(value, spec):
    """Lower ExtraAttr(sharding=...) to with_sharding_constraint against
    the active mesh (core.mesh_scope.use_mesh, which a DataParallel step
    enters with its own mesh). No active mesh -> no-op, so sharded configs
    still run single-device (the reference likewise ran parallel_nn
    configs on one GPU by ignoring device attrs)."""
    from jax.sharding import NamedSharding, PartitionSpec
    from paddle_tpu.core.mesh_scope import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return value
    # sharding specs address the flat [B, C*H*W] contract — materialize it
    value = _external(value)
    sharding = NamedSharding(mesh, PartitionSpec(*spec))
    constrain = lambda a: jax.lax.with_sharding_constraint(a, sharding)
    if isinstance(value, (SequenceBatch, NestedSequenceBatch)):
        # the spec addresses the data tensor; lengths stay replicated
        out = type(value).__new__(type(value))
        out.__dict__.update(value.__dict__)
        out.data = constrain(value.data)
        return out
    return constrain(value)


class Topology:
    def __init__(self, outputs):
        if isinstance(outputs, LayerNode):
            outputs = [outputs]
        self.outputs = list(outputs)
        self.nodes = topo_sort(self.outputs)
        self.by_name = {}
        for node in self.nodes:
            enforce(node.name not in self.by_name, "duplicate layer name %r", node.name)
            self.by_name[node.name] = node
        self.data_layers = {
            n.name: n for n in self.nodes if n.layer_type == "data"
        }
        # declaration-ordered (name, InputType) pairs, cached — convert_feed
        # hits this twice per minibatch
        self._data_types = [
            (n.name, n.input_type)
            for n in sorted(self.data_layers.values(),
                            key=lambda n: n.creation_index)
            if getattr(n, "input_type", None) is not None
        ]
        # running-state params (BN moving stats) stay float32 under the
        # mixed-precision policy — their updates bypass the optimizer
        self._state_param_names = {
            name for name, spec in self.param_specs().items()
            if getattr(spec, "is_state", False)
        }
        # label-like data layers: consumed ONLY by cost layers at input
        # position >= 1 (targets/scores/weights). The mixed-precision cast
        # must not quantize supervision signals — the cost math upcasts to
        # f32 and should see full-precision targets.
        from paddle_tpu.layer.cost import COST_LAYER_TYPES

        # reverse edges, kept public: {producer name: [(consumer node,
        # input position)]} — the static analyzers (analyze/
        # topology_check.py) and the label-feed classification below
        # both walk the graph consumer-side
        consumers = {}
        for node in self.nodes:
            for pos, parent in enumerate(node.inputs):
                consumers.setdefault(parent.name, []).append((node, pos))
        self.consumers = consumers
        self._label_feed_names = {
            name for name in self.data_layers
            if consumers.get(name)
            and all(n.layer_type in COST_LAYER_TYPES and pos >= 1
                    for n, pos in consumers[name])
        }

    # -- parameters ---------------------------------------------------------
    def param_specs(self):
        """Merged specs keyed by parameter name; shared params must agree."""
        merged = {}
        for node in self.nodes:
            for spec in node.param_specs:
                prev = merged.get(spec.name)
                if prev is None:
                    merged[spec.name] = spec
                else:
                    enforce(
                        prev.shape == spec.shape,
                        "shared parameter %r shape mismatch: %s vs %s",
                        spec.name,
                        prev.shape,
                        spec.shape,
                    )
        return merged

    def init_params(self, rng=None, dtype=None):
        """Materialize all parameters (cf. Parameter::randomize +
        parameters.create, python/paddle/v2/parameters.py)."""
        if rng is None:
            from paddle_tpu.utils import flags

            rng = jax.random.PRNGKey(flags.get_flag("seed") or 0)
        dtype = dtype_mod.canonical(dtype)
        out = {}
        for i, (name, spec) in enumerate(sorted(self.param_specs().items())):
            out[name] = spec.materialize(jax.random.fold_in(rng, i), dtype)
        return out

    # -- evaluation ---------------------------------------------------------
    def apply(self, params, feed, mode="train", rng=None, outputs=None,
              counts=None):
        """Evaluate the DAG. Returns ({layer_name: value}, state_updates).

        ``feed`` maps data-layer names to already-converted device values
        (see :func:`convert_feed`); ``outputs`` optionally restricts which
        layers' values are returned (all output nodes by default);
        ``counts``, a dict, takes the step's counters whose values are
        data (observe/step_counts.py), traced scalars by name.
        """
        ctx = Context(mode=mode, rng=rng)
        values = self._run_nodes(params, feed, ctx)
        if counts is not None:
            counts.update(ctx.counts)
        if mode == "train":
            # set as the step program is traced: what its recomputed
            # blocks keep for backward, what they hand to later blocks
            # (layer/decoder.py recompute), which key blocks attention
            # visits (gqa_attention), which form its Mamba-1 scans took
            # (mamba1), which form its attention calls took
            # (gqa_attention), what its expert layers hold and which form
            # their row passes took (moe)
            registry = observe_metrics.get_registry()
            for name, value, help_ in (
                    ("recompute_kept_bytes", ctx.recompute_kept_bytes,
                     "bytes a train step keeps inside recomputed blocks "
                     "besides their inputs"),
                    ("shared_across_blocks_bytes",
                     ctx.shared_across_blocks_bytes,
                     "bytes of the values a train step's recomputed blocks "
                     "hand out to later blocks beside the stream"),
                    ("attention_key_blocks_visited",
                     ctx.attention_key_blocks["visited"],
                     "key blocks a train step's attention layers visit, "
                     "summed over their query blocks"),
                    ("attention_key_blocks_possible",
                     ctx.attention_key_blocks["possible"],
                     "key blocks at or under the diagonal of a train "
                     "step's attention layers: visited without a window"),
                    ("selective_scan_fused", ctx.selective_scans["fused"],
                     "Mamba-1 scans of a train step that run as the fused "
                     "Pallas kernels"),
                    ("selective_scan_plain", ctx.selective_scans["plain"],
                     "Mamba-1 scans of a train step that run as plain "
                     "loops"),
                    ("attention_fused", ctx.attentions["fused"],
                     "attention calls of a train step that run as the "
                     "fused Pallas kernels"),
                    ("attention_plain", ctx.attentions["plain"],
                     "attention calls of a train step that run as plain "
                     "key-block scans"),
                    ("moe_experts_held", ctx.moe["held"],
                     "experts each expert layer of a train step holds "
                     "here"),
                    ("moe_experts_total", ctx.moe["total"],
                     "experts each expert layer of a train step routes "
                     "over"),
                    ("moe_rows_bound", ctx.moe["rows_bound"],
                     "rows of the sorted buffers of a train step's expert "
                     "layers, choices x positions x layers: no routing "
                     "overflows them"),
                    ("moe_fused", ctx.moe["fused"],
                     "expert layers of a train step whose row passes run "
                     "as the fused Pallas kernels"),
                    ("moe_plain", ctx.moe["plain"],
                     "expert layers of a train step whose row passes run "
                     "as a gather and ragged_dot")):
                registry.gauge("paddle_tpu_" + name, help=help_
                               + ", of the program traced last").set(value)
        wanted = outputs or [o.name for o in self.outputs]
        return {name: _external(values[name]) for name in wanted}, \
            ctx.state_updates

    def _run_nodes(self, params, feed, ctx):
        cd = dtype_mod.compute_dtype()
        if cd is not None:
            # mixed precision: float32 masters stay outside the trace; the
            # cast here is the gradient boundary (VJP casts grads back to
            # float32), so the optimizer update runs in full precision
            params = {
                k: (dtype_mod.to_compute(v)
                    if k not in self._state_param_names else v)
                for k, v in params.items()
            }
            feed = {k: (v if k in self._label_feed_names
                        else jax.tree.map(dtype_mod.to_compute, v))
                    for k, v in feed.items()}
        values = {}
        for node in self.nodes:
            try:
                if node.layer_type == "data":
                    enforce(node.name in feed,
                            "missing feed for data layer %r", node.name)
                    values[node.name] = node.forward(params,
                                                     [feed[node.name]], ctx)
                else:
                    inputs = [values[p.name] for p in node.inputs]
                    value = node.forward(params, inputs, ctx)
                    spec = getattr(node.extra_attr, "sharding", None)
                    if spec is not None:
                        value = _layer_sharding_constraint(value, spec)
                    values[node.name] = value
            except Exception as exc:
                # layer-stack context on failure (reference: CustomStackTrace
                # gLayerStackTrace, NeuralNetwork.cpp:244-251 — crashes name
                # the offending layer)
                note = "  in layer %r (type %s), inputs: %s" % (
                    node.name, node.layer_type,
                    [p.name for p in node.inputs])
                exc.add_note(note)
                raise
        return values

    def apply_decode(self, params, feed, decode_state, outputs=None):
        """Evaluate the DAG as ONE STREAMING WINDOW of a longer
        sequence: recurrent layers boot from ``decode_state`` (a dict
        ``{layer_name: [carry leaf, ...]}``; missing layers boot from
        zeros as usual) and the final carries come back so the caller
        can thread them into the next window. Test mode (serving).

        Returns ``({layer_name: value}, {layer_name: [carry leaf, ...]})``
        — the continuous-batching decode step (serve/export.py) is built
        on this; reverse recurrent layers and cross-position layers
        cannot stream and fail loudly (layer/recurrent.py,
        serve/export.py streamability check)."""
        ctx = Context(mode="test")
        ctx.decode_state = decode_state if decode_state is not None else {}
        ctx.decode_state_out = {}
        values = self._run_nodes(params, feed, ctx)
        wanted = outputs or [o.name for o in self.outputs]
        return ({name: _external(values[name]) for name in wanted},
                ctx.decode_state_out)

    def apply_all(self, params, feed, mode="test", rng=None):
        """Like apply() but returns every layer's value (debug / tests /
        --show_layer_stat parity)."""
        ctx = Context(mode=mode, rng=rng)
        values = self._run_nodes(params, feed, ctx)
        return {k: _external(v) for k, v in values.items()}, ctx.state_updates

    # -- proto interchange --------------------------------------------------
    def to_proto(self):
        """Serialize to a ModelConfig proto message — the self-contained
        deployment artifact (reference: python/paddle/v2/topology.py:64
        Topology.proto(); consumed by merge_model + capi without user
        Python)."""
        from paddle_tpu.proto.interchange import topology_to_proto

        return topology_to_proto(self)

    @classmethod
    def from_proto(cls, msg, opaque_builders=None):
        """Rebuild a Topology from a ModelConfig proto (bytes or message)
        without executing any user config code. Opaque layers (closure-built,
        e.g. recurrent_group steps) need ``opaque_builders`` — see
        paddle_tpu/proto/interchange.py."""
        from paddle_tpu.proto.interchange import topology_from_proto

        return cls(topology_from_proto(msg, opaque_builders))

    def data_types(self):
        """[(name, InputType)] for feeder construction, in *declaration
        order* — the default feeding maps reader tuple columns to data layers
        in the order the user created them (v2 Topology.data_type parity;
        alphabetical order would silently swap e.g. ('word', 'label'))."""
        return self._data_types


# the HostBuffers of the DeviceFeeder that is converting on this thread
_owner = threading.local()


@contextlib.contextmanager
def recycling_into(buffers):
    """Inside this scope, on this thread, :func:`convert_feed` assembles
    fixed-shape dense and index columns into ``buffers`` (a
    ``data.feeder.HostBuffers``), and places them where ``buffers`` says:
    straight onto its owner's mesh, if it has one. Only an owner that
    knows when a batch's bytes have left the host may enter it: the
    DeviceFeeder's producer. A
    scope and not an argument of ``convert_feed``, so that the pool
    reaches the real ``convert_feed`` through whatever a caller has put in
    its place (a wrapper of its four arguments, as the benchmark's
    stand-ins are), and no caller without an owner passes one."""
    previous = getattr(_owner, "buffers", None)
    _owner.buffers = buffers
    try:
        yield
    finally:
        _owner.buffers = previous


def convert_feed(topology, data_batch, feeding=None, max_len=None):
    """Convert a host minibatch (list of tuples, v2 reader convention) into
    device-ready feed values according to each data layer's InputType.

    Parity with py_paddle DataProviderConverter (reference:
    paddle/py_paddle/dataprovider_converter.py): dense slots become [B, dim]
    arrays, index slots int32 [B], sequence slots SequenceBatch, nested
    slots NestedSequenceBatch. Sparse slots densify below
    ``sparse_feed_threshold`` dims and feed as :class:`SparseRows` (padded
    id lists; fc consumes them via gather/weighted-sum) at or above it —
    the reference's million-dim sparse FC capability.

    ``max_len`` (length-bucketed batching, paddle_tpu.data.bucketing):
    pad single-level sequence slots to exactly this width instead of the
    batch-max bucket — one jit cache entry per bucket. Default None is
    the historical behavior, bit for bit.

    Inside a DeviceFeeder's :func:`recycling_into` scope the fixed-shape
    dense and index columns are assembled into its recycled host memory;
    every other caller gets a fresh ``np.asarray`` per column, bit for
    bit.
    """
    buffers = getattr(_owner, "buffers", None)
    names = [name for name, _ in topology.data_types()]
    if feeding is None:
        feeding = {name: i for i, name in enumerate(names)}
    feed = {}
    for name, itype in topology.data_types():
        idx = feeding[name]
        for row in data_batch:
            enforce(
                idx < len(row),
                "sample tuple of length %d has no column %d for data layer %r "
                "(feeding=%r)", len(row), idx, name, feeding)
        col = [row[idx] for row in data_batch]
        assemble = None if buffers is None else \
            functools.partial(buffers.assemble, name)
        feed[name] = convert_column(col, itype, max_len=max_len,
                                    assemble=assemble)
    return feed


def convert_column(col, itype, max_len=None, assemble=None):
    """One column of rows to its feed value. ``assemble(col, dtype)``,
    where given, copies the rows of a fixed-shape column into a host
    array its owner recycles, or returns None for rows it does not take
    (``data.feeder.HostBuffers.assemble``)."""
    if itype.seq_type == SEQ_NONE:
        if itype.value_type in (DENSE, INDEX):
            dtype = np.float32 if itype.value_type == DENSE else np.int32
            host = None if assemble is None else assemble(col, dtype)
            if host is None:
                return _place(np.asarray(col, dtype=dtype))
            return _place(host, recycled=True)
        if itype.value_type in (SPARSE_BINARY, SPARSE_FLOAT):
            if itype.dim >= flags.get_flag("sparse_feed_threshold"):
                # true sparse path: padded id lists + gather/weighted-sum
                # matmul instead of [B, dim] densification — the reference's
                # million-dim sparse FC capability (SparseRowMatrix.h:29)
                from paddle_tpu.core.sparse import SparseRows

                return SparseRows.from_rows(
                    col, itype.dim,
                    with_values=itype.value_type == SPARSE_FLOAT)
            return _place(_densify(col, itype))
    elif itype.seq_type == SEQ_SINGLE:
        if itype.value_type == DENSE:
            seqs = [np.asarray(s, dtype=np.float32) for s in col]
        elif itype.value_type == INDEX:
            seqs = [np.asarray(s, dtype=np.int32) for s in col]
        else:
            seqs = [_densify(s, itype) for s in col]
        return SequenceBatch.from_sequences(seqs, max_len=max_len)
    elif itype.seq_type == SEQ_NESTED:
        if itype.value_type == DENSE:
            nested = [[np.asarray(s, dtype=np.float32) for s in subs] for subs in col]
        elif itype.value_type == INDEX:
            nested = [[np.asarray(s, dtype=np.int32) for s in subs] for subs in col]
        else:
            nested = [[_densify(s, itype) for s in subs] for subs in col]
        return NestedSequenceBatch.from_nested(nested)
    raise TypeError("unsupported input type %r" % (itype,))


def _place(host, recycled=False):
    """Hand an assembled host array to the device, as a ``feed_place``
    span: what is left of the enclosing ``feed_convert`` is host
    assembly. Onto one device, but for a ``recycled`` array whose owner
    (the ``HostBuffers`` of :func:`recycling_into`) knows a mesh: it
    places each device's rows straight from the host array, so no batch
    crosses one device on its way to four. A ``recycled`` array will be
    written again, so what is placed from it must be a copy: the CPU
    platform wraps a suitably aligned numpy array instead of copying it
    (alignment decides, and ``device_put(may_alias=False)`` does not
    reach numpy inputs on jax 0.9), and there the placed array is copied
    on the device(s)."""
    with observe_spans.span("feed_place"):
        owner = getattr(_owner, "buffers", None) if recycled else None
        placed = None if owner is None else owner.place_sharded(host)
        if placed is None:
            placed = jnp.asarray(host)
        if recycled and _lives_in(placed, host):
            placed = jnp.copy(placed)
        return placed


def _lives_in(placed, host):
    """Whether any shard of a placed array lies inside ``host``'s own
    bytes. Only a device whose memory is the host's can; a TPU's transfer
    is never asked (its pointer would wait for the bytes to land)."""
    start = host.ctypes.data
    return any(
        shard.device.platform == "cpu"
        and start <= shard.data.unsafe_buffer_pointer() < start + host.nbytes
        for shard in placed.addressable_shards)


def _densify(rows, itype):
    """sparse ids / (id, value) pairs -> dense float32 rows.

    Duplicate ids SUM (the natural linear-algebra reading, and what the
    SparseRows gather/weighted-sum path computes) so results agree on
    both sides of sparse_feed_threshold; duplicate ids in one row are
    malformed input either way."""
    if isinstance(rows, np.ndarray) and rows.ndim == 2:
        return rows.astype(np.float32)
    first = rows[0] if len(rows) else None
    is_batch = isinstance(first, (list, tuple, np.ndarray))
    batch = rows if is_batch else [rows]
    out = np.zeros((len(batch), itype.dim), dtype=np.float32)
    for i, row in enumerate(batch):
        for item in row:
            if isinstance(item, (tuple, list)):
                idx, val = item
                out[i, int(idx)] += float(val)
            else:
                out[i, int(item)] += 1.0
    return out if is_batch else out[0]
