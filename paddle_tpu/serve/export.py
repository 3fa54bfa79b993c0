"""Export side of the AOT model-bundle format (docs/serving.md).

``export_bundle`` AOT-lowers the inference forward of a topology with
``jax.jit(...)`` + ``jax.export`` once per batch bucket, and writes a
self-contained bundle directory:

* ``manifest.json``  — versioned specs: inputs/outputs (names, kinds,
  dims, dtypes), the exported batch buckets, seq_len, framework/jax
  versions, export platforms.
* ``params.npz``     — the packed parameter payload (weights are call
  arguments of the exported function, not baked-in constants, so the
  per-bucket artifacts stay small and params remain swappable).
* ``fwd_b{B}.jaxexp``— one serialized StableHLO artifact per bucket.

The load side (:mod:`paddle_tpu.serve.bundle`) replays the artifacts
without importing any of the graph machinery this module uses — the
graph is built here, at export time, never again.
"""

import json
import os
import time

import numpy as np

from paddle_tpu.data_type import (DENSE, INDEX, SEQ_NONE, SEQ_SINGLE,
                                  SPARSE_BINARY, SPARSE_FLOAT)
from paddle_tpu.serve.bundle import BUNDLE_FORMAT, MANIFEST_NAME, Bundle
from paddle_tpu.utils.error import enforce

DEFAULT_BATCH_SIZES = (1, 8, 32)
DEFAULT_SEQ_LEN = 64
DEFAULT_DECODE_WINDOW = 8


class _InputSpec:
    __slots__ = ("name", "kind", "dim", "dtype")

    def __init__(self, name, kind, dim, dtype):
        self.name = name
        self.kind = kind
        self.dim = dim
        self.dtype = dtype

    def as_manifest(self):
        return {"name": self.name, "kind": self.kind, "dim": self.dim,
                "dtype": self.dtype}


def _input_specs(topology):
    """Manifest input specs from the topology's data layers. Sparse slots
    below the sparse_feed_threshold feed as densified [B, dim] rows (the
    same boundary convert_feed uses), so they export as ``dense``; the
    padded-id SparseRows path has no fixed exportable shape yet."""
    from paddle_tpu.utils import flags

    specs = []
    for name, itype in topology.data_types():
        if itype.seq_type == SEQ_NONE:
            if itype.value_type == DENSE:
                specs.append(_InputSpec(name, "dense", itype.dim, "float32"))
            elif itype.value_type == INDEX:
                specs.append(_InputSpec(name, "index", itype.dim, "int32"))
            elif itype.value_type in (SPARSE_BINARY, SPARSE_FLOAT):
                enforce(
                    itype.dim < flags.get_flag("sparse_feed_threshold"),
                    "input %r: sparse slots at/above sparse_feed_threshold "
                    "(dim %d) feed as SparseRows, which has no fixed "
                    "exportable shape; densify or lower the threshold",
                    name, itype.dim)
                specs.append(_InputSpec(name, "dense", itype.dim, "float32"))
            else:
                raise ValueError("input %r: unexportable value type %r"
                                 % (name, itype.value_type))
        elif itype.seq_type == SEQ_SINGLE:
            if itype.value_type == INDEX:
                specs.append(_InputSpec(name, "seq_index", itype.dim,
                                        "int32"))
            elif itype.value_type == DENSE:
                specs.append(_InputSpec(name, "seq_dense", itype.dim,
                                        "float32"))
            else:
                raise ValueError(
                    "input %r: sparse sequence slots are not exportable"
                    % name)
        else:
            raise ValueError(
                "input %r: nested-sequence slots are not exportable yet"
                % name)
    return specs


def _make_forward(topology, specs, out_names, quantization=None):
    """The function that gets AOT-lowered: (params, flat_inputs) ->
    {output_name: array}. Rebuilds SequenceBatch values from the flat
    ids+lengths pairs at trace time; test-mode forward (dropout off, BN
    moving stats from params). With ``quantization`` (the manifest
    block from serve/quantize.py) the int8 weight payload dequantizes
    INSIDE the traced program — non-native entries here, native ones in
    their consuming layer — so XLA fuses ``w_int8 * scale`` into the
    dot and the HBM-resident weights stay int8."""
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.serve.quantize import dequant_for_trace

    def forward(params, flat):
        if quantization is not None:
            params = dequant_for_trace(params, quantization)
        feed = {}
        for spec in specs:
            if spec.kind in ("seq_index", "seq_dense"):
                feed[spec.name] = SequenceBatch(flat[spec.name],
                                                flat[spec.name + ":lens"])
            else:
                feed[spec.name] = flat[spec.name]
        values, _ = topology.apply(params, feed, mode="test")
        out = {}
        for name in out_names:
            val = values[name]
            out[name] = val.data if hasattr(val, "lengths") else val
        return out

    return forward


def _check_streamable(topology, specs):
    """A topology can stream through the decode step only when nothing
    mixes information ACROSS time positions except resettable recurrent
    carries: the cross-position layer set is DERIVED from the layer
    sources by the static analyzer (exactly the set that must refuse
    packed input — streaming windows are the serving twin of packing),
    and every input must be a sequence the scheduler can slice
    window-by-window. Reverse recurrent layers additionally refuse at
    trace time (layer/recurrent.py _run_seq_scan)."""
    from paddle_tpu.analyze.topology_check import (
        packed_rejecting_node_types)

    blocked = packed_rejecting_node_types()
    for node in topology.nodes:
        enforce(
            node.layer_type not in blocked,
            "topology is not streamable: layer %r (type %s) mixes "
            "values across time positions, so a decode window cannot "
            "reproduce the full-sequence forward; continuous batching "
            "needs a per-position head over resettable recurrent layers",
            node.name, node.layer_type)
    for spec in specs:
        enforce(
            spec.kind in ("seq_index", "seq_dense"),
            "decode export needs every input to be a sequence slot "
            "(got %r for input %r): non-sequence inputs have no "
            "per-timestep slice to stream", spec.kind, spec.name)


def _make_decode_step(topology, specs, out_names, quantization=None):
    """The continuous-batching decode step that gets AOT-lowered once
    per slot capacity: ``(params, carry, flat) -> (carry', outputs)``
    over a fixed ``[slots, window]`` matrix.

    ``flat`` carries one data window per sequence input plus two
    shared control vectors: ``lens`` [S] i32 — valid steps this window
    per slot (0 = idle slot, carry passes through under the mask) — and
    ``reset`` [S] f32 — 1 where a freshly admitted sequence must not see
    the retired occupant's carry (the serving twin of the ``reset_bt``
    segment machinery; numeric safety first: the carry is zeroed BEFORE
    the cells run). ``carry`` is ``{recurrent_layer_name: [leaf, ...]}``
    with leading dim ``slots`` on every leaf."""
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.serve.quantize import dequant_for_trace

    def step(params, carry, flat):
        if quantization is not None:
            params = dequant_for_trace(params, quantization)
        reset = flat["reset"]
        lens = flat["lens"]
        keep = 1.0 - reset
        carry = {
            layer: [leaf * keep.reshape(
                        (-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
                    for leaf in leaves]
            for layer, leaves in carry.items()}
        feed = {spec.name: SequenceBatch(flat[spec.name], lens)
                for spec in specs}
        values, state_out = topology.apply_decode(params, feed, carry)
        outs = {}
        for name in out_names:
            val = values[name]
            enforce(hasattr(val, "lengths"),
                    "decode output %r is not a per-timestep sequence; "
                    "continuous decode emits one output row per "
                    "timestep (take the head's sequence output, not a "
                    "pooled value)", name)
            outs[name] = val.data
        return state_out, outs

    return step


def export_bundle(output_layer, parameters, out_dir,
                  batch_sizes=DEFAULT_BATCH_SIZES, seq_len=None,
                  name=None, platforms=None, decode_slots=None,
                  decode_window=None, quantize=None):
    """AOT-export the inference forward over ``output_layer`` as a
    versioned bundle directory; returns the manifest dict.

    ``batch_sizes`` are the exported batch buckets (the serving engine
    pads each dynamic batch up to the nearest one). ``seq_len`` fixes
    the padded time dimension of sequence inputs (required only when the
    model has any; defaults to 64). ``platforms`` optionally lowers for
    several backends at once (e.g. ``("cpu", "tpu")``) so a bundle
    exported on a CPU host serves on the chip.

    ``decode_slots`` additionally exports a **continuous-batching decode
    step** per slot capacity (docs/serving.md "Continuous batching"):
    one jitted ``[slots, window]`` window of the same forward with the
    recurrent carries as explicit, DONATED arguments, so the serving
    scheduler (serve/scheduler.py) can admit and retire sequences
    between dispatches instead of padding every request to ``seq_len``.
    Requires a streamable topology (per-position layers + forward
    recurrent layers; checked). ``decode_window`` is the timesteps per
    dispatch (default ``DEFAULT_DECODE_WINDOW`` = 8).

    ``quantize="int8"`` writes a **quantized bundle** (docs/serving.md
    "Quantized bundles"): matmul/conv weights become per-output-channel
    symmetric int8 with f32 scale sidecars in ``params.npz`` (biases,
    norm/embedding tables and recurrent cells stay fp; decode carries
    untouched), the exported programs dequantize inside the jit so HBM
    weight traffic drops ~4x, the manifest records the ``quantization``
    block, and ``hbm_estimate_bytes`` shrinks accordingly — which
    raises ``cli serve --replicas auto`` under PADDLE_TPU_HBM_BUDGET.
    """
    import jax
    from jax import export as jax_export

    from paddle_tpu.graph import LayerNode
    from paddle_tpu.topology import Topology

    outputs = ([output_layer] if isinstance(output_layer, LayerNode)
               else list(output_layer))
    topology = Topology(outputs)
    out_names = [o.name for o in outputs]
    specs = _input_specs(topology)
    enforce(bool(specs), "topology has no data layers to feed")
    batch_sizes = sorted({int(b) for b in batch_sizes})
    enforce(bool(batch_sizes) and batch_sizes[0] >= 1,
            "batch_sizes must be positive, got %r", batch_sizes)
    has_seq = any(s.kind in ("seq_index", "seq_dense") for s in specs)
    if has_seq:
        seq_len = int(seq_len or DEFAULT_SEQ_LEN)
    else:
        seq_len = None

    quantization = None
    if quantize:
        enforce(quantize == "int8",
                "unsupported quantize scheme %r (only 'int8')", quantize)
        from paddle_tpu.serve.quantize import quantize_parameters

        # the quantized Parameters REPLACE the fp payload from here on:
        # the npz, the exported call signatures and the HBM estimate
        # all see the int8 tensors + scale sidecars
        parameters, quantization = quantize_parameters(parameters,
                                                       topology)

    params = {k: np.asarray(parameters.get(k)) for k in parameters.names()}
    param_structs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in params.items()}
    forward = _make_forward(topology, specs, out_names,
                            quantization=quantization)
    jitted = jax.jit(forward)
    export_kwargs = {}
    if platforms is not None:
        export_kwargs["platforms"] = tuple(platforms)

    os.makedirs(out_dir, exist_ok=True)
    buckets = []
    out_specs = None
    exported_platforms = None
    for batch in batch_sizes:
        flat_structs = {}
        for spec in specs:
            shape = _feed_shape(spec, batch, seq_len)
            flat_structs[spec.name] = jax.ShapeDtypeStruct(
                shape, np.dtype(spec.dtype))
            if spec.kind in ("seq_index", "seq_dense"):
                flat_structs[spec.name + ":lens"] = jax.ShapeDtypeStruct(
                    (batch,), np.int32)
        exported = jax_export.export(jitted, **export_kwargs)(
            param_structs, flat_structs)
        artifact = "fwd_b%d.jaxexp" % batch
        with open(os.path.join(out_dir, artifact), "wb") as fh:
            fh.write(exported.serialize())
        buckets.append({"batch": batch, "artifact": artifact})
        exported_platforms = list(exported.platforms)
        if out_specs is None:
            out_avals = jax.tree_util.tree_unflatten(
                exported.out_tree, list(exported.out_avals))
            out_specs = [
                {"name": n,
                 "dtype": str(np.dtype(out_avals[n].dtype)),
                 "shape_suffix": [int(d) for d in out_avals[n].shape[1:]]}
                for n in out_names]

    decode_manifest = None
    if decode_slots:
        _check_streamable(topology, specs)
        window = int(decode_window or DEFAULT_DECODE_WINDOW)
        enforce(window >= 1, "decode_window must be >= 1, got %r", window)
        step = _make_decode_step(topology, specs, out_names,
                                 quantization=quantization)
        slot_sizes = sorted({int(s) for s in decode_slots})
        enforce(slot_sizes[0] >= 1,
                "decode_slots must be positive, got %r", decode_slots)
        carry_spec = None
        decode_buckets = []
        for slots in slot_sizes:
            flat_structs = {
                "lens": jax.ShapeDtypeStruct((slots,), np.int32),
                "reset": jax.ShapeDtypeStruct((slots,), np.float32),
            }
            for spec in specs:
                shape = ((slots, window) if spec.kind == "seq_index"
                         else (slots, window, spec.dim))
                flat_structs[spec.name] = jax.ShapeDtypeStruct(
                    shape, np.dtype(spec.dtype))

            def probe(params, flat, _specs=specs):
                from paddle_tpu.core.sequence import SequenceBatch
                from paddle_tpu.serve.quantize import dequant_for_trace

                if quantization is not None:
                    params = dequant_for_trace(params, quantization)
                feed = {s.name: SequenceBatch(flat[s.name], flat["lens"])
                        for s in _specs}
                _, st = topology.apply_decode(params, feed, {})
                return st

            state_structs = jax.eval_shape(probe, param_structs,
                                           flat_structs)
            enforce(bool(state_structs),
                    "decode export found no recurrent carries — a "
                    "carry-free topology has nothing to stream; serve "
                    "it through the ordinary batch buckets")
            # the carry is donated: slot state never round-trips the
            # host and the scheduler's step is a true in-place update
            exported_step = jax_export.export(
                jax.jit(step, donate_argnums=(1,)), **export_kwargs)(
                    param_structs, state_structs, flat_structs)
            artifact = "step_s%d.jaxexp" % slots
            with open(os.path.join(out_dir, artifact), "wb") as fh:
                fh.write(exported_step.serialize())
            decode_buckets.append({"slots": slots, "artifact": artifact})
            if carry_spec is None:
                carry_spec = {
                    layer: [{"shape_suffix": [int(d) for d in
                                              leaf.shape[1:]],
                             "dtype": str(np.dtype(leaf.dtype))}
                            for leaf in leaves]
                    for layer, leaves in state_structs.items()}
        decode_manifest = {"window": window, "slots": decode_buckets,
                           "carry": carry_spec}

    params_file = "params.npz"
    with open(os.path.join(out_dir, params_file), "wb") as fh:
        parameters.to_npz(fh)

    # static HBM footprint of the largest exported program (params +
    # largest-bucket feed + forward activations, docs/analyze.md): the
    # number the sharded-bundle work sizes against, recorded in the
    # manifest and checked against PADDLE_TPU_HBM_BUDGET at export time
    # — a bundle that cannot fit its serving chip should fail the build,
    # not the first /readyz probe
    from paddle_tpu.analyze import topology_check as _topology_check

    seq_pads = {s.name: seq_len for s in specs
                if s.kind in ("seq_index", "seq_dense")}
    hbm_est = _topology_check.estimate_hbm_bytes(
        topology, rows=batch_sizes[-1], seq_pad=seq_pads,
        parameters=parameters, mode="infer")
    budget = _topology_check.hbm_budget_bytes()
    if budget is not None and hbm_est["total"] > budget:
        from paddle_tpu.utils.logger import logger

        logger.warning(
            "export_bundle: static HBM estimate %s for the largest "
            "bucket (batch=%d) exceeds PADDLE_TPU_HBM_BUDGET=%s — the "
            "bundle will not fit its serving chip; export smaller "
            "buckets or wait for the sharded-bundle path",
            _topology_check._fmt_bytes(hbm_est["total"]),
            batch_sizes[-1], _topology_check._fmt_bytes(budget))

    from paddle_tpu.core import dtype as dtype_mod

    cd = dtype_mod.compute_dtype()
    manifest = {
        "format": BUNDLE_FORMAT,
        "version": 1,
        "name": name or out_names[0],
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "framework": {
            "paddle_tpu": _paddle_tpu_version(),
            "jax": jax.__version__,
        },
        "platforms": exported_platforms,
        "compute_dtype": str(np.dtype(cd)) if cd is not None else "float32",
        "inputs": [s.as_manifest() for s in specs],
        "outputs": out_specs,
        "seq_len": seq_len,
        "buckets": buckets,
        "params_file": params_file,
        "hbm_estimate_bytes": int(hbm_est["total"]),
    }
    if quantization is not None:
        manifest["quantization"] = quantization
    if decode_manifest is not None:
        manifest["decode"] = decode_manifest
    with open(os.path.join(out_dir, MANIFEST_NAME), "w") as fh:
        json.dump(manifest, fh, indent=2)
    return manifest


def _feed_shape(spec, batch, seq_len):
    if spec.kind == "dense":
        return (batch, spec.dim)
    if spec.kind == "index":
        return (batch,)
    if spec.kind == "seq_index":
        return (batch, seq_len)
    if spec.kind == "seq_dense":
        return (batch, seq_len, spec.dim)
    raise ValueError("unknown input kind %r" % spec.kind)


def _paddle_tpu_version():
    import paddle_tpu

    return paddle_tpu.__version__


def verify_bundle(out_dir):
    """Reload the just-written bundle in THIS process and run its
    smallest bucket on dummy inputs — the cheap export-time smoke that
    the artifacts deserialize and execute, run by ``cli export`` on
    every bundle it writes (the cross-process equivalence check lives in
    tests/test_serve.py and ``cli serve --selfcheck``)."""
    bundle = Bundle(out_dir)
    out = bundle.infer(bundle.dummy_inputs(1))
    for name, arr in out.items():
        enforce(np.all(np.isfinite(arr)),
                "bundle selfcheck: output %r is not finite", name)
    if bundle.has_decoder():
        # the decode artifacts must deserialize and run one window too
        bundle.warmup_decoder()
    return {k: v.shape for k, v in out.items()}
