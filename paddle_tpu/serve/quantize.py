"""Weight-only int8 quantization for serving bundles (docs/serving.md
"Quantized bundles").

The round-4/5 bf16 read-replica experiments (benchmark/exp_bf16_replica.py)
proved that lower-precision READS of full-precision masters win on HBM
traffic without losing accuracy; this module pushes the same move one
step further for the serve tier: ``cli export --quantize int8`` stores
matmul/conv weights as **per-output-channel symmetric int8** with an
f32 scale sidecar per tensor (``<name>::scale``), shrinking every
bundle ~4x versus f32 — which the manifest's ``hbm_estimate_bytes``
and the fleet's ``--replicas auto`` pre-check (serve/fleet.py) convert
directly into more replicas per chip.

Scheme (``int8-sym-perchannel``):

* quantized: 2D+ floating weights consumed ONLY by matmul/conv layers
  (``fc``, ``conv``) — ``q = clip(round(w / s), -127, 127)`` with one
  scale per output channel (last axis), ``s = amax(|w|, other axes)
  / 127``; symmetric, no zero point, so dequant is one fused multiply.
* kept full-precision: biases and every 1D tensor, norm scales/shifts
  and running stats, embedding/table lookups (gathers read one row —
  there is no bandwidth win to buy accuracy with), recurrent cell
  weights (their error compounds across timesteps), and anything a
  non-matmul layer consumes.
* decode carries are untouched — continuous batching and streaming
  generation (serve/scheduler.py, serve/generate.py) run unchanged on
  quantized bundles.

At run time the dequant happens INSIDE the exported jit program, so
XLA fuses ``w_int8 * scale`` into the consuming dot and the weights
stream from HBM as int8 (a quarter of the f32 traffic). Weights whose
consumers are int8-native (``fc``) skip even that: the int8 tensor
rides into the layer itself, which routes through
``ops.pallas_kernels.int8_matmul`` — the XLA dequant-fused dot by
default, or the native int8-dot Pallas kernel where an on-chip A/B
recorded a win (``_INT8_MEASURED_WINS``, the ops/pallas_conv.py gate
pattern).

This module stays importable without the graph machinery (numpy/jax
only — the topology is only ever *walked*, never imported), keeping
the serve-side import contract intact.
"""

import numpy as np

SCHEME_INT8 = "int8-sym-perchannel"
SCALE_SUFFIX = "::scale"

# layer node types whose weights are matmul/conv contractions — the only
# consumers worth quantizing (bandwidth-bound MXU reads). Everything
# else (embedding gathers, norm tables, recurrent cells) stays fp.
QUANTIZABLE_CONSUMERS = frozenset({"fc", "img_conv"})
# consumers that take the int8 weight NATIVELY (the layer looks up the
# scale sidecar itself and runs the dequant-fused / Pallas int8 dot);
# the rest get their weight dequantized at the top of the exported
# forward instead (still inside the jit program).
INT8_NATIVE_CONSUMERS = frozenset({"fc"})


def scale_name(param_name):
    """The params-dict key of one quantized tensor's f32 scale sidecar."""
    return param_name + SCALE_SUFFIX


def is_scale_name(name):
    return name.endswith(SCALE_SUFFIX)


def quantize_int8(w):
    """Per-output-channel symmetric int8: ``(q, scale)`` with ``q``
    int8 of ``w``'s shape and ``scale`` f32 ``[out_channels]`` (last
    axis). All-zero channels get scale 1.0 so dequant stays exact."""
    w = np.asarray(w, np.float32)
    if w.ndim < 1:
        raise ValueError("cannot channel-quantize a scalar")
    amax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return q, scale


def dequantize(q, scale):
    """``q * scale`` back to the scale's dtype — the fused-dequant read
    (broadcast over the output-channel last axis). Works on numpy and
    traced jax values alike."""
    return q.astype(scale.dtype) * scale


def quantizable_params(topology, parameters):
    """Choose the quantizable parameter set of a built topology:
    ``{name: {"native": bool}}``. A parameter qualifies when it is a
    floating 2D+ tensor, not running state, and EVERY declaring layer
    is a matmul/conv consumer (``QUANTIZABLE_CONSUMERS``); ``native``
    is True when every consumer also takes int8 weights directly
    (``INT8_NATIVE_CONSUMERS``)."""
    consumers = {}
    for node in topology.nodes:
        for spec in node.param_specs:
            consumers.setdefault(spec.name, set()).add(node.layer_type)
    out = {}
    for name in parameters.names():
        types = consumers.get(name)
        if not types or not types <= QUANTIZABLE_CONSUMERS:
            continue
        arr = np.asarray(parameters.get(name))
        if arr.ndim < 2 or not np.issubdtype(arr.dtype, np.floating):
            continue
        spec = parameters.spec(name)
        if spec is not None and getattr(spec, "is_state", False):
            continue
        out[name] = {"native": types <= INT8_NATIVE_CONSUMERS}
    return out


def quantize_parameters(parameters, topology):
    """Quantize a :class:`~paddle_tpu.parameters.Parameters` payload for
    export: returns ``(qparams, qmanifest)`` where ``qparams`` holds the
    int8 tensors plus their ``<name>::scale`` f32 sidecars (everything
    else copied through untouched) and ``qmanifest`` is the manifest
    block ``{"scheme", "scale_suffix", "params": {name: {"dtype",
    "scale", "native"}}}`` the loaded bundle reports."""
    from paddle_tpu.attr import ParamAttr
    from paddle_tpu.graph import ParamSpec
    from paddle_tpu.initializer import Constant
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.utils.error import enforce

    chosen = quantizable_params(topology, parameters)
    enforce(bool(chosen),
            "nothing to quantize: no floating 2D+ parameter is consumed "
            "exclusively by matmul/conv layers (%s)",
            sorted(QUANTIZABLE_CONSUMERS))
    qparams = Parameters()
    qmanifest = {"scheme": SCHEME_INT8, "scale_suffix": SCALE_SUFFIX,
                 "params": {}}
    for name in parameters.names():
        arr = np.asarray(parameters.get(name))
        spec = parameters.spec(name)
        if name in chosen:
            q, scale = quantize_int8(arr)
            sname = scale_name(name)
            qparams._values[name] = q
            qparams._values[sname] = scale
            qparams._specs[name] = ParamSpec(
                name, q.shape, Constant(0.0),
                attr=ParamAttr(is_static=True))
            qparams._specs[sname] = ParamSpec(
                sname, scale.shape, Constant(1.0),
                attr=ParamAttr(is_static=True))
            qmanifest["params"][name] = {
                "dtype": "int8", "scale": sname,
                "native": bool(chosen[name]["native"]),
            }
        else:
            qparams._values[name] = arr
            if spec is not None:
                qparams._specs[name] = spec
    return qparams, qmanifest


def dequant_for_trace(params, qmanifest):
    """The top-of-forward hook baked into the exported jit program
    (serve/export.py): dequantize the NON-native int8 entries (their
    consumers cannot take int8 weights directly) and pass the native
    ones through untouched — the int8-aware layers fetch their own
    scale sidecars and run the dequant-fused dot themselves. Either
    way the dequant multiply happens inside the traced program, so the
    HBM-resident tensor stays int8."""
    qinfo = qmanifest.get("params", {})
    out = dict(params)
    for name, info in qinfo.items():
        if name in out and not info.get("native"):
            out[name] = dequantize(out[name], out[scale_name(name)])
    return out
