"""Layers of today's decoder-only language models: RMSNorm (plain and
gated) and LayerNorm, the gated MLP, a sparse expert layer that holds
some of the experts, the Mamba-2 and Mamba-1 mixers, the Gated DeltaNet
linear-attention mixer, the Gated Memory Unit, the gated short
convolution, grouped-query causal attention with rotary positions or
none (with or without normalised queries and keys, a window,
differential heads, biases, and over its own keys and values or another
layer's), the head over a vocabulary table, and a block that is
recomputed in backward (the token-level cost over the head's logits is
``layer/cost.py lm_cost``).

All take and give ``SequenceBatch`` values [B, T, width]. The layers that
mix across time (``mamba2``, ``mamba1``, ``gated_delta_net``,
``short_conv``, ``gqa_attention``) refuse packed rows: their state,
convolution taps and attention do not yet reset at segment starts. Every
piece of device work runs under a ``jax.named_scope`` of its own
(docs/observability.md "Decoder scopes"). A layer that hands out a second value (``mamba1``'s
scan output, ``gqa_attention``'s keys and values) gives a tuple, and
:func:`_values` makes one node of each of its values.
"""

import contextlib
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.core.dtype import upcast_f32
from paddle_tpu.graph import auto_name
from paddle_tpu.initializer import Constant, Uniform
from paddle_tpu.layer.base import (bias_spec, data_of, featurewise, is_seq,
                                   like, make_node, register_layer,
                                   reject_packed, to_list, weight_spec)
from paddle_tpu.ops import attention as attention_ops
from paddle_tpu.ops import delta_rule as delta_ops
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops import ssm as ssm_ops
from paddle_tpu.utils.error import enforce


def _rms_normalize(x, weight, eps, gate=None, gate_first=True):
    """x * rsqrt(mean(x^2) + eps) * weight over the last axis, in float32
    at least. With ``gate``: of x * silu(gate) (Mamba-2's order), or with
    ``gate_first=False`` the norm of x, then times silu(gate) (Gated
    DeltaNet's)."""
    with jax.named_scope("paddle_tpu.rmsnorm"):
        wide = upcast_f32(x)
        if gate is not None and gate_first:
            wide = wide * jax.nn.silu(upcast_f32(gate))
        scale = jax.lax.rsqrt(jnp.mean(wide * wide, axis=-1, keepdims=True)
                              + eps)
        out = wide * scale * upcast_f32(weight)
        if gate is not None and not gate_first:
            out = out * jax.nn.silu(upcast_f32(gate))
        return out.astype(x.dtype)


def _l2_normalize(x, scale=1.0, eps=1e-6):
    """scale * x / sqrt(sum(x^2) + eps) over the last axis, in float32 at
    least (the eps keeps an all-zero padded position finite)."""
    with jax.named_scope("paddle_tpu.l2norm"):
        wide = upcast_f32(x)
        inverse = jax.lax.rsqrt(jnp.sum(wide * wide, axis=-1, keepdims=True)
                                + eps)
        return (wide * (inverse * scale)).astype(x.dtype)


def _ones_spec(name, shape, param_attr):
    spec = weight_spec(name, 0, shape, param_attr)
    if spec.attr.initializer is None and spec.attr.initial_std is None:
        spec.initializer = Constant(1.0)
    return spec


@register_layer("rms_norm")
def rms_norm(input, gate=None, eps=1e-5, name=None, param_attr=None,
             layer_attr=None):
    """RMSNorm over the feature axis with a learned scale (ones at the
    start); with ``gate`` the gated form, RMSNorm(x * silu(gate))."""
    name = name or auto_name("rms_norm")
    spec = _ones_spec(name, (input.size,), param_attr)

    def forward(params, values, ctx):
        g = data_of(values[1]) if gate is not None else None
        return featurewise(
            lambda d: _rms_normalize(d, params[spec.name], eps, g),
            values[0])

    return make_node("rms_norm", forward, [input] + to_list(gate), name=name,
                     size=input.size, param_specs=[spec],
                     layer_attr=layer_attr)


@register_layer("layer_norm")
def layer_norm(input, eps=1e-5, name=None, param_attr=None, bias_attr=None,
               layer_attr=None):
    """LayerNorm over the feature axis, (x - mean) * rsqrt(var + eps) *
    scale + bias, in float32 at least; the scale ``<name>.w0`` starts at
    ones, the bias ``<name>.wbias`` at zeros."""
    name = name or auto_name("layer_norm")
    scale = _ones_spec(name, (input.size,), param_attr)
    bias = bias_spec(name, (input.size,), bias_attr)

    def normalize(x, params):
        with jax.named_scope("paddle_tpu.layer_norm"):
            wide = upcast_f32(x)
            centred = wide - jnp.mean(wide, axis=-1, keepdims=True)
            out = centred * jax.lax.rsqrt(
                jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
            return (out * upcast_f32(params[scale.name])
                    + upcast_f32(params[bias.name])).astype(x.dtype)

    def forward(params, values, ctx):
        return featurewise(lambda d: normalize(d, params), values[0])

    return make_node("layer_norm", forward, [input], name=name,
                     size=input.size, param_specs=[scale, bias],
                     layer_attr=layer_attr)


# What a recomputed block may keep for backward (``recompute(keep=...)``):
# GATED_MLP_PRODUCT names x W_in inside ``gated_mlp``, before the split;
# MAMBA_IN_PRODUCT u W_in inside ``mamba2``, MAMBA1_IN_PRODUCT inside
# ``mamba1``, both before the split; MOE_PRODUCT the sorted rows times the
# held experts' first matrices inside ``moe``, before the split;
# _KEPT_OUTPUT the outputs of the inner nodes a block lists.
GATED_MLP_PRODUCT = "paddle_tpu.gated_mlp.product"
MOE_PRODUCT = "paddle_tpu.moe.product"
MAMBA_IN_PRODUCT = "paddle_tpu.mamba2.in_product"
MAMBA1_IN_PRODUCT = "paddle_tpu.mamba1.in_product"
_KEPT_OUTPUT = "paddle_tpu.block.kept"


def _values(node, sizes):
    """One node for each value of ``node``, a layer whose forward gives a
    tuple: the i-th has the i-th value and ``sizes[i]``."""
    return [make_node("value_of", lambda params, values, ctx, i=i:
                      values[0][i], [node], name="%s.%d" % (node.name, i),
                      size=size) for i, size in enumerate(sizes)]


def _kept(x, name, ctx):
    """``x`` under ``name`` for the checkpoint policy of the block around
    it (``jax.ad_checkpoint.checkpoint_name``: the identity outside a
    checkpoint and under a policy that does not list the name), its bytes
    added to the trace's count where the block keeps the name."""
    if name in ctx.recompute_keeping:
        ctx.recompute_kept_bytes += x.size * x.dtype.itemsize
    return checkpoint_name(x, name)


def _gated_mlp(x, w_in, w_out, ctx):
    with jax.named_scope("paddle_tpu.gated_mlp"):
        product = _kept(jnp.matmul(x, w_in), GATED_MLP_PRODUCT, ctx)
        a, b = jnp.split(product, 2, axis=-1)
        return jnp.matmul(jax.nn.silu(a) * b, w_out)


@register_layer("gated_mlp")
def gated_mlp(input, size, name=None, param_attr=None, layer_attr=None):
    """(silu(a) * b) W_out with [a, b] = x W_in: widths d -> 2 * size -> d,
    no bias. The first product carries the name ``GATED_MLP_PRODUCT``, which
    a ``recompute`` block around the layer may keep."""
    name = name or auto_name("gated_mlp")
    attrs = param_attr if isinstance(param_attr, (list, tuple)) \
        else [param_attr] * 2
    w_in = weight_spec(name, 0, (input.size, 2 * size), attrs[0])
    w_out = weight_spec(name, 1, (size, input.size), attrs[1])

    def forward(params, values, ctx):
        return featurewise(
            lambda d: _gated_mlp(d, params[w_in.name], params[w_out.name],
                                 ctx),
            values[0])

    return make_node("gated_mlp", forward, [input], name=name,
                     size=input.size, param_specs=[w_in, w_out],
                     layer_attr=layer_attr)


def _named_spec(name, suffix, shape, initializer=None, std=None,
                static=False):
    from paddle_tpu.attr import ParamAttr

    attr = ParamAttr(name="%s.%s" % (name, suffix), initializer=initializer,
                     initial_std=std, is_static=static)
    return weight_spec(name, 0, shape, attr)


@register_layer("moe")
def moe(input, experts_total, experts_held, first_held, top_k, width,
        normalize=True, scaling=1.0, use_bias=True, initial_std=0.02,
        name=None, layer_attr=None, shared_width=None):
    """A sparse expert layer on a chip that holds ``experts_held`` of the
    ``experts_total`` experts, those from ``first_held`` on
    (``ops/moe.py``):
        s = sigmoid(u W_r)                    all experts, float32
        chosen = top_k(s + expert_bias)       the bias selects and no more
        w = s[chosen] / (sum s[chosen] + 1e-6) * scaling      ``normalize``
        out = sum over chosen e held here of w_e * expert_e(u)  [+ shared(u)]
    each expert a gated MLP of ``width``; with ``shared_width`` a shared
    expert beside them, a gated MLP of that width that every token crosses
    unweighted and every chip computes alike. What the absent experts would
    add is left out (their chips compute it); no pair of a held expert is
    dropped whatever the imbalance, the sorted buffer having ``top_k`` rows
    a position; padded positions route nowhere. Parameters
    ``<name>.router`` [d, total], ``.expert_bias`` [total] (static: it
    selects and is never differentiated; zeros at the start; absent
    without ``use_bias``), ``.w_in`` [held, d, 2 * width] (gate then up),
    ``.w_out`` [held, width, d]; ``.shared_in`` [d, 2 * shared_width] and
    ``.shared_out`` with ``shared_width``. The first grouped product
    carries the name ``MOE_PRODUCT``, which a ``recompute`` block around
    the layer may keep. The passes over the sorted rows run as
    ``ops/pallas_moe.py``'s kernels on the TPU where the widths and the
    positions tile, else as gathers and ``ragged_dot``; the gauges
    ``paddle_tpu_moe_fused`` / ``_plain`` count a traced step's layers by
    form. Each traced layer adds to the gauges ``paddle_tpu_moe_*`` and to
    the step's data counters, the fused form to
    ``paddle_tpu_moe_rows_visited`` too (docs/observability.md)."""
    name = name or auto_name("moe")
    d = input.size
    enforce(0 <= first_held and first_held + experts_held <= experts_total,
            "moe holds experts %d..%d of %d", first_held,
            first_held + experts_held - 1, experts_total)
    specs = {
        "router": _named_spec(name, "router", (d, experts_total),
                              std=initial_std),
        "w_in": _named_spec(name, "w_in", (experts_held, d, 2 * width),
                            std=initial_std),
        "w_out": _named_spec(name, "w_out", (experts_held, width, d),
                             std=initial_std),
    }
    if use_bias:
        specs["expert_bias"] = _named_spec(
            name, "expert_bias", (experts_total,), Constant(0.0),
            static=True)
    if shared_width:
        specs["shared_in"] = _named_spec(name, "shared_in",
                                         (d, 2 * shared_width),
                                         std=initial_std)
        specs["shared_out"] = _named_spec(name, "shared_out",
                                          (shared_width, d), std=initial_std)

    def forward(params, values, ctx):
        seq = values[0]
        x = data_of(seq)
        rows = x.reshape(-1, d)
        lengths = seq.lengths if is_seq(seq) else None
        valid = jnp.ones(rows.shape[:1], bool) if lengths is None else (
            jnp.arange(x.shape[1])[None, :] < lengths[:, None]).reshape(-1)
        p = {k: params[s.name] for k, s in specs.items()}
        out, here, busiest, visited = moe_ops.moe(
            rows, valid, p["router"], p.get("expert_bias"), p["w_in"],
            p["w_out"], top_k, first_held, scaling, normalize,
            kept=lambda product: _kept(product, MOE_PRODUCT, ctx))
        if shared_width:
            with jax.named_scope("paddle_tpu.shared_expert"):
                a, b = jnp.split(jnp.matmul(rows, p["shared_in"]), 2,
                                 axis=-1)
                out = out + jnp.matmul(jax.nn.silu(a) * b, p["shared_out"])
        ctx.moe["held"] = experts_held
        ctx.moe["total"] = experts_total
        ctx.moe["rows_bound"] += top_k * rows.shape[0]
        ctx.moe[moe_ops.experts_form(d, width, rows.shape[0], top_k)] += 1
        ctx.count("paddle_tpu_moe_rows_here", here)
        ctx.count("paddle_tpu_moe_expert_load_max", busiest)
        if visited is not None:
            ctx.count("paddle_tpu_moe_rows_visited", visited)
        return like(seq, out.reshape(x.shape))

    return make_node("moe", forward, [input], name=name, size=d,
                     param_specs=list(specs.values()), layer_attr=layer_attr)


@register_layer("short_conv")
def short_conv(input, conv_width=3, initial_std=0.02, name=None,
               layer_attr=None, eps=None):
    """The gated short convolution (LFM2's ``conv`` layers):
        [B, C, x] = u W_in                        d -> 3 d, no bias
        y = C * conv1d_causal(B * x)              depthwise, no bias, no activation
        out = y W_out
    Parameters ``<name>.in_proj`` [d, 3 d], ``.conv_w`` [d, K],
    ``.out_proj`` [d, d]. ``eps`` is taken for ``hybrid_lm``, which hands
    every mixer the model's, and not used: the layer has no norm."""
    name = name or auto_name("short_conv")
    d = input.size
    specs = {
        "in_proj": _named_spec(name, "in_proj", (d, 3 * d), std=initial_std),
        # as torch.nn.Conv1d starts a depthwise filter
        "conv_w": _named_spec(name, "conv_w", (d, conv_width),
                              Uniform(-conv_width ** -0.5,
                                      conv_width ** -0.5)),
        "out_proj": _named_spec(name, "out_proj", (d, d), std=initial_std),
    }

    def forward(params, values, ctx):
        seq = values[0]
        reject_packed(seq, "short_conv")
        enforce(is_seq(seq), "short_conv needs a sequence input")
        p = {k: params[s.name] for k, s in specs.items()}
        with jax.named_scope("paddle_tpu.short_conv"):
            b_gate, c_gate, x = jnp.split(
                jnp.matmul(seq.data, p["in_proj"]), 3, axis=-1)
            y = c_gate * ssm_ops.causal_conv1d(b_gate * x, p["conv_w"], None,
                                               seq.lengths)
            return like(seq, jnp.matmul(y, p["out_proj"]))

    return make_node("short_conv", forward, [input], name=name, size=d,
                     param_specs=list(specs.values()), layer_attr=layer_attr)


class _InverseSoftplusOfLogUniform:
    """dt_bias such that softplus(dt_bias) is log-uniform in [low, high]
    (the Mamba-2 initialisation)."""

    def __init__(self, low=1e-3, high=1e-1):
        self.low, self.high = low, high

    def __call__(self, rng, shape, dtype):
        u = jax.random.uniform(rng, shape, dtype)
        dt = jnp.exp(u * (math.log(self.high) - math.log(self.low))
                     + math.log(self.low))
        return dt + jnp.log(-jnp.expm1(-dt))


class _LogArange:
    """A_log = log(1..n) along the last axis, every row alike."""

    def __call__(self, rng, shape, dtype):
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=dtype)), shape)


@register_layer("mamba2")
def mamba2(input, heads, head_dim, state, conv_width=4, groups=1, chunk=256,
           eps=1e-5, initial_std=0.02, name=None, layer_attr=None):
    """The Mamba-2 mixer (Dao & Gu 2024):
        [z, xBC, dt] = u W_in
        xBC = silu(conv1d_causal(xBC))             depthwise, with bias
        [x, B, C] = xBC                            heads * head_dim, 2 * groups * state
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
        out = RMSNorm(y * silu(z)) W_out
    the scan in chunks of ``chunk`` (``ops/ssm.py``). Parameters
    ``<name>.in_proj``, ``.conv_w`` [C, K], ``.conv_b``, ``.A_log``, ``.D``,
    ``.dt_bias``, ``.norm_w``, ``.out_proj``; no bias on the projections.
    The first product, before the split, carries the name
    ``MAMBA_IN_PRODUCT``, which a ``recompute`` block around the layer may
    keep."""
    name = name or auto_name("mamba2")
    d = input.size
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    specs = {
        "in_proj": _named_spec(name, "in_proj",
                               (d, inner + conv_dim + heads), std=initial_std),
        # as torch.nn.Conv1d starts a depthwise filter and its bias
        "conv_w": _named_spec(name, "conv_w", (conv_dim, conv_width),
                              Uniform(-conv_width ** -0.5,
                                      conv_width ** -0.5)),
        "conv_b": _named_spec(name, "conv_b", (conv_dim,),
                              Uniform(-conv_width ** -0.5,
                                      conv_width ** -0.5)),
        "A_log": _named_spec(name, "A_log", (heads,), _LogArange()),
        "D": _named_spec(name, "D", (heads,), Constant(1.0)),
        "dt_bias": _named_spec(name, "dt_bias", (heads,),
                               _InverseSoftplusOfLogUniform()),
        "norm_w": _named_spec(name, "norm_w", (inner,), Constant(1.0)),
        "out_proj": _named_spec(name, "out_proj", (inner, d),
                                std=initial_std),
    }

    def forward(params, values, ctx):
        seq = values[0]
        reject_packed(seq, "mamba2")
        enforce(is_seq(seq), "mamba2 needs a sequence input")
        p = {k: params[s.name] for k, s in specs.items()}
        u = seq.data
        b, t = u.shape[:2]
        product = _kept(jnp.matmul(u, p["in_proj"]), MAMBA_IN_PRODUCT, ctx)
        z, xbc, dt = jnp.split(product, [inner, inner + conv_dim], axis=-1)
        xbc = jax.nn.silu(ssm_ops.causal_conv1d(
            xbc, p["conv_w"], p["conv_b"], seq.lengths))
        x, b_mat, c_mat = jnp.split(
            xbc, [inner, inner + groups * state], axis=-1)
        dt = jax.nn.softplus(upcast_f32(dt) + upcast_f32(p["dt_bias"]))
        y = ssm_ops.ssd_scan(
            x.reshape(b, t, heads, head_dim), dt,
            -jnp.exp(upcast_f32(p["A_log"])),
            b_mat.reshape(b, t, groups, state),
            c_mat.reshape(b, t, groups, state), p["D"], chunk, seq.lengths)
        y = _rms_normalize(y.reshape(b, t, inner), p["norm_w"], eps, gate=z)
        return like(seq, jnp.matmul(y, p["out_proj"]))

    return make_node("mamba2", forward, [input], name=name, size=d,
                     param_specs=list(specs.values()), layer_attr=layer_attr)


@register_layer("mamba1")
def mamba1(input, state=16, conv_width=4, expand=2, dt_rank=None, chunk=16,
           initial_std=0.02, name=None, layer_attr=None, hand_out=False,
           eps=None):
    """The Mamba-1 mixer (Gu & Dao 2023, arXiv:2312.00752), E = expand *
    width channels, ``state`` states a channel, R = ``dt_rank`` (width /
    16, rounded up, by default):
        [x, z] = u W_in
        x = silu(conv1d_causal(x))                 depthwise, with bias
        [r, B, C] = x W_x                          R, state, state
        dt = softplus(r W_dt + dt_bias);  A = -exp(A_log)     A_log [E, state]
        S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
        y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]
        out = (y * silu(z)) W_out
    the scan token by token (``ops/ssm.py selective_scan``): fused Pallas
    kernels on the TPU where ``expand * d`` is a multiple of 128 and
    ``state`` of 8, else plain loops kept by chunks of ``chunk``; the
    gauges ``paddle_tpu_selective_scan_fused`` / ``_plain`` count a traced
    step's scans by form. Parameters ``<name>.in_proj``,
    ``.conv_w`` [E, K], ``.conv_b``, ``.x_proj``, ``.dt_proj``,
    ``.dt_bias``, ``.A_log``, ``.D``, ``.out_proj``; no bias on the
    projections. The first product, before the split, carries the name
    ``MAMBA1_IN_PRODUCT``, which a ``recompute`` block around the layer may
    keep. With ``hand_out`` returns [the layer, its scan output y before
    the gate]: the memory that ``gmu`` layers read. ``eps`` is taken for
    ``hybrid_lm``, which hands every mixer the model's, and not used: the
    layer has no norm."""
    name = name or auto_name("mamba1")
    d = input.size
    inner = expand * d
    rank = dt_rank if dt_rank is not None else -(-d // 16)
    specs = {
        "in_proj": _named_spec(name, "in_proj", (d, 2 * inner),
                               std=initial_std),
        # as torch.nn.Conv1d starts a depthwise filter and its bias
        "conv_w": _named_spec(name, "conv_w", (inner, conv_width),
                              Uniform(-conv_width ** -0.5,
                                      conv_width ** -0.5)),
        "conv_b": _named_spec(name, "conv_b", (inner,),
                              Uniform(-conv_width ** -0.5,
                                      conv_width ** -0.5)),
        "x_proj": _named_spec(name, "x_proj", (inner, rank + 2 * state),
                              std=initial_std),
        "dt_proj": _named_spec(name, "dt_proj", (rank, inner),
                               Uniform(-rank ** -0.5, rank ** -0.5)),
        "dt_bias": _named_spec(name, "dt_bias", (inner,),
                               _InverseSoftplusOfLogUniform()),
        "A_log": _named_spec(name, "A_log", (inner, state), _LogArange()),
        "D": _named_spec(name, "D", (inner,), Constant(1.0)),
        "out_proj": _named_spec(name, "out_proj", (inner, d),
                                std=initial_std),
    }

    def forward(params, values, ctx):
        seq = values[0]
        reject_packed(seq, "mamba1")
        enforce(is_seq(seq), "mamba1 needs a sequence input")
        p = {k: params[s.name] for k, s in specs.items()}
        with jax.named_scope("paddle_tpu.mamba1"):
            product = _kept(jnp.matmul(seq.data, p["in_proj"]),
                            MAMBA1_IN_PRODUCT, ctx)
            x, z = jnp.split(product, 2, axis=-1)
            x = jax.nn.silu(ssm_ops.causal_conv1d(
                x, p["conv_w"], p["conv_b"], seq.lengths))
            r, b_mat, c_mat = jnp.split(jnp.matmul(x, p["x_proj"]),
                                        [rank, rank + state], axis=-1)
            dt = jax.nn.softplus(
                jnp.matmul(r, p["dt_proj"],
                           preferred_element_type=upcast_f32(r).dtype)
                + upcast_f32(p["dt_bias"]))
            ctx.selective_scans[
                ssm_ops.selective_scan_form(inner, state)] += 1
            y, _ = ssm_ops.selective_scan(
                x, dt, -jnp.exp(upcast_f32(p["A_log"])), b_mat, c_mat,
                p["D"], chunk, seq.lengths)
            out = like(seq, jnp.matmul(y * jax.nn.silu(z), p["out_proj"]))
            return (out, like(seq, y)) if hand_out else out

    node = make_node("mamba1", forward, [input], name=name, size=d,
                     param_specs=list(specs.values()), layer_attr=layer_attr)
    return _values(node, (d, inner)) if hand_out else node


@register_layer("gmu")
def gmu(input, memory, initial_std=0.02, name=None, layer_attr=None,
        eps=None):
    """The Gated Memory Unit (SambaY, arXiv:2507.06607):
    ``(m * silu(u W_1)) W_2`` with ``m`` the value of ``memory``, an
    earlier ``mamba1`` layer's scan output, position by position.
    Parameters ``<name>.in_proj`` [d, width of m], ``.out_proj``; no bias.
    ``eps`` as ``mamba1``'s: taken and not used."""
    name = name or auto_name("gmu")
    d = input.size
    w_in = _named_spec(name, "in_proj", (d, memory.size), std=initial_std)
    w_out = _named_spec(name, "out_proj", (memory.size, d), std=initial_std)

    def forward(params, values, ctx):
        with jax.named_scope("paddle_tpu.gmu"):
            gate = jax.nn.silu(jnp.matmul(data_of(values[0]),
                                          params[w_in.name]))
            return like(values[0], jnp.matmul(data_of(values[1]) * gate,
                                              params[w_out.name]))

    return make_node("gmu", forward, [input, memory], name=name, size=d,
                     param_specs=[w_in, w_out], layer_attr=layer_attr)


class _LogUniform:
    """A_log = log(uniform(0, high)), as the published layer starts it."""

    def __init__(self, high=16.0):
        self.high = high

    def __call__(self, rng, shape, dtype):
        return jnp.log(self.high * jax.random.uniform(rng, shape, dtype))


@register_layer("gated_delta_net")
def gated_delta_net(input, heads, key_dim, value_dim, conv_width=4,
                    neg_eigval=True, chunk=64, eps=1e-6, initial_std=0.02,
                    name=None, layer_attr=None):
    """The Gated DeltaNet mixer (Yang, Kautz & Hatamizadeh,
    arXiv:2412.06464), ``heads`` heads of ``key_dim`` / ``value_dim``:
        q~, k~, v = silu(conv1d_causal([u W_q, u W_k, u W_v]))   depthwise, no bias
        q_t = q~_t / |q~_t| * key_dim^-1/2;  k_t = k~_t / |k~_t|   per head
        beta_t = sigmoid(u_t W_b), doubled with ``neg_eigval``
        alpha_t = exp(-exp(A_log) * softplus(u_t W_a + dt_bias))
        S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T;  o_t = S_t^T q_t
        out = (RMSNorm(o_t) * silu(u_t W_g)) W_o               norm and gate per head
    the rule in chunks of ``chunk`` (``ops/delta_rule.py``). Parameters
    ``<name>.q``, ``.k``, ``.v``, ``.g``, ``.a``, ``.b``, ``.conv_w``
    [C, K] over q, k, v side by side, ``.A_log``, ``.dt_bias``, ``.norm_w``
    [value_dim], ``.o``; no bias anywhere."""
    name = name or auto_name("gated_delta_net")
    d = input.size
    key_inner, value_inner = heads * key_dim, heads * value_dim
    specs = {
        "q": _named_spec(name, "q", (d, key_inner), std=initial_std),
        "k": _named_spec(name, "k", (d, key_inner), std=initial_std),
        "v": _named_spec(name, "v", (d, value_inner), std=initial_std),
        "g": _named_spec(name, "g", (d, value_inner), std=initial_std),
        "a": _named_spec(name, "a", (d, heads), std=initial_std),
        "b": _named_spec(name, "b", (d, heads), std=initial_std),
        # as torch.nn.Conv1d starts a depthwise filter
        "conv_w": _named_spec(name, "conv_w",
                              (2 * key_inner + value_inner, conv_width),
                              Uniform(-conv_width ** -0.5,
                                      conv_width ** -0.5)),
        "A_log": _named_spec(name, "A_log", (heads,), _LogUniform()),
        "dt_bias": _named_spec(name, "dt_bias", (heads,),
                               _InverseSoftplusOfLogUniform()),
        "norm_w": _named_spec(name, "norm_w", (value_dim,), Constant(1.0)),
        "o": _named_spec(name, "o", (value_inner, d), std=initial_std),
    }

    def forward(params, values, ctx):
        seq = values[0]
        reject_packed(seq, "gated_delta_net")
        enforce(is_seq(seq), "gated_delta_net needs a sequence input")
        p = {k: params[s.name] for k, s in specs.items()}
        u = seq.data
        b, t = u.shape[:2]
        with jax.named_scope("paddle_tpu.gated_delta_net"):
            qkv = jnp.concatenate(
                [jnp.matmul(u, p[n]) for n in ("q", "k", "v")], axis=-1)
            qkv = jax.nn.silu(ssm_ops.causal_conv1d(
                qkv, p["conv_w"], None, seq.lengths))
            q, k, v = (x.reshape(b, t, heads, -1) for x in jnp.split(
                qkv, [key_inner, 2 * key_inner], axis=-1))
            log_alpha = -jnp.exp(upcast_f32(p["A_log"])) * jax.nn.softplus(
                upcast_f32(jnp.matmul(u, p["a"])) + upcast_f32(p["dt_bias"]))
            beta = jax.nn.sigmoid(upcast_f32(jnp.matmul(u, p["b"]))) \
                * (2.0 if neg_eigval else 1.0)
            o, _ = delta_ops.gated_delta_rule(
                _l2_normalize(q, key_dim ** -0.5), _l2_normalize(k), v,
                log_alpha, beta, chunk, seq.lengths)
            y = _rms_normalize(
                o, p["norm_w"], eps, gate_first=False,
                gate=jnp.matmul(u, p["g"]).reshape(b, t, heads, value_dim))
            return like(seq, jnp.matmul(y.reshape(b, t, value_inner),
                                        p["o"]))

    return make_node("gated_delta_net", forward, [input], name=name, size=d,
                     param_specs=list(specs.values()), layer_attr=layer_attr)


def lambda_init(depth):
    """A differential attention layer's starting lambda by its depth in
    the model, 0.8 - 0.6 exp(-0.3 depth) (Ye et al. 2024,
    arXiv:2410.05258, eq. 3, depth from 0)."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _differential_attention(q, k, v, lam, lam_init, norm_w, eps, scale,
                            lengths, block, window):
    """Differential attention (arXiv:2410.05258): query heads (2p, 2p+1)
    and key heads (2g, 2g+1) in pairs, g = p // (H / KV), the pair's
    values side by side; per pair (softmax(Q1 K1^T) - lam softmax(Q2
    K2^T)) [V1 | V2], RMS-normalised over its 2 D values and times
    1 - lam_init. Both softmaxes go through one blockwise pass: the first
    heads of the pairs, then the second, each over the pair's values;
    the difference and the norm are float32."""
    b, t, heads, d = q.shape
    with jax.named_scope("paddle_tpu.diff_attention"):
        pair_v = v.reshape(b, t, v.shape[2] // 2, 2 * d)
        both = attention_ops.blockwise_attention(
            jnp.concatenate([q[:, :, 0::2], q[:, :, 1::2]], axis=2),
            jnp.concatenate([k[:, :, 0::2], k[:, :, 1::2]], axis=2),
            jnp.concatenate([pair_v, pair_v], axis=2),
            scale, True, lengths, block, window)
        first, second = jnp.split(upcast_f32(both), 2, axis=2)
        out = _rms_normalize(first - lam * second, norm_w, eps) \
            * (1.0 - lam_init)
        return out.astype(q.dtype)


@register_layer("gqa_attention")
def gqa_attention(input, heads, kv_heads, head_dim, scale=None, block=512,
                  initial_std=0.02, name=None, layer_attr=None,
                  qk_norm=False, eps=1e-5, window=None, differential=None,
                  bias=False, kv=None, hand_out=False, rope_theta=None,
                  rope=None, gate=None):
    """Causal self-attention with ``heads`` query heads over ``kv_heads``
    shared key-value heads, no positional encoding and no bias; scores are
    multiplied by ``scale`` (1 / sqrt(head_dim) by default). Blockwise
    (``ops/attention.py``): no [T, T] score matrix is held; on the TPU,
    where the shapes tile, as ``ops/pallas_attention.py``'s kernels, and
    the gauges ``paddle_tpu_attention_fused`` / ``_plain`` count a traced
    step's layers by form. With
    ``qk_norm`` queries and keys are RMS-normalised with a learned scale,
    each over its whole projection, before the split into heads (the
    OLMo 2 layout), or with ``qk_norm="head"`` each head over its own
    ``head_dim`` values, one scale [head_dim] for the queries and one for
    the keys. With ``rope`` (the keywords of ``ops/attention.py rotary``:
    ``theta``, and ``dims``, ``inverse``, ``factor`` for a partial or
    scaled turn) queries and keys then turn by rotary positions 0..T-1;
    ``rope_theta`` is ``rope={"theta": rope_theta}``, the whole head.
    With ``gate="head"`` each head's output is multiplied by its own
    sigmoid(u W_g) before the output projection, ``W_g`` [d, heads].
    With ``window`` a query sees the ``window`` keys that
    end with its own, and key blocks outside are not visited. With
    ``differential`` (the layer's starting lambda, ``lambda_init`` of its
    depth) heads go in pairs that subtract two softmaxes
    (``_differential_attention``). With ``bias`` the four projections have
    biases. With ``kv``, a node handed out by an earlier attention layer
    (``hand_out``: returns [the layer, its keys and values side by side,
    [B, T, 2 * kv_heads * head_dim]]), the layer has a query projection
    only and attends to that layer's keys and values (cross-attention in
    a decoder that shares one key-value cache). Parameters ``<name>.q``,
    ``.k``, ``.v``, ``.o``; ``.q_norm``, ``.k_norm`` with ``qk_norm``;
    ``.q_b``, ``.k_b``, ``.v_b``, ``.o_b`` with ``bias``; ``.lambda_q1``,
    ``.lambda_k1``, ``.lambda_q2``, ``.lambda_k2`` [head_dim] and
    ``.subln`` [2 * head_dim] with ``differential``; ``.g`` with
    ``gate``."""
    name = name or auto_name("gqa_attention")
    d = input.size
    enforce(heads % kv_heads == 0, "kv_heads %d must divide heads %d",
            kv_heads, heads)
    enforce(differential is None or kv_heads % 2 == 0,
            "differential attention pairs heads: kv_heads is %d", kv_heads)
    enforce(gate in (None, "head"), "gqa_attention: gate is %r, not None "
            "or head", gate)
    enforce(not (rope and rope_theta), "gqa_attention: rope_theta is "
            "rope={'theta': ...}; give one of the two")
    rope = {"theta": float(rope_theta)} if rope_theta else rope
    scale = scale if scale is not None else head_dim ** -0.5
    widths = {"q": heads * head_dim, "k": kv_heads * head_dim,
              "v": kv_heads * head_dim}
    made = ("q",) if kv is not None else ("q", "k", "v")
    specs = {n: _named_spec(name, n, (d, widths[n]), std=initial_std)
             for n in made}
    specs["o"] = _named_spec(name, "o", (heads * head_dim, d),
                             std=initial_std)
    per_head = qk_norm == "head"
    enforce(kv is None or not (per_head or rope),
            "gqa_attention: cross-attention takes another layer's keys as "
            "they are, without a per-head norm or rotary positions")
    if qk_norm:
        specs["q_norm"] = _named_spec(
            name, "q_norm", (head_dim if per_head else heads * head_dim,),
            Constant(1.0))
        specs["k_norm"] = _named_spec(
            name, "k_norm", (head_dim if per_head else kv_heads * head_dim,),
            Constant(1.0))
    if bias:
        for n in made:
            specs[n + "_b"] = _named_spec(name, n + "_b", (widths[n],),
                                          Constant(0.0))
        specs["o_b"] = _named_spec(name, "o_b", (d,), Constant(0.0))
    if differential is not None:
        for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            specs[n] = _named_spec(name, n, (head_dim,), std=0.1)
        specs["subln"] = _named_spec(name, "subln", (2 * head_dim,),
                                     Constant(1.0))
    if gate:
        specs["g"] = _named_spec(name, "g", (d, heads), std=initial_std)

    def forward(params, values, ctx):
        seq = values[0]
        reject_packed(seq, "gqa_attention")
        enforce(is_seq(seq), "gqa_attention needs a sequence input")
        u = seq.data
        b, t = u.shape[:2]

        def projected(n, h):
            x = jnp.matmul(u, params[specs[n].name])
            if bias:
                x = x + params[specs[n + "_b"].name]
            if qk_norm and not per_head and n != "v":
                with jax.named_scope("paddle_tpu.qk_norm"):
                    x = _rms_normalize(x, params[specs[n + "_norm"].name],
                                       eps)
            x = x.reshape(b, t, h, head_dim)
            if n == "v":
                return x
            if per_head:
                with jax.named_scope("paddle_tpu.qk_norm"):
                    x = _rms_normalize(x, params[specs[n + "_norm"].name],
                                       eps)
            if rope:
                x = attention_ops.rotary(x, **rope)
            return x

        def attend(q, k, v):
            if differential is None:
                return attention_ops.blockwise_attention(
                    q, k, v, scale, True, seq.lengths, block, window)
            lam = [jnp.sum(upcast_f32(params[specs["lambda_q%d" % j].name])
                           * upcast_f32(params[specs["lambda_k%d" % j].name]))
                   for j in (1, 2)]
            return _differential_attention(
                q, k, v, jnp.exp(lam[0]) - jnp.exp(lam[1]) + differential,
                differential, params[specs["subln"].name], eps, scale,
                seq.lengths, block, window)

        with jax.named_scope("paddle_tpu.gqa_attention"):
            if kv is None:
                q, k, v = (projected(n, h) for n, h in (
                    ("q", heads), ("k", kv_heads), ("v", kv_heads)))
            else:
                with jax.named_scope("paddle_tpu.cross_attention"):
                    q = projected("q", heads)
                    k, v = (x.reshape(b, t, kv_heads, head_dim) for x in
                            jnp.split(data_of(values[1]), 2, axis=-1))
            for visited, w in (("visited", window), ("possible", None)):
                ctx.attention_key_blocks[visited] += sum(
                    end - first for first, end in attention_ops.key_blocks(
                        t, block, True, w))
            ctx.attentions[attention_ops.attention_form(
                heads, kv_heads, head_dim,
                head_dim * (2 if differential is not None else 1))] += 1
            with jax.named_scope("paddle_tpu.window_attention") \
                    if window is not None else contextlib.nullcontext():
                y = attend(q, k, v)
            if gate:
                with jax.named_scope("paddle_tpu.attention_gate"):
                    g = jax.nn.sigmoid(jnp.matmul(
                        u, params[specs["g"].name],
                        preferred_element_type=upcast_f32(u).dtype))
                    y = y * g.astype(y.dtype)[..., None]
            out = jnp.matmul(y.reshape(b, t, heads * head_dim),
                             params[specs["o"].name])
            if bias:
                out = out + params[specs["o_b"].name]
            if not hand_out:
                return like(seq, out)
            return like(seq, out), like(seq, jnp.concatenate(
                [k.reshape(b, t, -1), v.reshape(b, t, -1)], axis=-1))

    node = make_node("gqa_attention", forward, [input] + to_list(kv),
                     name=name, size=d, param_specs=list(specs.values()),
                     layer_attr=layer_attr)
    return _values(node, (d, 2 * kv_heads * head_dim)) if hand_out else node


@register_layer("lm_head")
def lm_head(input, vocab, param_attr, scale=1.0, name=None, layer_attr=None):
    """Logits ``scale * h E^T`` over a [vocab, width] table, in float32
    whatever the compute dtype. Name the table as the embedding's
    (``param_attr=ParamAttr(name=...)``) and the two are one parameter,
    whose gradient is the sum of its two uses; under another name, or
    none, the head has a table of its own."""
    name = name or auto_name("lm_head")
    spec = weight_spec(name, 0, (vocab, input.size), param_attr)

    def forward(params, values, ctx):
        table = params[spec.name]

        def logits(h):
            return jnp.einsum("...d,vd->...v", h, table,
                              preferred_element_type=upcast_f32(h).dtype
                              ) * scale

        return featurewise(logits, values[0])

    return make_node("lm_head", forward, [input], name=name, size=vocab,
                     param_specs=[spec], layer_attr=layer_attr)


@register_layer("recompute")
def recompute(output, inputs, enabled=True, name=None, keep=()):
    """The sub-graph from ``inputs`` to ``output`` as one node, whose
    forward runs under ``jax.checkpoint``: backward keeps the block's
    inputs and computes its inside again, memory for time. The node owns
    the parameters of the layers inside; ``enabled=False`` runs the same
    node without the checkpoint.

    ``output`` may be a list of nodes: the block then hands out every
    one, and a list of nodes comes back, one for each. What follows the
    first is a value that later blocks read beside the stream (they list
    it in their ``inputs``): it is made once and lives across the blocks,
    and backward adds into it the gradients of every block that read it.
    Its bytes are added to ``ctx.shared_across_blocks_bytes`` (the gauge
    ``paddle_tpu_shared_across_blocks_bytes``).

    ``keep`` lists what backward keeps besides the inputs, so that the
    second forward need not make it again: a node inside the block (its
    output) or a name that a layer inside gives one of its values
    (``GATED_MLP_PRODUCT``, ``MAMBA_IN_PRODUCT``, ``MAMBA1_IN_PRODUCT``,
    ``MOE_PRODUCT``). Worth keeping is a value that is dear to make and
    small to hold, a large product's output; whatever only fed a kept
    value is then dead in the second forward (the product before a kept
    sum). With nothing listed the checkpoint has no policy. The bytes kept are added to
    ``ctx.recompute_kept_bytes``, which ``Topology.apply`` sets the gauge
    ``paddle_tpu_recompute_kept_bytes`` from."""
    inputs = to_list(inputs)
    several = isinstance(output, (list, tuple))
    outputs = to_list(output)
    boundary = {id(n) for n in inputs}
    inside = _between(outputs, boundary)
    kept_nodes = {id(k) for k in keep if not isinstance(k, str)}
    enforce(kept_nodes <= {id(n) for n in inside},
            "recompute: keep lists a node that is not inside the block")
    names = frozenset(k for k in keep if isinstance(k, str)) \
        | ({_KEPT_OUTPUT} if kept_nodes else frozenset())
    policy = jax.checkpoint_policies.save_only_these_names(*sorted(names)) \
        if names else None
    specs = {}
    for node in inside:
        enforce(node.layer_type != "data",
                "recompute: data layer %r is inside the block; list it in "
                "inputs", node.name)
        for spec in node.param_specs:
            enforce(not spec.is_state, "recompute: %r keeps running state "
                    "(%s), which a recomputed block cannot", node.name,
                    spec.name)
            specs[spec.name] = spec

    def forward(params, values, ctx):
        def run(block_params, block_inputs):
            # the counters the layers inside make leave with the outputs:
            # a value of the checkpointed trace cannot leave through ctx
            outer_counts, ctx.counts = ctx.counts, {}
            try:
                seen = {id(n): v for n, v in zip(inputs, block_inputs)}
                for node in inside:
                    value = node.forward(
                        block_params, [seen[id(p)] for p in node.inputs],
                        ctx)
                    if id(node) in kept_nodes:
                        value = featurewise(
                            lambda d: _kept(d, _KEPT_OUTPUT, ctx), value)
                    seen[id(node)] = value
                result = tuple(seen[id(n)] for n in outputs) if several \
                    else seen[id(output)]
                return result, ctx.counts
            finally:
                ctx.counts = outer_counts

        def handed_out(made):
            result, counts = made
            for name, value in counts.items():
                ctx.count(name, value)
            for value in result[1:] if several else ():
                x = data_of(value)
                ctx.shared_across_blocks_bytes += x.size * x.dtype.itemsize
            return result

        block_params = {k: params[k] for k in specs}
        with jax.named_scope("paddle_tpu.block"):
            if not enabled:
                return handed_out(run(block_params, list(values)))
            outer, ctx.recompute_keeping = ctx.recompute_keeping, names
            try:
                return handed_out(jax.checkpoint(run, policy=policy)(
                    block_params, list(values)))
            finally:
                ctx.recompute_keeping = outer

    node = make_node("recompute", forward, inputs,
                     name=name or auto_name("recompute"),
                     size=outputs[0].size, param_specs=list(specs.values()))
    return _values(node, [n.size for n in outputs]) if several else node


def _between(outputs, boundary):
    """Nodes from the boundary (left out) to ``outputs``, inputs first."""
    order, seen = [], set(boundary)

    def visit(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for parent in node.inputs:
            visit(parent)
        order.append(node)

    for node in outputs:
        visit(node)
    return order
