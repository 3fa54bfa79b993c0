"""Layers of today's decoder-only language models: RMSNorm (plain and
gated), the gated MLP, the Mamba-2 mixer, the Gated DeltaNet
linear-attention mixer, grouped-query causal attention without positions
(with or without normalised queries and keys), the head over a
vocabulary table, and a block that is recomputed in backward (the
token-level cost over the head's logits is ``layer/cost.py lm_cost``).

All take and give ``SequenceBatch`` values [B, T, width]. The three layers
that mix across time (``mamba2``, ``gated_delta_net``, ``gqa_attention``)
refuse packed rows: their state, convolution taps and attention do not yet
reset at segment starts. Every piece of device work runs under a
``jax.named_scope`` of its own (docs/observability.md "Decoder scopes").
"""

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu.core.dtype import upcast_f32
from paddle_tpu.graph import auto_name
from paddle_tpu.initializer import Constant, Uniform
from paddle_tpu.layer.base import (data_of, featurewise, is_seq, like,
                                   make_node, register_layer, reject_packed,
                                   to_list, weight_spec)
from paddle_tpu.ops import attention as attention_ops
from paddle_tpu.ops import delta_rule as delta_ops
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


# What a recomputed block may keep for backward (``recompute(keep=...)``):
# GATED_MLP_PRODUCT names x W_in inside ``gated_mlp``, before the split;
# MAMBA_IN_PRODUCT u W_in inside ``mamba2``, before the split;
# _KEPT_OUTPUT the outputs of the inner nodes a block lists.
GATED_MLP_PRODUCT = "paddle_tpu.gated_mlp.product"
MAMBA_IN_PRODUCT = "paddle_tpu.mamba2.in_product"
_KEPT_OUTPUT = "paddle_tpu.block.kept"


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


def _named_spec(name, suffix, shape, initializer=None, std=None):
    from paddle_tpu.attr import ParamAttr

    attr = ParamAttr(name="%s.%s" % (name, suffix), initializer=initializer,
                     initial_std=std)
    return weight_spec(name, 0, shape, attr)


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
    """A_log = log(1..H)."""

    def __call__(self, rng, shape, dtype):
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


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


@register_layer("gqa_attention")
def gqa_attention(input, heads, kv_heads, head_dim, scale=None, block=512,
                  initial_std=0.02, name=None, layer_attr=None,
                  qk_norm=False, eps=1e-5):
    """Causal self-attention with ``heads`` query heads over ``kv_heads``
    shared key-value heads, no positional encoding and no bias; scores are
    multiplied by ``scale`` (1 / sqrt(head_dim) by default). Blockwise
    (``ops/attention.py``): no [T, T] score matrix is held. With
    ``qk_norm`` queries and keys are RMS-normalised with a learned scale,
    each over its whole projection, before the split into heads (the
    OLMo 2 layout). Parameters ``<name>.q``, ``.k``, ``.v``, ``.o``, and
    ``.q_norm``, ``.k_norm`` with ``qk_norm``."""
    name = name or auto_name("gqa_attention")
    d = input.size
    enforce(heads % kv_heads == 0, "kv_heads %d must divide heads %d",
            kv_heads, heads)
    scale = scale if scale is not None else head_dim ** -0.5
    specs = {
        "q": _named_spec(name, "q", (d, heads * head_dim), std=initial_std),
        "k": _named_spec(name, "k", (d, kv_heads * head_dim),
                         std=initial_std),
        "v": _named_spec(name, "v", (d, kv_heads * head_dim),
                         std=initial_std),
        "o": _named_spec(name, "o", (heads * head_dim, d), std=initial_std),
    }
    if qk_norm:
        specs["q_norm"] = _named_spec(name, "q_norm", (heads * head_dim,),
                                      Constant(1.0))
        specs["k_norm"] = _named_spec(name, "k_norm", (kv_heads * head_dim,),
                                      Constant(1.0))

    def forward(params, values, ctx):
        seq = values[0]
        reject_packed(seq, "gqa_attention")
        enforce(is_seq(seq), "gqa_attention needs a sequence input")
        u = seq.data
        b, t = u.shape[:2]

        def projected(n, h):
            x = jnp.matmul(u, params[specs[n].name])
            if qk_norm and n != "v":
                with jax.named_scope("paddle_tpu.qk_norm"):
                    x = _rms_normalize(x, params[specs[n + "_norm"].name],
                                       eps)
            return x.reshape(b, t, h, head_dim)

        with jax.named_scope("paddle_tpu.gqa_attention"):
            q, k, v = (projected(n, h) for n, h in (
                ("q", heads), ("k", kv_heads), ("v", kv_heads)))
            y = attention_ops.blockwise_attention(
                q, k, v, scale, True, seq.lengths, block)
            return like(seq, jnp.matmul(y.reshape(b, t, heads * head_dim),
                                        params[specs["o"].name]))

    return make_node("gqa_attention", forward, [input], name=name, size=d,
                     param_specs=list(specs.values()), layer_attr=layer_attr)


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

    ``keep`` lists what backward keeps besides the inputs, so that the
    second forward need not make it again: a node inside the block (its
    output) or a name that a layer inside gives one of its values
    (``GATED_MLP_PRODUCT``, ``MAMBA_IN_PRODUCT``). Worth keeping is a value
    that is dear to make and small to hold, a large product's output;
    whatever only fed a kept value is then dead in the second forward (the
    product before a kept sum). With nothing listed the checkpoint has no
    policy. The bytes kept are added to ``ctx.recompute_kept_bytes``, which
    ``Topology.apply`` sets the gauge ``paddle_tpu_recompute_kept_bytes``
    from."""
    inputs = to_list(inputs)
    boundary = {id(n) for n in inputs}
    inside = _between(output, boundary)
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
            seen = {id(n): v for n, v in zip(inputs, block_inputs)}
            for node in inside:
                value = node.forward(
                    block_params, [seen[id(p)] for p in node.inputs], ctx)
                if id(node) in kept_nodes:
                    value = featurewise(
                        lambda d: _kept(d, _KEPT_OUTPUT, ctx), value)
                seen[id(node)] = value
            return seen[id(output)]

        block_params = {k: params[k] for k in specs}
        with jax.named_scope("paddle_tpu.block"):
            if not enabled:
                return run(block_params, list(values))
            outer, ctx.recompute_keeping = ctx.recompute_keeping, names
            try:
                return jax.checkpoint(run, policy=policy)(
                    block_params, list(values))
            finally:
                ctx.recompute_keeping = outer

    return make_node("recompute", forward, inputs,
                     name=name or auto_name("recompute"), size=output.size,
                     param_specs=list(specs.values()))


def _between(output, boundary):
    """Nodes from the boundary (left out) to ``output``, inputs first."""
    order, seen = [], set(boundary)

    def visit(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        for parent in node.inputs:
            visit(parent)
        order.append(node)

    visit(output)
    return order
