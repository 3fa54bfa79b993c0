"""Plain reference of the `laguna-xs.2` configuration (poolside
Laguna-XS.2, `model_type` `laguna`): full attention layers whose query and
key heads turn their first half by YaRN rotary positions, beside window
attention layers with more query heads and plain rotary positions, every
head's output times its own sigmoid gate; one leading layer with a dense
SwiGLU, then layers whose feed-forward is a sparse mixture of experts with
sigmoid scores beside a shared expert; an untied head. Written from the
layer equations in float32 at `highest`; it imports nothing of the
program.

    h0 = E[ids]
    h += attn(N(h));   h += ffn(N(h));   N(x) = x / rms(x) * scale
    logits = N(h) W_head^T;   cost = mean token cross entropy, valid positions

attention, layer of kind `full_attention` or `sliding_attention` (H query
heads by `num_attention_heads_per_layer`, KV key-value heads of D):
    q, k, v = u W_q, u W_k, u W_v
    q, k <- rotary by the kind's `rope_parameters` over the first
            partial_rotary_factor * D values, rotate-half within them,
            positions 0..T-1; YaRN's inverse frequencies and cos, sin times
            attention_factor in full layers; the other values pass
    o_h = softmax(q_h k_h^T / sqrt(D) + causal [+ i - j < sliding_window]) v_h
    out = concat_h(sigmoid(u W_g)_h * o_h) W_o
dense ffn (layers whose `mlp_layer_types` entry is dense):
    (silu(gate) * up) W_2,  [gate, up] = u W_1
expert ffn (E = `num_experts_published` experts, k a token):
    s = sigmoid(u W_r);   chosen = the k largest of s
    w = s[chosen] / (sum s[chosen] + 1e-6) * moe_routed_scaling_factor
    out = sum over chosen e with first_expert <= e < first_expert + num_experts
          of w_e * expert_e(u)  +  shared(u)
each expert and the shared expert a SwiGLU of `moe_intermediate_size` /
`shared_expert_intermediate_size`. **The configuration holds
`num_experts` of the E experts**, those from `first_expert` on: the
router scores all E and chooses among all E, and what the experts held
elsewhere would add is left out, here as in the program (guide section 4:
their chips compute it); the shared expert is computed whole. No sorting
and no kernels: every held expert is applied to every token, and its
output is multiplied by the token's weight for it, zero where the token
did not choose it.

Departures from the published description, all listed in the
configuration's `assumed` too: `gating` true read as a gate a head (the
parameter count says so); sigmoid scores, their normalisation and the
1e-6 (the config has no key for them); the gate and up matrices are one
matrix, gate first. The published modelling code was not at hand when
this was written.

So that three steps fit beside the float32 weights, velocity and gradient,
a layer takes the rows of the batch one after the other and backward
keeps each row's input to it only (`jax.checkpoint`), then each branch's
input in turn, and inside the expert branch one expert's at a time;
attention keeps a block of queries' scores at a time (64 heads x 256
queries x 4,096 keys): the values are those of the equations, computed
again.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
# the harness's batch layout, as lfm2's reference gives it
from chipbench.reference.lfm2_moe import batch_arrays  # noqa: F401

_QUERY_BLOCK = 256   # queries whose scores are alive together
_EPS = 1e-6          # in the sum of a token's chosen scores

_ATTENTION = ("q", "k", "v", "o", "g")
_DENSE = ("mlp_in", "mlp_out")
_EXPERTS = ("router", "w_in", "w_out", "shared_in", "shared_out")


def layers_of(cfg):
    """[(attention kind, whether the feed-forward is the experts')] of
    the layers held: the first `num_hidden_layers` of `layer_types`."""
    n = cfg["num_hidden_layers"]
    return [(kind, ffn == "sparse") for kind, ffn in
            zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n])]


def leaves_of(sparse):
    """(the attention's leaf names, the feed-forward's) of a layer."""
    return _ATTENTION, _EXPERTS if sparse else _DENSE


def experts_of(cfg):
    """(all experts the router scores, those held here, the first held)."""
    return (cfg.get("num_experts_published", cfg["num_experts"]),
            cfg["num_experts"], cfg.get("first_expert", 0))


def heads_of(cfg, kind):
    """Query heads of a layer of that kind."""
    return cfg["num_attention_heads_per_layer"][cfg["layer_types"].index(kind)]


def _shapes(cfg):
    """{leaf name: shape} in a fixed order; norms are the 1-d leaves."""
    d, mlp, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hd
    total, here, _ = experts_of(cfg)
    width = cfg["moe_intermediate_size"]
    shared = cfg["shared_expert_intermediate_size"]
    out = {"emb": (cfg["vocab_size"], d), "final_norm": (d,),
           "head": (cfg["vocab_size"], d)}
    for i, (kind, sparse) in enumerate(layers_of(cfg)):
        p, heads = "l%d." % i, heads_of(cfg, kind)
        out.update({p + "norm1": (d,), p + "q": (d, heads * hd),
                    p + "k": (d, kv), p + "v": (d, kv),
                    p + "o": (heads * hd, d), p + "g": (d, heads),
                    p + "norm2": (d,)})
        if sparse:
            out.update({p + "router": (d, total),
                        p + "w_in": (here, d, 2 * width),
                        p + "w_out": (here, width, d),
                        p + "shared_in": (d, 2 * shared),
                        p + "shared_out": (shared, d)})
        else:
            out.update({p + "mlp_in": (d, 2 * mlp), p + "mlp_out": (mlp, d)})
    return out


def parameter_count(cfg):
    return sum(int(np.prod(shape)) for shape in _shapes(cfg).values())


def init_weights(seed, cfg):
    """(weights, state): normal 0.02 matrices, norm scales 1; no state."""
    shapes = _shapes(cfg)

    @jax.jit
    def make(key):
        return {name: jnp.ones(shape, jnp.float32) if len(shape) == 1
                else 0.02 * jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32)
                for i, (name, shape) in enumerate(shapes.items())}

    return make(common.seed_key(seed)), {}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def _swiglu(u, w_in, w_out, quant):
    gate, up = jnp.split(common.matmul(u, w_in, quant), 2, axis=-1)
    return common.matmul(up * jax.nn.silu(gate), w_out, quant)


def yarn(params, dim):
    """(inverse frequencies [dim / 2] float32, cos and sin factor) of a
    YaRN entry of `rope_parameters`, as transformers'
    `_compute_yarn_parameters` defines them: the correction range of the
    pairs that turn beta_fast and beta_slow times over the original
    positions, a linear ramp between, interpolated frequencies (over
    factor) below it and extrapolated ones above."""
    base, factor = float(params["rope_theta"]), float(params["factor"])
    original = params["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(params["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(params["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inverse = interpolation * (1 - extrapolation_factor) \
        + extrapolation * extrapolation_factor
    return inverse, float(params["attention_factor"])


def rotary(x, params):
    """x [B, T, H, D] turned by its positions 0..T-1 by one layer kind's
    `rope_parameters`: the first partial_rotary_factor * D values, value i
    with value i + dims/2, by plain or YaRN frequencies; the rest as they
    are."""
    dims = int(x.shape[-1] * params.get("partial_rotary_factor", 1.0))
    half = dims // 2
    if params.get("rope_type", "default") == "yarn":
        inverse, scale = yarn(params, dims)
    else:
        inverse = float(params["rope_theta"]) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        scale = 1.0
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inverse
    cos, sin = (scale * f(angles)[None, :, None, :]
                for f in (jnp.cos, jnp.sin))
    first, second, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin, rest], axis=-1)


def attention(u, w, kind, cfg, quant=None):
    """u [B, T, d] through the layer's causal grouped-query attention
    (within the window in a sliding layer) and the heads' gates."""
    b, t, _ = u.shape
    heads, kvh, hd = heads_of(cfg, kind), cfg["num_key_value_heads"], \
        cfg["head_dim"]
    groups = heads // kvh
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    params = cfg["rope_parameters"][kind]
    q = common.matmul(u, w["q"], quant).reshape(b, t, heads, hd)
    k = common.matmul(u, w["k"], quant).reshape(b, t, kvh, hd)
    v = common.matmul(u, w["v"], quant).reshape(b, t, kvh, hd)
    q, k = rotary(q, params), rotary(k, params)
    q = q.reshape(b, t, kvh, groups, hd)
    block = min(_QUERY_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3)
    keys = jnp.arange(t)

    @jax.checkpoint
    def rows(args):
        q_blk, start = args                       # [B, L, KV, G, hd]
        at = (start + jnp.arange(block))[:, None]
        s = common.matmul(jnp.moveaxis(q_blk, 1, 3),            # [B,KV,G,L,hd]
                          jnp.moveaxis(k, 1, -1)[:, :, None],   # [B,KV,1,hd,T]
                          quant) * hd ** -0.5
        seen = keys[None, :] <= at
        if window is not None:
            seen = seen & (at - keys[None, :] < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out = common.matmul(p, jnp.moveaxis(v, 1, 2)[:, :, None], quant)
        return jnp.moveaxis(out, 3, 1)            # [B, L, KV, G, hd]

    n = (t + pad) // block
    out = jax.lax.map(rows, (
        jnp.moveaxis(q.reshape(b, n, block, kvh, groups, hd), 1, 0),
        jnp.arange(n) * block))
    y = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads, hd)[:, :t]
    gate = jax.nn.sigmoid(common.matmul(u, w["g"], quant))
    return common.matmul((y * gate[..., None]).reshape(b, t, heads * hd),
                         w["o"], quant)


def routing(u, router, cfg, quant=None):
    """[..., E] float32: each token's weight for every expert the router
    scores, zero for those it did not choose."""
    total = experts_of(cfg)[0]
    scores = jax.nn.sigmoid(common.matmul(u, router, quant))
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores),
                              cfg["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, total, dtype=scores.dtype),
                     axis=-2)
    weights = scores * picked
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + _EPS)
    return weights * cfg["moe_routed_scaling_factor"]


def routed(u, w, cfg, quant=None, first=None):
    """The routed experts' part of the expert feed-forward: the experts
    `w["w_in"]` holds, the global experts from `first` on (the
    configuration's `first_expert` by default), each applied to every
    token and weighted by the routing, zero where a token chose
    another."""
    first = experts_of(cfg)[2] if first is None else first
    weights = routing(u, w["router"], cfg, quant)

    def one(carry, xs):
        w_in, w_out, weight = xs                       # weight [B, T]
        return carry + weight[..., None] * jax.checkpoint(
            lambda a, b, c: _swiglu(a, b, c, quant))(u, w_in, w_out), None

    held = w["w_in"].shape[0]
    here = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (w["w_in"], w["w_out"], here))
    return out


def experts(u, w, cfg, quant=None, first=None):
    """u [B, T, d] through the expert feed-forward: the held experts'
    part and the shared expert."""
    return routed(u, w, cfg, quant, first) \
        + _swiglu(u, w["shared_in"], w["shared_out"], quant)


def _layer(h, w, kind, sparse, cfg, quant):
    eps = cfg["rms_norm_eps"]
    # a branch at a time in backward: each keeps its input and runs again
    h = h + jax.checkpoint(lambda u, w_: attention(u, w_, kind, cfg, quant))(
        _rms_norm(h, w["norm1"], eps), {k: w[k] for k in _ATTENTION})
    u = _rms_norm(h, w["norm2"], eps)
    if sparse:
        return h + jax.checkpoint(lambda u_, w_: experts(u_, w_, cfg, quant))(
            u, {k: w[k] for k in _EXPERTS})
    return h + jax.checkpoint(lambda u_, a, b: _swiglu(u_, a, b, quant))(
        u, w["mlp_in"], w["mlp_out"])


def _row_by_row(fn, *rows):
    """fn over each row of the batch in turn, every argument [B, ...] seen
    as [1, ...]; backward keeps a row's arguments and computes the row
    again."""
    return jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                       tuple(a[:, None] for a in rows))


def hidden_of(weights, tokens, cfg, quant=None):
    """[B, T, hidden] after the last layer's norm, of int32 tokens."""
    h = common.quantize(weights["emb"], quant)[tokens]
    for i, (kind, sparse) in enumerate(layers_of(cfg)):
        prefix = "l%d." % i
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h = _row_by_row(lambda row: _layer(row, w, kind, sparse, cfg, quant),
                        h)[:, 0]
    return _rms_norm(h, weights["final_norm"], cfg["rms_norm_eps"])


def logits_of(weights, tokens, cfg, quant=None):
    """[B, T, vocab] float32 logits of int32 tokens [B, T]."""
    return common.matmul(hidden_of(weights, tokens, cfg, quant),
                         weights["head"].T, quant)


def loss(weights, state, batch, cfg, quant=None):
    """(mean token cross entropy over the batch's valid positions, the
    state, which is empty)."""
    tokens, targets, lengths = batch
    valid = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]

    def row_cost(h, y, seen):
        logits = common.quantize(
            common.matmul(h, weights["head"].T, quant), quant)
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                     y[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(seen, picked, 0.0))

    costs = _row_by_row(row_cost, hidden_of(weights, tokens, cfg, quant),
                        targets, valid)
    return jnp.sum(costs) / jnp.sum(valid), state
