"""Plain reference of the `olmo-hybrid-7b` configuration (allenai
Olmo-Hybrid-7B, `model_type` `olmo_hybrid`): Gated DeltaNet
linear-attention layers (Yang, Kautz & Hatamizadeh, arXiv:2412.06464)
beside full attention with normalised queries and keys, each followed by a
gated MLP, norms after the branches, an untied head. Written from the
layer equations in float32 at `highest`; it imports nothing of the program.

    h0 = E[ids]
    h += RMSNorm(mixer(h));   h += RMSNorm(MLP(h));   MLP(u) = (silu(a) * b) W_out, [a, b] = u W_in
    logits = RMSNorm(h) W_head^T;   cost = mean token cross entropy over valid positions

Full attention: q = RMSNorm(u W_q), k = RMSNorm(u W_k), each over its whole
projection and with its own scale, before the split into heads; v = u W_v;
causal, no positions, scores over sqrt(head_dim); the whole row of scores of
a query is held, a block of queries at a time. Gated DeltaNet mixer, a
head at a time (d_k the key width, S the state [d_k, d_v]):
    q~, k~, v = silu(conv1d_causal([u W_q, u W_k, u W_v]))      depthwise, no bias
    q_t = q~_t / sqrt(|q~_t|^2 + 1e-6) / sqrt(d_k);   k_t = k~_t / sqrt(|k~_t|^2 + 1e-6)
    beta_t = 2 sigmoid(u_t W_b)                                  (2: linear_allow_neg_eigval)
    alpha_t = exp(-exp(A_log) softplus(u_t W_a + dt_bias))
    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T;   o_t = S_t^T q_t
    out = concat_h(RMSNorm(o_t; w) * silu(u_t W_g)) W_o
the state stepped token by token (`lax.scan` over time: no chunks, no
triangular system).

Departures from the published description, all listed in the
configuration's `assumed` too: the config names none of the mixer's inner
choices, which are those of the published Gated DeltaNet layer (output
gate, norm then gate, silu after the convolutions, no convolution bias,
unit keys and queries with the 1e-6 under the root that keeps a padded
position finite); the norm placement and the normalised queries and keys
are the OLMo 2 / OLMo 3 layout; `head_dim` is hidden / heads. The builder
had no access to the published `olmo_hybrid` modelling code.

So that three steps fit beside the float32 weights, velocity and gradient,
a layer takes the rows of the batch one after the other and backward
keeps each row's input to it only (`jax.checkpoint`) and then each
branch's input in turn, as do the head and the cost with theirs; the
recurrence keeps its state every `_SEGMENT` tokens, and
attention keeps a block of queries' scores at a time: the values are
those of the equations, computed again. (The MLP and the head take a
whole row: a loop over blocks of its tokens would carry a second sum of
the weights' gradients, 2.4 GB more than the blocks save.)
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

_SEGMENT = 64        # tokens between the states the recurrence keeps
_QUERY_BLOCK = 256   # queries whose scores are alive together
_L2_EPS = 1e-6

_LINEAR = ("q", "k", "v", "g", "a", "b", "conv_w", "A_log", "dt_bias",
           "norm_w", "o")
_FULL = ("q", "k", "v", "o", "q_norm", "k_norm")


def _kinds(cfg):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def leaves_of(kind):
    """The mixer's leaf names of a layer kind."""
    return _LINEAR if kind == "linear_attention" else _FULL


def _shapes(cfg):
    """{leaf name: (shape, kind of start)} in a fixed order."""
    d, mlp = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    lh, taps = cfg["linear_num_value_heads"], cfg["linear_conv_kernel_dim"]
    key = lh * cfg["linear_key_head_dim"]
    value = lh * cfg["linear_value_head_dim"]
    out = {"emb": ((cfg["vocab_size"], d), "normal"),
           "head": ((cfg["vocab_size"], d), "normal"),
           "final_norm": ((d,), "ones")}
    for i, kind in enumerate(_kinds(cfg)):
        p = "l%d." % i
        if kind == "linear_attention":
            out[p + "q"] = ((d, key), "normal")
            out[p + "k"] = ((d, key), "normal")
            out[p + "v"] = ((d, value), "normal")
            out[p + "g"] = ((d, value), "normal")
            out[p + "a"] = ((d, lh), "normal")
            out[p + "b"] = ((d, lh), "normal")
            out[p + "conv_w"] = ((2 * key + value, taps), "conv")
            out[p + "A_log"] = ((lh,), "a_log")
            out[p + "dt_bias"] = ((lh,), "dt_bias")
            out[p + "norm_w"] = ((cfg["linear_value_head_dim"],), "ones")
            out[p + "o"] = ((value, d), "normal")
        else:
            out[p + "q"] = ((d, heads * hd), "normal")
            out[p + "k"] = ((d, kv * hd), "normal")
            out[p + "v"] = ((d, kv * hd), "normal")
            out[p + "o"] = ((heads * hd, d), "normal")
            out[p + "q_norm"] = ((heads * hd,), "ones")
            out[p + "k_norm"] = ((kv * hd,), "ones")
        out[p + "norm1"] = ((d,), "ones")
        out[p + "mlp_in"] = ((d, 2 * mlp), "normal")
        out[p + "mlp_out"] = ((mlp, d), "normal")
        out[p + "norm2"] = ((d,), "ones")
    return out


def _start(key, shape, kind, taps):
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "conv":  # as torch.nn.Conv1d starts a depthwise filter
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if kind == "a_log":  # A uniform in (0, 16), as the published layer
        return jnp.log(16.0 * jax.random.uniform(key, shape, jnp.float32))
    if kind == "dt_bias":  # softplus(dt_bias) log-uniform in [0.001, 0.1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def init_weights(seed, cfg):
    shapes = _shapes(cfg)
    taps = cfg["linear_conv_kernel_dim"]

    @jax.jit
    def make(key):
        return {name: _start(jax.random.fold_in(key, i), shape, kind, taps)
                for i, (name, (shape, kind)) in enumerate(shapes.items())}

    return make(common.seed_key(seed)), {}


def batch_arrays(samples, cfg):
    """(tokens [B, T] int32 zero-padded to the longest row, targets
    [B, T], lengths [B]) from per-sample (tokens, targets) tuples."""
    lengths = np.asarray([len(s[0]) for s in samples], np.int32)
    tokens = np.zeros((len(samples), int(lengths.max())), np.int32)
    targets = np.zeros_like(tokens)
    for i, s in enumerate(samples):
        tokens[i, : lengths[i]] = s[0]
        targets[i, : lengths[i]] = s[1]
    return tokens, targets, lengths


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _mlp(u, w_in, w_out, quant):
    a, b = jnp.split(common.matmul(u, w_in, quant), 2, axis=-1)
    return common.matmul(jax.nn.silu(a) * b, w_out, quant)


def _attention(u, w, cfg, quant):
    b, t, d = u.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    eps = cfg["rms_norm_eps"]
    q = _rms_norm(common.matmul(u, w["q"], quant), w["q_norm"], eps)
    k = _rms_norm(common.matmul(u, w["k"], quant), w["k_norm"], eps)
    q = q.reshape(b, t, kv, heads // kv, hd)
    k = k.reshape(b, t, kv, hd)
    v = common.matmul(u, w["v"], quant).reshape(b, t, kv, hd)
    block = min(_QUERY_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    k_t = jnp.moveaxis(k, 1, -1)                       # [B, KV, hd, T]
    v_h = jnp.moveaxis(v, 1, 2)                        # [B, KV, T, hd]
    keys = jnp.arange(t)

    @jax.checkpoint
    def rows(args):
        q_blk, start = args                            # [B, L, KV, G, hd]
        s = common.matmul(jnp.moveaxis(q_blk, 1, 3), k_t[:, :, None], quant) \
            * hd ** -0.5                               # [B, KV, G, L, T]
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.moveaxis(common.matmul(p, v_h[:, :, None], quant), 3, 1)

    n = (t + pad) // block
    out = jax.lax.map(rows, (
        jnp.moveaxis(q.reshape(b, n, block, kv, heads // kv, hd), 1, 0),
        jnp.arange(n) * block))                        # [n, B, L, KV, G, hd]
    y = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads * hd)[:, :t]
    return common.matmul(y, w["o"], quant)


def _recurrence(q, k, v, alpha, beta, quant):
    """o_t = S_t^T q_t with S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1}
    + beta_t k_t v_t^T, token by token. q and k [B, T, H, K], v
    [B, T, H, V], alpha and beta [B, T, H]. The state is kept every
    `_SEGMENT` tokens for backward and stepped again in between."""
    batch, t, heads, dk = q.shape
    dv = v.shape[-1]
    seg = min(_SEGMENT, t)
    pad = -t % seg   # beta 0 there: nothing is written, outputs dropped
    q, k, v, alpha, beta = (
        jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        for x in (q, k, v, alpha, beta))

    def token(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs        # [B,H,K] [B,H,K] [B,H,V] [B,H]
        k_col = k_t[..., None]              # [B, H, K, 1]
        held = common.matmul(k_t[..., None, :], state, quant)  # [B, H, 1, V]
        state = a_t[..., None, None] * (
            state - b_t[..., None, None] * common.matmul(k_col, held, quant))
        state = state + b_t[..., None, None] * common.matmul(
            k_col, v_t[..., None, :], quant)
        return state, common.matmul(q_t[..., None, :], state, quant)[..., 0, :]

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    def by_segment(x):   # [B, T, ...] -> [T / seg, seg, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(((t + pad) // seg, seg) + x.shape[1:])

    _, o = jax.lax.scan(
        segment, jnp.zeros((batch, heads, dk, dv), jnp.float32),
        tuple(by_segment(x) for x in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o.reshape((t + pad,) + o.shape[2:]), 0, 1)[:, :t]


def _gated_delta_net(u, w, cfg, quant):
    b, t, _ = u.shape
    heads, taps = cfg["linear_num_value_heads"], cfg["linear_conv_kernel_dim"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    qkv = jnp.concatenate([common.matmul(u, w[n], quant)
                           for n in ("q", "k", "v")], axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, i:i + t] * w["conv_w"][:, i]
                          for i in range(taps)))
    q, k, v = (x.reshape(b, t, heads, -1) for x in jnp.split(
        qkv, [heads * dk, 2 * heads * dk], axis=-1))
    beta = jax.nn.sigmoid(common.matmul(u, w["b"], quant))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(
        common.matmul(u, w["a"], quant) + w["dt_bias"]))
    o = _recurrence(_unit(q) * dk ** -0.5, _unit(k), v, alpha, beta, quant)
    gate = common.matmul(u, w["g"], quant).reshape(b, t, heads, dv)
    y = _rms_norm(o, w["norm_w"], cfg["rms_norm_eps"]) * jax.nn.silu(gate)
    return common.matmul(y.reshape(b, t, heads * dv), w["o"], quant)


def _layer(h, w, kind, cfg, quant):
    eps = cfg["rms_norm_eps"]
    mixer = _gated_delta_net if kind == "linear_attention" else _attention
    # a branch at a time in backward: each keeps its input and runs again
    mixed = jax.checkpoint(lambda h_, w_: mixer(h_, w_, cfg, quant))(h, w)
    h = h + _rms_norm(mixed, w["norm1"], eps)
    fed = jax.checkpoint(lambda h_, a, b: _mlp(h_, a, b, quant))(
        h, w["mlp_in"], w["mlp_out"])
    return h + _rms_norm(fed, w["norm2"], eps)


def _row_by_row(fn, *rows):
    """fn over each row of the batch in turn, every argument [B, ...] seen
    as [1, ...]; backward keeps a row's arguments and computes the row
    again."""
    return jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                       tuple(a[:, None] for a in rows))


def hidden_of(weights, tokens, cfg, quant=None):
    """[B, T, hidden] after the last layer's norm, of int32 tokens."""
    h = common.quantize(weights["emb"], quant)[tokens]
    for i, kind in enumerate(_kinds(cfg)):
        prefix = "l%d." % i
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h = _row_by_row(lambda row: _layer(row, w, kind, cfg, quant), h)[:, 0]
    return _rms_norm(h, weights["final_norm"], cfg["rms_norm_eps"])


def logits_of(weights, tokens, cfg, quant=None):
    """[B, T, vocab] float32 logits of int32 tokens [B, T]."""
    return common.matmul(hidden_of(weights, tokens, cfg, quant),
                         weights["head"].T, quant)


def loss(weights, state, batch, cfg, quant=None):
    """(mean token cross entropy over the batch's valid positions, {})."""
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
    return jnp.sum(costs) / jnp.sum(valid), {}
