"""Plain reference of the `granite-4.0-h-micro` configuration (IBM Granite
4.0-H, `granitemoehybrid` without experts): Mamba-2 state-space layers and
position-free grouped-query attention layers, each followed by a gated
MLP, on a tied embedding. Written from the layer equations in float32 at
`highest`; it imports nothing of the program.

    h0 = embedding_multiplier * E[ids]
    h += residual_multiplier * mixer(RMSNorm(h))
    h += residual_multiplier * MLP(RMSNorm(h));  MLP(u) = (silu(a) * b) W_out, [a, b] = u W_in
    logits = RMSNorm(h) E^T / logits_scaling;   cost = mean token cross entropy over valid positions

Attention: causal, no positions, scores times attention_multiplier, each
group of query heads on one key-value head; the whole row of scores of a
query is held, a block of queries at a time. Mamba-2 mixer:
    [z, xBC, dt] = u W_in;  xBC = silu(conv1d_causal(xBC) + bias);  [x, B, C] = xBC
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D x_t
    out = (RMSNorm(y * silu(z)) * w) W_out
the state stepped token by token (`lax.scan` over time, no chunks).

So that three steps fit beside the float32 weights, velocity and gradient,
a layer takes the rows of the batch one after the other and backward
keeps each row's input to it only (`jax.checkpoint`), as do the head and
the cost; the recurrence keeps its state every `_SEGMENT` tokens, and
attention keeps a block of queries' scores at a time: the values are
those of the equations, computed again.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

_SEGMENT = 64      # tokens between the states the recurrence keeps
_QUERY_BLOCK = 256  # queries whose scores are alive together


def _kinds(cfg):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _sizes(cfg):
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, conv


def _shapes(cfg):
    """{leaf name: (shape, kind of start)} in a fixed order."""
    d, mlp = cfg["hidden_size"], cfg["shared_intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    mh, taps = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    inner, conv = _sizes(cfg)
    out = {"emb": ((cfg["vocab_size"], d), "normal"),
           "final_norm": ((d,), "ones")}
    for i, kind in enumerate(_kinds(cfg)):
        p = "l%d." % i
        out[p + "norm1"] = ((d,), "ones")
        if kind == "mamba":
            out[p + "in_proj"] = ((d, inner + conv + mh), "normal")
            out[p + "conv_w"] = ((conv, taps), "conv")
            out[p + "conv_b"] = ((conv,), "conv")
            out[p + "A_log"] = ((mh,), "a_log")
            out[p + "D"] = ((mh,), "ones")
            out[p + "dt_bias"] = ((mh,), "dt_bias")
            out[p + "norm_w"] = ((inner,), "ones")
            out[p + "out_proj"] = ((inner, d), "normal")
        else:
            out[p + "q"] = ((d, heads * hd), "normal")
            out[p + "k"] = ((d, kv * hd), "normal")
            out[p + "v"] = ((d, kv * hd), "normal")
            out[p + "o"] = ((heads * hd, d), "normal")
        out[p + "norm2"] = ((d,), "ones")
        out[p + "mlp_in"] = ((d, 2 * mlp), "normal")
        out[p + "mlp_out"] = ((mlp, d), "normal")
    return out


def _start(key, shape, kind, taps):
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "conv":  # as torch.nn.Conv1d starts a depthwise filter
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if kind == "a_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if kind == "dt_bias":  # softplus(dt_bias) log-uniform in [0.001, 0.1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def init_weights(seed, cfg):
    shapes = _shapes(cfg)
    taps = cfg["mamba_d_conv"]

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


def _mlp(u, w_in, w_out, quant):
    a, b = jnp.split(common.matmul(u, w_in, quant), 2, axis=-1)
    return common.matmul(jax.nn.silu(a) * b, w_out, quant)


def _attention(u, w, cfg, quant):
    b, t, d = u.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    q = common.matmul(u, w["q"], quant).reshape(b, t, kv, heads // kv, hd)
    k = common.matmul(u, w["k"], quant).reshape(b, t, kv, hd)
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
            * cfg["attention_multiplier"]              # [B, KV, G, L, T]
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.moveaxis(common.matmul(p, v_h[:, :, None], quant), 3, 1)

    n = (t + pad) // block
    out = jax.lax.map(rows, (
        jnp.moveaxis(q.reshape(b, n, block, kv, heads // kv, hd), 1, 0),
        jnp.arange(n) * block))                        # [n, B, L, KV, G, hd]
    y = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads * hd)[:, :t]
    return common.matmul(y, w["o"], quant)


def _recurrence(x, dt, a, b_mat, c_mat, d_skip, quant):
    """y_t = S_t C_t + D x_t with S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,
    token by token. x [B, T, H, P], dt [B, T, H], a and d_skip [H], b_mat
    and c_mat [B, T, G, N]. The state is kept every `_SEGMENT` tokens for
    backward and stepped again in between."""
    batch, t, heads, p = x.shape
    groups, n = b_mat.shape[2:]
    per = heads // groups
    seg = min(_SEGMENT, t)
    pad = -t % seg   # dt 0 there: the state stands still, outputs dropped
    x, dt, b_mat, c_mat = (
        jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        for v in (x, dt, b_mat, c_mat))

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs            # [B,H,P] [B,H] [B,G,N] [B,G,N]
        b_h = jnp.repeat(b_t, per, axis=1)  # [B, H, N]
        c_h = jnp.repeat(c_t, per, axis=1)
        state = jnp.exp(dt_t * a)[..., None, None] * state + common.matmul(
            (dt_t[..., None] * x_t)[..., None], b_h[..., None, :], quant)
        y_t = common.matmul(state, c_h[..., None], quant)[..., 0]
        return state, y_t + d_skip[:, None] * x_t

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    def by_segment(v):   # [B, T, ...] -> [T / seg, seg, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(((t + pad) // seg, seg) + v.shape[1:])

    _, y = jax.lax.scan(
        segment, jnp.zeros((batch, heads, p, n), jnp.float32),
        tuple(by_segment(v) for v in (x, dt, b_mat, c_mat)))
    return jnp.moveaxis(y.reshape((t + pad,) + y.shape[2:]), 0, 1)[:, :t]


def _mamba(u, w, cfg, quant):
    b, t, _ = u.shape
    heads, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    taps = cfg["mamba_d_conv"]
    inner, conv = _sizes(cfg)
    z, xbc, dt = jnp.split(common.matmul(u, w["in_proj"], quant),
                           [inner, inner + conv], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(w["conv_b"] + sum(
        padded[:, k:k + t] * w["conv_w"][:, k] for k in range(taps)))
    x, b_mat, c_mat = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    y = _recurrence(
        x.reshape(b, t, heads, hd), jax.nn.softplus(dt + w["dt_bias"]),
        -jnp.exp(w["A_log"]), b_mat.reshape(b, t, groups, n),
        c_mat.reshape(b, t, groups, n), w["D"], quant)
    y = _rms_norm(y.reshape(b, t, inner) * jax.nn.silu(z), w["norm_w"],
                  cfg["rms_norm_eps"])
    return common.matmul(y, w["out_proj"], quant)


def _layer(h, w, kind, cfg, quant):
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    h = h + res * mixer(_rms_norm(h, w["norm1"], eps), w, cfg, quant)
    return h + res * _mlp(_rms_norm(h, w["norm2"], eps), w["mlp_in"],
                          w["mlp_out"], quant)


def _row_by_row(fn, *rows):
    """fn over each row of the batch in turn, every argument [B, ...] seen
    as [1, ...]; backward keeps a row's arguments and computes the row
    again."""
    return jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                       tuple(a[:, None] for a in rows))


def hidden_of(weights, tokens, cfg, quant=None):
    """[B, T, hidden] after the last layer's norm, of int32 tokens."""
    h = cfg["embedding_multiplier"] * common.quantize(
        weights["emb"], quant)[tokens]
    for i, kind in enumerate(_kinds(cfg)):
        prefix = "l%d." % i
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h = _row_by_row(lambda row: _layer(row, w, kind, cfg, quant), h)[:, 0]
    return _rms_norm(h, weights["final_norm"], cfg["rms_norm_eps"])


def _logits(h, emb, cfg, quant):
    return common.matmul(h, emb.T, quant) / cfg["logits_scaling"]


def logits_of(weights, tokens, cfg, quant=None):
    """[B, T, vocab] float32 logits of int32 tokens [B, T]."""
    return _logits(hidden_of(weights, tokens, cfg, quant), weights["emb"],
                   cfg, quant)


def loss(weights, state, batch, cfg, quant=None):
    """(mean token cross entropy over the batch's valid positions, {})."""
    tokens, targets, lengths = batch
    valid = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]

    def row_cost(h, y, seen):
        logits = common.quantize(_logits(h, weights["emb"], cfg, quant),
                                 quant)
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                     y[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(seen, picked, 0.0))

    costs = _row_by_row(row_cost, hidden_of(weights, tokens, cfg, quant),
                        targets, valid)
    return jnp.sum(costs) / jnp.sum(valid), {}
