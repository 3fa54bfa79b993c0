"""Plain reference of the `phi-4-mini-flash-reasoning` configuration
(microsoft Phi-4-mini-flash-reasoning, `model_type` `phi4flash`; the
architecture is SambaY with differential attention, arXiv:2507.06607):
a self-decoder of Mamba-1 layers (Gu & Dao, arXiv:2312.00752) beside
window attention, one full-attention layer, then a cross-decoder of Gated
Memory Units that read the last Mamba layer's scan output and of
cross-attention layers that read the full-attention layer's keys and
values; attention is differential (Ye et al., arXiv:2410.05258). Written
from the layer equations in float32 at `highest`; it imports nothing of
the program.

    h0 = E[ids]
    h += mixer(LN(h));   h += MLP(LN(h));   MLP(u) = (up * silu(gate)) W_down, [gate, up] = u W_in
    logits = LN(h) E^T;   cost = mean token cross entropy over valid positions
    LN(x) = (x - mean) / sqrt(var + eps) * scale + bias

`mamba1` (E channels, N states, R the rank of dt):
    [x, z] = u W_in;   x = silu(conv1d_causal(x; w, b))          depthwise, with bias
    [r, B, C] = x W_x;   dt = softplus(r W_dt + b_dt);   A = -exp(A_log)
    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n];   S_0 = 0
    y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]
    m = y (what the `gmu` layers after it read);   out = (y * silu(z)) W_out
the state stepped token by token (`lax.scan` over single tokens: no
chunks). `gmu`: out = (m * silu(u W_1)) W_2, m the nearest earlier Mamba
layer's, position by position.

Differential attention, query heads (2p, 2p+1) and key and value heads
(2g, 2g+1) in pairs, g = p // (heads / kv heads), V = [V_2g | V_2g+1]:
    A_j = softmax(Q_j K_j^T / sqrt(head_dim) + M),  j = 1, 2
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init;   lam_init = 0.8 - 0.6 exp(-0.3 i)
    o_p = RMSNorm((A_1 - lam A_2) V; g) * (1 - lam_init);   out = [o_0 | ...] W_o + b_o
with i the layer's index in the published model. M is causal;
`sliding_attention` sees the `sliding_window` keys that end with the
query's own. `sliding_attention` and `full_attention` project q, k and v
from their input with biases; `cross_attention` projects q only and takes
the nearest earlier `full_attention` layer's k and v as that layer made
them. The whole row of scores of a query is held, a block of queries at a
time, with the mask written out.

Departures from the published description, all listed in the
configuration's `assumed` too: the config names none of the mixers' inner
choices (Mamba's sizes and biases are `mamba_ssm`'s defaults; the pairing
of heads, the norm of a pair and its factor are the Differential
Transformer's; the Gated Memory Unit is the SambaY paper's), `W_qkv` is
held as three matrices q, k, v side by side, and there are no positions.
The builder had no access to the published `modeling_phi4flash.py`.

So that three steps fit beside the float32 weights, velocity and gradient,
a layer takes the rows of the batch one after the other and backward
keeps each row's input to it only (`jax.checkpoint`) and then each
branch's input in turn, as do the head and the cost with theirs; the
recurrence keeps its state every `_SEGMENT` tokens, and attention keeps a
block of queries' scores at a time: the values are those of the
equations, computed again.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

_SEGMENT = 64        # tokens between the states the recurrence keeps
_QUERY_BLOCK = 256   # queries whose scores are alive together

_MAMBA = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_proj", "dt_bias",
          "A_log", "D", "out_proj")
_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
_SELF = ("q", "k", "v", "o", "q_b", "k_b", "v_b", "o_b") + _LAMBDAS \
    + ("subln",)
_CROSS = ("q", "o", "q_b", "o_b") + _LAMBDAS + ("subln",)
_GMU = ("in_proj", "out_proj")
_LEAVES = {"mamba1": _MAMBA, "sliding_attention": _SELF,
           "full_attention": _SELF, "cross_attention": _CROSS, "gmu": _GMU}
# what a layer kind hands to later layers, and what it reads of them
_MAKES = {"mamba1": "memory", "full_attention": "kv"}
_READS = {"gmu": "memory", "cross_attention": "kv"}


def _layers(cfg):
    """[(kind, index in the published model)] of the layers held."""
    kept = cfg.get("kept_layers", range(cfg["num_hidden_layers"]))
    return [(cfg["layer_types"][i], i) for i in kept]


def leaves_of(kind):
    """The mixer's leaf names of a layer kind."""
    return _LEAVES[kind]


def mamba_sizes(cfg):
    """(channels E, states N, taps K, rank R of dt): `mamba_ssm`'s
    defaults unless the configuration gives them."""
    d = cfg["hidden_size"]
    rank = cfg.get("mamba_dt_rank")
    return (cfg.get("mamba_expand", 2) * d, cfg.get("mamba_d_state", 16),
            cfg.get("mamba_d_conv", 4),
            rank if rank is not None else -(-d // 16))


def _shapes(cfg):
    """{leaf name: (shape, kind of start)} in a fixed order."""
    d, mlp = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    inner, n, taps, rank = mamba_sizes(cfg)
    mixers = {
        "in_proj": ((d, 2 * inner), "normal"),
        "conv_w": ((inner, taps), "conv"), "conv_b": ((inner,), "conv"),
        "x_proj": ((inner, rank + 2 * n), "normal"),
        "dt_proj": ((rank, inner), "dt_proj"),
        "dt_bias": ((inner,), "dt_bias"), "A_log": ((inner, n), "a_log"),
        "D": ((inner,), "ones"), "out_proj": ((inner, d), "normal"),
        "q": ((d, heads * hd), "normal"), "k": ((d, kv * hd), "normal"),
        "v": ((d, kv * hd), "normal"), "o": ((heads * hd, d), "normal"),
        "q_b": ((heads * hd,), "zeros"), "k_b": ((kv * hd,), "zeros"),
        "v_b": ((kv * hd,), "zeros"), "o_b": ((d,), "zeros"),
        "subln": ((2 * hd,), "ones"),
        **{name: ((hd,), "lambda") for name in _LAMBDAS},
    }
    gmu = {"in_proj": ((d, inner), "normal"),
           "out_proj": ((inner, d), "normal")}
    out = {"emb": ((cfg["vocab_size"], d), "normal"),
           "final_norm": ((d,), "ones"), "final_norm_b": ((d,), "zeros")}
    for i, (kind, _) in enumerate(_layers(cfg)):
        p = "l%d." % i
        for leaf in _LEAVES[kind]:
            out[p + leaf] = (gmu if kind == "gmu" else mixers)[leaf]
        out[p + "norm1"] = ((d,), "ones")
        out[p + "norm1_b"] = ((d,), "zeros")
        out[p + "mlp_in"] = ((d, 2 * mlp), "normal")
        out[p + "mlp_out"] = ((mlp, d), "normal")
        out[p + "norm2"] = ((d,), "ones")
        out[p + "norm2_b"] = ((d,), "zeros")
    return out


def _start(key, shape, kind, taps):
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "lambda":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "conv":  # as torch.nn.Conv1d starts a depthwise filter
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if kind == "dt_proj":  # uniform +-R^-1/2, as mamba_ssm starts it
        bound = shape[0] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if kind == "a_log":  # A[c, n] = -(n + 1)
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    if kind == "dt_bias":  # softplus(dt_bias) log-uniform in [0.001, 0.1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def init_weights(seed, cfg):
    shapes = _shapes(cfg)
    taps = mamba_sizes(cfg)[2]

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


def lambda_init(index):
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) \
        * scale + bias


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def _mlp(u, w_in, w_out, quant):
    gate, up = jnp.split(common.matmul(u, w_in, quant), 2, axis=-1)
    return common.matmul(up * jax.nn.silu(gate), w_out, quant)


def _recurrence(dt, x, a, b_mat, c_mat):
    """y_t[c] = sum_n S_t[c, n] C_t[n] with S_t[c, n] = exp(dt_t[c]
    A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n], token by token. dt and
    x [B, T, E], a [E, N], b_mat and c_mat [B, T, N]. The state is kept
    every `_SEGMENT` tokens for backward and stepped again in between."""
    batch, t, channels = x.shape
    seg = min(_SEGMENT, t)
    pad = -t % seg   # dt 0 there: the state stands still, outputs dropped
    dt, x, b_mat, c_mat = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                           for v in (dt, x, b_mat, c_mat))

    def token(state, xs):
        dt_t, x_t, b_t, c_t = xs            # [B, E] [B, E] [B, N] [B, N]
        state = jnp.exp(dt_t[..., None] * a) * state \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    def by_segment(v):   # [B, T, W] -> [T / seg, seg, B, W]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(((t + pad) // seg, seg) + v.shape[1:])

    _, y = jax.lax.scan(
        segment, jnp.zeros((batch, channels, a.shape[1]), jnp.float32),
        tuple(by_segment(v) for v in (dt, x, b_mat, c_mat)))
    return jnp.moveaxis(y.reshape((t + pad,) + y.shape[2:]), 0, 1)[:, :t]


def _mamba(u, w, cfg, quant):
    """(out, the scan's output y before the gate)."""
    t = u.shape[1]
    _, n, taps, rank = mamba_sizes(cfg)
    x, z = jnp.split(common.matmul(u, w["in_proj"], quant), 2, axis=-1)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[:, i:i + t] * w["conv_w"][:, i]
                        for i in range(taps)) + w["conv_b"])
    r, b_mat, c_mat = jnp.split(common.matmul(x, w["x_proj"], quant),
                                [rank, rank + n], axis=-1)
    dt = jax.nn.softplus(common.matmul(r, w["dt_proj"], quant)
                         + w["dt_bias"])
    y = _recurrence(dt, x, -jnp.exp(w["A_log"]), b_mat, c_mat) + w["D"] * x
    return common.matmul(y * jax.nn.silu(z), w["out_proj"], quant), y


def _gmu(u, memory, w, quant):
    return common.matmul(
        memory * jax.nn.silu(common.matmul(u, w["in_proj"], quant)),
        w["out_proj"], quant)


def _attention(u, kv, w, index, window, cfg, quant):
    """(out, the keys and values side by side [B, T, 2 * kv heads * head
    size]): differential attention of `u`'s queries over `kv`, or over
    `u`'s own keys and values where `kv` is None."""
    b, t, d = u.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    pairs, groups = kvh // 2, heads // kvh
    eps = cfg["layer_norm_eps"]
    q = common.matmul(u, w["q"], quant) + w["q_b"]
    if kv is None:
        kv = jnp.concatenate([common.matmul(u, w[n], quant) + w[n + "_b"]
                              for n in ("k", "v")], axis=-1)
    k, v = jnp.split(kv, 2, axis=-1)
    # heads in pairs: [..., pair, first or second of it, head size]
    q = q.reshape(b, t, pairs, groups, 2, hd)
    k = k.reshape(b, t, pairs, 2, hd)
    v_h = jnp.moveaxis(v.reshape(b, t, pairs, 2 * hd), 1, 2)  # [B, P, T, 2hd]
    lam_init = lambda_init(index)
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) \
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam_init
    block = min(_QUERY_BLOCK, t)
    pad = -t % block
    q = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 4)
    keys = jnp.arange(t)

    @jax.checkpoint
    def rows(args):
        q_blk, start = args                       # [B, L, P, G, 2, hd]
        at = (start + jnp.arange(block))[:, None]
        seen = keys[None, :] <= at
        if window is not None:
            seen = seen & (at - keys[None, :] < window)
        maps = []
        for j in (0, 1):
            s = common.matmul(
                jnp.moveaxis(q_blk[..., j, :], 1, 3),         # [B,P,G,L,hd]
                jnp.moveaxis(k[..., j, :], 1, -1)[:, :, None],  # [B,P,1,hd,T]
                quant) * hd ** -0.5
            maps.append(jax.nn.softmax(jnp.where(seen, s, -jnp.inf),
                                       axis=-1))
        both = common.matmul(maps[0] - lam * maps[1], v_h[:, :, None], quant)
        out = _rms_norm(both, w["subln"], eps) * (1.0 - lam_init)
        return jnp.moveaxis(out, 3, 1)            # [B, L, P, G, 2hd]

    n = (t + pad) // block
    out = jax.lax.map(rows, (
        jnp.moveaxis(q.reshape(b, n, block, pairs, groups, 2, hd), 1, 0),
        jnp.arange(n) * block))                   # [n, B, L, P, G, 2hd]
    y = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads * hd)[:, :t]
    return common.matmul(y, w["o"], quant) + w["o_b"], kv


def _layer(h, read, w, kind, index, cfg, quant):
    """(the stream after the layer, what the layer hands to later ones:
    nothing is [B, T, 0])."""
    eps = cfg["layer_norm_eps"]

    def mixer(u, read_, w_):
        if kind == "mamba1":
            return _mamba(u, w_, cfg, quant)
        if kind == "gmu":
            return _gmu(u, read_, w_, quant), None
        return _attention(
            u, read_, w_, index,
            cfg["sliding_window"] if kind == "sliding_attention" else None,
            cfg, quant)

    # a branch at a time in backward: each keeps its input and runs again
    mixed, made = jax.checkpoint(mixer)(
        _layer_norm(h, w["norm1"], w["norm1_b"], eps), read,
        {k: w[k] for k in _LEAVES[kind]})
    h = h + mixed
    fed = jax.checkpoint(lambda u, a, b: _mlp(u, a, b, quant))(
        _layer_norm(h, w["norm2"], w["norm2_b"], eps), w["mlp_in"],
        w["mlp_out"])
    if kind not in _MAKES:
        made = jnp.zeros(h.shape[:2] + (0,), h.dtype)
    return h + fed, made


def _row_by_row(fn, *rows):
    """fn over each row of the batch in turn, every argument [B, ...] seen
    as [1, ...]; backward keeps a row's arguments and computes the row
    again."""
    return jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                       tuple(a[:, None] for a in rows))


def hidden_of(weights, tokens, cfg, quant=None):
    """[B, T, hidden] after the last layer's norm, of int32 tokens."""
    h = common.quantize(weights["emb"], quant)[tokens]
    handed = {}   # "memory" and "kv": the nearest earlier layer's
    nothing = jnp.zeros(h.shape[:2] + (0,), h.dtype)
    for i, (kind, index) in enumerate(_layers(cfg)):
        prefix = "l%d." % i
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        read = handed[_READS[kind]] if kind in _READS else None
        h, made = (x[:, 0] for x in _row_by_row(
            lambda row, read_: _layer(row, read_ if kind in _READS else None,
                                      w, kind, index, cfg, quant),
            h, nothing if read is None else read))
        if kind in _MAKES:
            handed[_MAKES[kind]] = made
    return _layer_norm(h, weights["final_norm"], weights["final_norm_b"],
                       cfg["layer_norm_eps"])


def logits_of(weights, tokens, cfg, quant=None):
    """[B, T, vocab] float32 logits of int32 tokens [B, T]."""
    return common.matmul(hidden_of(weights, tokens, cfg, quant),
                         weights["emb"].T, quant)


def loss(weights, state, batch, cfg, quant=None):
    """(mean token cross entropy over the batch's valid positions, {})."""
    tokens, targets, lengths = batch
    valid = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]

    def row_cost(h, y, seen):
        logits = common.quantize(
            common.matmul(h, weights["emb"].T, quant), quant)
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                     y[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(seen, picked, 0.0))

    costs = _row_by_row(row_cost, hidden_of(weights, tokens, cfg, quant),
                        targets, valid)
    return jnp.sum(costs) / jnp.sum(valid), {}
