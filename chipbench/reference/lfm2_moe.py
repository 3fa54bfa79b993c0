"""Plain reference of the `lfm2-8b-a1b` configuration (LiquidAI
LFM2-8B-A1B, `model_type` `lfm2_moe`): gated short convolutions beside
rotary grouped-query attention whose queries and keys are normalised head
by head, two leading layers with a dense gated MLP, then layers whose
feed-forward is a sparse mixture of experts with sigmoid scores, a
selection bias and no shared expert. Written from the layer equations in
float32 at `highest`; it imports nothing of the program.

    h0 = E[ids]
    h += mixer(N(h));   h += ffn(N(h));   N(x) = x / rms(x) * scale, eps norm_eps
    logits = N(h) E^T;   cost = mean token cross entropy over valid positions

`conv`:
    [B, C, x] = u W_in;   y = C * conv1d_causal(B * x; w);   out = y W_out      3 taps, no bias
`full_attention` (H query heads over KV key-value heads of D):
    q, k, v = u W_q, u W_k, u W_v;   q, k <- N(q), N(k) head by head, scales [D]
    q, k <- rotary(theta, the whole D, value i paired with i + D/2, positions 0..T-1)
    out = softmax(q k^T / sqrt(D) + causal) v W_o
dense ffn (published layers under `num_dense_layers`):
    (up * silu(gate)) W_2,  [gate, up] = u W_1
expert ffn (E = `num_experts_published` experts, k a token):
    s = sigmoid(u W_r);   chosen = the k largest of s + expert_bias
    w = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor      (`norm_topk_prob`)
    out = sum over chosen e with first_expert <= e < first_expert + num_experts of w_e * expert_e(u)
each expert a gated MLP of `moe_intermediate_size`. **The configuration
holds `num_experts` of the E experts**, those from `first_expert` on: the
router scores all E and chooses among all E, and what the experts held
elsewhere would add is left out, here as in the program (guide section 4:
their chips compute it). No sorting and no kernels: every held expert is
applied to every token, and its output is multiplied by the token's weight
for it, zero where the token did not choose it. `expert_bias` selects and
is never differentiated: it is state that no step changes, not a weight.

Departures from the published description, all listed in the
configuration's `assumed` too: sigmoid scores and the 1e-6 are the
family's modelling code's (the config has no key for either); the table
is tied (the parameter count says so); the gate and up matrices are one
matrix, gate first. The builder had no access to the published
`modeling_lfm2_moe.py`.

So that three steps fit beside the float32 weights, velocity and gradient,
a layer takes the rows of the batch one after the other and backward
keeps each row's input to it only (`jax.checkpoint`), then each branch's
input in turn, and inside the expert branch one expert's at a time;
attention keeps a block of queries' scores at a time: the values are
those of the equations, computed again.
"""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

_QUERY_BLOCK = 256   # queries whose scores are alive together

_CONV = ("in_proj", "conv_w", "out_proj")
_ATTENTION = ("q", "k", "v", "o", "q_norm", "k_norm")
_MIXERS = {"conv": _CONV, "full_attention": _ATTENTION}
_DENSE = ("mlp_in", "mlp_out")
_EXPERTS = ("router", "w_in", "w_out")


def layers_of(cfg):
    """[(mixer kind, whether the feed-forward is the experts')] of the
    layers held: the first `num_hidden_layers` of `layer_types`."""
    return [(kind, i >= cfg["num_dense_layers"]) for i, kind in
            enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]])]


def leaves_of(kind, sparse):
    """(the mixer's leaf names, the feed-forward's) of a layer."""
    return _MIXERS[kind], _EXPERTS if sparse else _DENSE


def experts_of(cfg):
    """(all experts the router scores, those held here, the first held)."""
    return (cfg.get("num_experts_published", cfg["num_experts"]),
            cfg["num_experts"], cfg.get("first_expert", 0))


def _shapes(cfg):
    """{leaf name: (shape, kind of start)} in a fixed order."""
    d, mlp = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    total, here, _ = experts_of(cfg)
    width, taps = cfg["moe_intermediate_size"], cfg["conv_L_cache"]
    leaves = {
        "in_proj": ((d, 3 * d), "normal"), "conv_w": ((d, taps), "conv"),
        "out_proj": ((d, d), "normal"),
        "q": ((d, heads * hd), "normal"), "k": ((d, kv * hd), "normal"),
        "v": ((d, kv * hd), "normal"), "o": ((heads * hd, d), "normal"),
        "q_norm": ((hd,), "ones"), "k_norm": ((hd,), "ones"),
        "mlp_in": ((d, 2 * mlp), "normal"), "mlp_out": ((mlp, d), "normal"),
        "router": ((d, total), "normal"),
        "w_in": ((here, d, 2 * width), "normal"),
        "w_out": ((here, width, d), "normal"),
    }
    out = {"emb": ((cfg["vocab_size"], d), "normal"),
           "final_norm": ((d,), "ones")}
    for i, (kind, sparse) in enumerate(layers_of(cfg)):
        p = "l%d." % i
        mixer, ffn = leaves_of(kind, sparse)
        out[p + "norm1"] = ((d,), "ones")
        for leaf in mixer:
            out[p + leaf] = leaves[leaf]
        out[p + "norm2"] = ((d,), "ones")
        for leaf in ffn:
            out[p + leaf] = leaves[leaf]
    return out


def parameter_count(cfg):
    """Parameters of the configuration, the selection biases among them
    (a buffer of the published model, counted with its weights)."""
    total = experts_of(cfg)[0]
    biases = sum(total for _, sparse in layers_of(cfg)
                 if sparse and cfg["use_expert_bias"])
    return biases + sum(int(np.prod(shape))
                        for shape, _ in _shapes(cfg).values())


def _start(key, shape, kind, taps):
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "conv":  # as torch.nn.Conv1d starts a depthwise filter
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    raise ValueError(kind)


def init_weights(seed, cfg):
    """(weights, state): the state is every expert layer's selection
    bias, zeros."""
    shapes = _shapes(cfg)
    taps = cfg["conv_L_cache"]

    @jax.jit
    def make(key):
        return {name: _start(jax.random.fold_in(key, i), shape, kind, taps)
                for i, (name, (shape, kind)) in enumerate(shapes.items())}

    state = {"l%d.expert_bias" % i: jnp.zeros((experts_of(cfg)[0],),
                                              jnp.float32)
             for i, (_, sparse) in enumerate(layers_of(cfg))
             if sparse and cfg["use_expert_bias"]}
    return make(common.seed_key(seed)), state


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
    gate, up = jnp.split(common.matmul(u, w_in, quant), 2, axis=-1)
    return common.matmul(up * jax.nn.silu(gate), w_out, quant)


def short_conv(u, w, cfg, quant=None):
    """u [B, T, d] through the gated short convolution."""
    t, taps = u.shape[1], cfg["conv_L_cache"]
    b_gate, c_gate, x = jnp.split(common.matmul(u, w["in_proj"], quant), 3,
                                  axis=-1)
    padded = jnp.pad(b_gate * x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = c_gate * sum(padded[:, i:i + t] * w["conv_w"][:, i]
                     for i in range(taps))
    return common.matmul(y, w["out_proj"], quant)


def rotary(x, theta):
    """x [B, T, H, D] turned by its positions 0..T-1: value i with value
    i + D/2, by the angle position * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inverse
    cos, sin = (f(angles)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def attention(u, w, cfg, quant=None):
    """u [B, T, d] through causal grouped-query attention with per-head
    norms of queries and keys and rotary positions."""
    b, t, d = u.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, groups = d // heads, heads // kvh
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    q = common.matmul(u, w["q"], quant).reshape(b, t, heads, hd)
    k = common.matmul(u, w["k"], quant).reshape(b, t, kvh, hd)
    v = common.matmul(u, w["v"], quant).reshape(b, t, kvh, hd)
    q = rotary(_rms_norm(q, w["q_norm"], eps), theta)
    k = rotary(_rms_norm(k, w["k_norm"], eps), theta)
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
        p = jax.nn.softmax(jnp.where(keys[None, :] <= at, s, -jnp.inf),
                           axis=-1)
        out = common.matmul(p, jnp.moveaxis(v, 1, 2)[:, :, None], quant)
        return jnp.moveaxis(out, 3, 1)            # [B, L, KV, G, hd]

    n = (t + pad) // block
    out = jax.lax.map(rows, (
        jnp.moveaxis(q.reshape(b, n, block, kvh, groups, hd), 1, 0),
        jnp.arange(n) * block))
    y = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads * hd)[:, :t]
    return common.matmul(y, w["o"], quant)


def routing(u, router, bias, cfg, quant=None):
    """[..., E] float32: each token's weight for every expert the router
    scores, zero for those it did not choose."""
    total = experts_of(cfg)[0]
    scores = jax.nn.sigmoid(common.matmul(u, router, quant))
    select = scores + bias if bias is not None else scores
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(select),
                              cfg["num_experts_per_tok"])
    picked = jnp.sum(jax.nn.one_hot(chosen, total, dtype=scores.dtype),
                     axis=-2)
    weights = scores * picked
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return weights * cfg["routed_scaling_factor"]


def experts(u, w, bias, cfg, quant=None, first=None):
    """u [B, T, d] through the expert feed-forward: the experts
    `w["w_in"]` holds, the global experts from `first` on (the
    configuration's `first_expert` by default), each applied to every
    token and weighted by the routing, zero where a token chose
    another."""
    first = experts_of(cfg)[2] if first is None else first
    weights = routing(u, w["router"], bias, cfg, quant)

    def one(carry, xs):
        w_in, w_out, weight = xs                       # weight [B, T]
        return carry + weight[..., None] * jax.checkpoint(
            lambda a, b, c: _mlp(a, b, c, quant))(u, w_in, w_out), None

    held = w["w_in"].shape[0]
    here = jnp.moveaxis(weights[..., first:first + held], -1, 0)
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (w["w_in"], w["w_out"], here))
    return out


def _mixed(h, w, kind, cfg, quant):
    """The stream after a layer's mixer."""
    mixer = short_conv if kind == "conv" else attention
    # a branch at a time in backward: each keeps its input and runs again
    return h + jax.checkpoint(lambda u, w_: mixer(u, w_, cfg, quant))(
        _rms_norm(h, w["norm1"], cfg["norm_eps"]),
        {k: w[k] for k in _MIXERS[kind]})


def _layer(h, w, bias, kind, sparse, cfg, quant):
    h = _mixed(h, w, kind, cfg, quant)
    u = _rms_norm(h, w["norm2"], cfg["norm_eps"])
    if sparse:
        return h + jax.checkpoint(
            lambda u_, w_: experts(u_, w_, bias, cfg, quant))(
                u, {k: w[k] for k in _EXPERTS})
    return h + jax.checkpoint(lambda u_, a, b: _mlp(u_, a, b, quant))(
        u, w["mlp_in"], w["mlp_out"])


def _row_by_row(fn, *rows):
    """fn over each row of the batch in turn, every argument [B, ...] seen
    as [1, ...]; backward keeps a row's arguments and computes the row
    again."""
    return jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                       tuple(a[:, None] for a in rows))


def hidden_of(weights, state, tokens, cfg, quant=None):
    """[B, T, hidden] after the last layer's norm, of int32 tokens."""
    h = common.quantize(weights["emb"], quant)[tokens]
    for i, (kind, sparse) in enumerate(layers_of(cfg)):
        prefix = "l%d." % i
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        bias = state.get(prefix + "expert_bias")
        h = _row_by_row(lambda row: _layer(row, w, bias, kind, sparse, cfg,
                                           quant), h)[:, 0]
    return _rms_norm(h, weights["final_norm"], cfg["norm_eps"])


def choices_of(weights, state, tokens, cfg):
    """{index of an expert layer: [B, T, k] the experts each token
    chooses there}, in float32: what `chipbench/routing_agreement.py`
    holds the program's own choices against."""
    h = weights["emb"][tokens]
    out = {}
    for i, (kind, sparse) in enumerate(layers_of(cfg)):
        prefix = "l%d." % i
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        bias = state.get(prefix + "expert_bias")
        if sparse:
            u = _rms_norm(_mixed(h, w, kind, cfg, None), w["norm2"],
                          cfg["norm_eps"])
            scores = jax.nn.sigmoid(common.matmul(u, w["router"]))
            out[i] = jax.lax.top_k(
                scores + bias if bias is not None else scores,
                cfg["num_experts_per_tok"])[1]
        h = _layer(h, w, bias, kind, sparse, cfg, None)
    return out


def logits_of(weights, state, tokens, cfg, quant=None):
    """[B, T, vocab] float32 logits of int32 tokens [B, T]."""
    return common.matmul(hidden_of(weights, state, tokens, cfg, quant),
                         weights["emb"].T, quant)


def loss(weights, state, batch, cfg, quant=None):
    """(mean token cross entropy over the batch's valid positions, the
    state as it was: no step moves a selection bias)."""
    tokens, targets, lengths = batch
    valid = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]

    def row_cost(h, y, seen):
        logits = common.quantize(
            common.matmul(h, weights["emb"].T, quant), quant)
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                     y[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(seen, picked, 0.0))

    costs = _row_by_row(row_cost,
                        hidden_of(weights, state, tokens, cfg, quant),
                        targets, valid)
    return jnp.sum(costs) / jnp.sum(valid), state
