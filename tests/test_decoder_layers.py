"""`layer/decoder.py` and `ops/attention.py`: each new layer against its
equation written out (the Gated DeltaNet mixer and normalised queries
and keys among them), blockwise attention against full scores, causality,
the padded tail, packed rows refused, `checkgrad` on each layer, and a
recomputed block against the same block kept, whole and with the values
a block may keep for backward (the MLP's first product and the residual
after the mixer; a Mamba-2 mixer's first product). Float32 at `highest`."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import data_type
from paddle_tpu import layer as L
from paddle_tpu.attr import ParamAttr
from paddle_tpu.checkgrad import check_layer_grad
from paddle_tpu.core.sequence import PackedSequenceBatch, SequenceBatch
from paddle_tpu.layer import decoder
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.ops import attention as attention_ops
from paddle_tpu.topology import Topology
from paddle_tpu.utils.error import EnforceError


@pytest.fixture(autouse=True)
def _highest():
    L.reset_name_counters()
    with jax.default_matmul_precision("highest"):
        yield
    jax.config.update("jax_enable_x64", False)  # check_layer_grad sets it


def _seq(seed, batch=2, t=12, width=8, lengths=(12, 9)):
    rng = np.random.default_rng(seed)
    data = jnp.asarray(rng.standard_normal((batch, t, width)), jnp.float32)
    return SequenceBatch(data, jnp.asarray(lengths, jnp.int32))


def _input(width, name="x"):
    return L.data(name=name, type=data_type.dense_vector_sequence(width))


def _apply(node, feed, seed=0):
    topo = Topology(node)
    params = topo.init_params(jax.random.PRNGKey(seed))
    values, _ = topo.apply(params, feed, mode="test")
    return values[node.name], params


def silu(x):
    return x / (1.0 + np.exp(-x))


def test_rms_norm_is_its_formula():
    x = _seq(0)
    node = L.rms_norm(input=_input(8), eps=1e-5, name="n")
    out, params = _apply(node, {"x": x})
    w = np.linspace(0.5, 1.5, 8).astype(np.float32)
    np.testing.assert_array_equal(params["n.w0"], np.ones(8, np.float32))
    topo = Topology(node)
    out = topo.apply({"n.w0": jnp.asarray(w)}, {"x": x})[0]["n"]
    d = np.asarray(x.data, np.float64)
    want = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(out.data, want, atol=1e-5)
    np.testing.assert_array_equal(out.lengths, x.lengths)


def test_gated_rms_norm_gates_before_it_normalises():
    x, z = _seq(1), _seq(2)
    node = L.rms_norm(input=_input(8), gate=_input(8, "z"), name="n")
    out, _ = _apply(node, {"x": x, "z": z})
    d = np.asarray(x.data, np.float64) * silu(np.asarray(z.data, np.float64))
    want = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(out.data, want, atol=1e-5)


def test_gated_mlp_is_its_formula():
    x = _seq(3)
    node = L.gated_mlp(input=_input(8), size=16, name="m")
    out, params = _apply(node, {"x": x})
    assert params["m.w0"].shape == (8, 32) and params["m.w1"].shape == (16, 8)
    ab = np.asarray(x.data, np.float64) @ np.asarray(params["m.w0"],
                                                    np.float64)
    want = (silu(ab[..., :16]) * ab[..., 16:]) @ np.asarray(params["m.w1"],
                                                           np.float64)
    np.testing.assert_allclose(out.data, want, atol=1e-5)


def full_scores(q, k, v, scale, causal, lengths):
    """Attention with the whole [T, T] score matrix, grouped heads by
    repeating the key-value heads."""
    groups = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, groups, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = q.shape[1]
    seen = jnp.ones((t, t), bool)
    if causal:
        seen = jnp.tril(seen)
    seen = seen[None, None] & (jnp.arange(t)[None, :]
                               < lengths[:, None])[:, None, None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _qkv(seed, t, heads=4, kv=2, d=8):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((2, t, heads, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((2, t, kv, d)), jnp.float32),
            jnp.asarray(rng.standard_normal((2, t, kv, d)), jnp.float32))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,block", [(24, 8), (21, 8), (7, 512)])
def test_blockwise_attention_is_attention_with_full_scores(t, block, causal):
    q, k, v = _qkv(4, t)
    lengths = jnp.asarray([t, t - 3])
    want = full_scores(q, k, v, 0.3, causal, lengths)
    got = attention_ops.blockwise_attention(q, k, v, 0.3, causal, lengths,
                                            block)
    valid = (jnp.arange(t)[None, :] < lengths[:, None])[..., None, None]
    np.testing.assert_allclose(jnp.where(valid, got, 0),
                               jnp.where(valid, want, 0), atol=2e-5)


def test_blockwise_attention_has_full_scores_gradients():
    q, k, v = _qkv(5, 21)
    lengths = jnp.asarray([21, 16])
    valid = (jnp.arange(21)[None, :] < lengths[:, None])[..., None, None]

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.where(valid, jnp.sin(fn(*a)),
                                                     0)),
                        argnums=(0, 1, 2))(q, k, v)

    want = through(lambda *a: full_scores(*a, 0.3, True, lengths))
    got = through(lambda *a: attention_ops.blockwise_attention(
        *a, 0.3, True, lengths, 8))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_context_parallels_full_attention_takes_the_same_step():
    from paddle_tpu.parallel.context_parallel import full_attention

    q, k, v = _qkv(6, 16, heads=4, kv=4)
    lengths = jnp.asarray([16, 11])
    np.testing.assert_allclose(
        full_attention(q, k, v, causal=True, scale=0.3, lengths=lengths),
        full_scores(q, k, v, 0.3, True, lengths), atol=2e-5)


def _mixers():
    return {
        "mamba2": lambda x: L.mamba2(input=x, heads=4, head_dim=4, state=8,
                                     groups=2, chunk=4, name="mix"),
        "gqa_attention": lambda x: L.gqa_attention(
            input=x, heads=4, kv_heads=2, head_dim=4, scale=0.25, block=4,
            name="mix"),
        "gqa_attention_qk_norm": lambda x: L.gqa_attention(
            input=x, heads=4, kv_heads=2, head_dim=4, block=4, qk_norm=True,
            name="mix"),
        "gated_delta_net": lambda x: L.gated_delta_net(
            input=x, heads=2, key_dim=4, value_dim=6, chunk=4, name="mix"),
    }


@pytest.mark.parametrize("kind", sorted(_mixers()))
def test_a_mixer_sees_no_later_token_and_nothing_of_the_tail(kind):
    x = _seq(7, t=14, lengths=(14, 9))
    node = _mixers()[kind](_input(8))
    out, params = _apply(node, {"x": x})
    topo = Topology(node)
    moved = SequenceBatch(x.data.at[:, 10].add(1.0), x.lengths)
    out2 = topo.apply(params, {"x": moved}, mode="test")[0]["mix"]
    np.testing.assert_array_equal(out2.data[:, :10], out.data[:, :10])
    assert float(jnp.abs(out2.data[0, 10:] - out.data[0, 10:]).max()) > 1e-6
    # row 1 has 9 tokens: its valid outputs do not read positions 9..13
    np.testing.assert_array_equal(out2.data[1, :9], out.data[1, :9])


@pytest.mark.parametrize("kind", sorted(_mixers()))
def test_a_mixer_refuses_packed_rows(kind):
    x = _seq(8)
    packed = PackedSequenceBatch(x.data, x.lengths,
                                 jnp.zeros(x.data.shape[:2], jnp.int32))
    node = _mixers()[kind](_input(8))
    topo = Topology(node)
    params = topo.init_params(jax.random.PRNGKey(0))
    with pytest.raises(EnforceError, match="packed"):
        topo.apply(params, {"x": packed}, mode="test")


def test_the_mamba2_mixer_is_its_equations_token_by_token():
    heads, hd, n, groups, taps = 4, 4, 8, 2, 4
    x = _seq(9, t=11, lengths=(11, 8))
    node = L.mamba2(input=_input(8), heads=heads, head_dim=hd, state=n,
                    groups=groups, chunk=4, name="mix")
    out, p = _apply(node, {"x": x})
    p = {k.split(".", 1)[1]: np.asarray(v, np.float64) for k, v in p.items()}
    inner, per = heads * hd, heads // groups
    want = np.zeros((2, 11, 8))
    for row, length in enumerate((11, 8)):
        u = np.asarray(x.data[row, :length], np.float64)
        zxd = u @ p["in_proj"]
        z, xbc, dt = np.split(zxd, [inner, inner + inner + 2 * groups * n],
                              axis=-1)
        padded = np.concatenate([np.zeros((taps - 1, xbc.shape[1])), xbc])
        xbc = silu(p["conv_b"] + sum(padded[k:k + length] * p["conv_w"][:, k]
                                     for k in range(taps)))
        xs, b_mat, c_mat = np.split(xbc, [inner, inner + groups * n], axis=-1)
        dt = np.log1p(np.exp(dt + p["dt_bias"]))
        a = -np.exp(p["A_log"])
        state = np.zeros((heads, hd, n))
        y = np.zeros((length, heads, hd))
        for t in range(length):
            for h in range(heads):
                g = h // per
                x_t = xs[t, h * hd:(h + 1) * hd]
                state[h] = np.exp(dt[t, h] * a[h]) * state[h] + dt[t, h] \
                    * np.outer(x_t, b_mat[t, g * n:(g + 1) * n])
                y[t, h] = state[h] @ c_mat[t, g * n:(g + 1) * n] \
                    + p["D"][h] * x_t
        y = y.reshape(length, inner) * silu(z)
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) * p["norm_w"]
        want[row, :length] = y @ p["out_proj"]
    valid = np.arange(11)[None, :] < np.asarray([11, 8])[:, None]
    np.testing.assert_allclose(np.where(valid[..., None], out.data, 0), want,
                               atol=2e-5)


def test_the_gated_delta_net_mixer_is_its_equations_token_by_token():
    heads, dk, dv, taps = 2, 4, 6, 4
    x = _seq(12, t=11, lengths=(11, 8))
    node = L.gated_delta_net(input=_input(8), heads=heads, key_dim=dk,
                             value_dim=dv, chunk=4, eps=1e-6,
                             initial_std=0.5, name="mix")
    out, p = _apply(node, {"x": x})
    p = {k.split(".", 1)[1]: np.asarray(v, np.float64) for k, v in p.items()}
    assert sorted(p) == ["A_log", "a", "b", "conv_w", "dt_bias", "g", "k",
                         "norm_w", "o", "q", "v"]
    p["norm_w"] = np.linspace(0.5, 1.5, dv)
    out = Topology(node).apply(
        {"mix." + k: jnp.asarray(v, jnp.float32) for k, v in p.items()},
        {"x": x}, mode="test")[0]["mix"]
    want = np.zeros((2, 11, 8))
    for row, length in enumerate((11, 8)):
        u = np.asarray(x.data[row, :length], np.float64)
        qkv = np.concatenate([u @ p["q"], u @ p["k"], u @ p["v"]], axis=-1)
        padded = np.concatenate([np.zeros((taps - 1, qkv.shape[1])), qkv])
        qkv = silu(sum(padded[k:k + length] * p["conv_w"][:, k]
                       for k in range(taps)))
        q, k, v = np.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
        beta = 2.0 / (1.0 + np.exp(-(u @ p["b"])))
        alpha = np.exp(-np.exp(p["A_log"])
                       * np.log1p(np.exp(u @ p["a"] + p["dt_bias"])))
        gate = silu(u @ p["g"])
        y = np.zeros((length, heads, dv))
        for h in range(heads):
            state = np.zeros((dk, dv))
            for t in range(length):
                q_t, k_t = (z[t, h * dk:(h + 1) * dk] for z in (q, k))
                q_t = q_t / np.sqrt(q_t @ q_t + 1e-6) * dk ** -0.5
                k_t = k_t / np.sqrt(k_t @ k_t + 1e-6)
                v_t = v[t, h * dv:(h + 1) * dv]
                state = alpha[t, h] * (
                    state - beta[t, h] * np.outer(k_t, k_t @ state)) \
                    + beta[t, h] * np.outer(k_t, v_t)
                o_t = state.T @ q_t
                y[t, h] = o_t / np.sqrt((o_t * o_t).mean() + 1e-6) \
                    * p["norm_w"] * gate[t, h * dv:(h + 1) * dv]
        want[row, :length] = y.reshape(length, heads * dv) @ p["o"]
    valid = np.arange(11)[None, :] < np.asarray([11, 8])[:, None]
    np.testing.assert_allclose(np.where(valid[..., None], out.data, 0), want,
                               atol=2e-5)


def test_normalised_queries_and_keys_are_normalised_before_the_split():
    """q and k are RMS-normalised over all heads at once, each with its
    own learned scale; with scales of one and a single head that is
    attention over unit-RMS queries and keys."""
    x = _seq(13, t=10, lengths=(10, 7))
    node = L.gqa_attention(input=_input(8), heads=2, kv_heads=1, head_dim=4,
                           block=4, qk_norm=True, eps=1e-6, initial_std=0.5,
                           name="mix")
    out, p = _apply(node, {"x": x})
    assert p["mix.q_norm"].shape == (8,) and p["mix.k_norm"].shape == (4,)
    p["mix.q_norm"] = jnp.linspace(0.5, 1.5, 8)
    p["mix.k_norm"] = jnp.linspace(1.5, 0.5, 4)
    out = Topology(node).apply(p, {"x": x}, mode="test")[0]["mix"]

    def rms(z, w):
        return z / jnp.sqrt(jnp.mean(z * z, -1, keepdims=True) + 1e-6) * w

    q = rms(x.data @ p["mix.q"], p["mix.q_norm"]).reshape(2, 10, 2, 4)
    k = rms(x.data @ p["mix.k"], p["mix.k_norm"]).reshape(2, 10, 1, 4)
    v = (x.data @ p["mix.v"]).reshape(2, 10, 1, 4)
    want = full_scores(q, k, v, 0.5, True, x.lengths).reshape(2, 10, 8) \
        @ p["mix.o"]
    valid = (jnp.arange(10)[None, :] < x.lengths[:, None])[..., None]
    np.testing.assert_allclose(jnp.where(valid, out.data, 0),
                               jnp.where(valid, want, 0), atol=2e-5)


def test_attention_without_the_norm_has_no_norm_parameters():
    node = L.gqa_attention(input=_input(8), heads=2, kv_heads=1, head_dim=4,
                           name="mix")
    assert sorted(s.name for s in node.param_specs) == [
        "mix.k", "mix.o", "mix.q", "mix.v"]


def _checkgrad_nodes():
    x = lambda: _input(6)
    return {
        "rms_norm": lambda: L.rms_norm(input=x()),
        "gated_rms_norm": lambda: L.rms_norm(input=x(), gate=_input(6, "z")),
        "gated_mlp": lambda: L.gated_mlp(
            input=x(), size=5, param_attr=ParamAttr(initial_std=0.5)),
        "mamba2": lambda: L.mamba2(input=x(), heads=2, head_dim=3, state=4,
                                   chunk=4, initial_std=0.5),
        "gqa_attention": lambda: L.gqa_attention(
            input=x(), heads=4, kv_heads=2, head_dim=3, block=4,
            initial_std=0.5),
        "gqa_attention_qk_norm": lambda: L.gqa_attention(
            input=x(), heads=4, kv_heads=2, head_dim=3, block=4,
            qk_norm=True, initial_std=0.5),
        "gated_delta_net": lambda: L.gated_delta_net(
            input=x(), heads=2, key_dim=3, value_dim=4, chunk=4,
            initial_std=0.5),
        "lm_head": lambda: L.lm_head(
            input=x(), vocab=7, scale=0.5,
            param_attr=ParamAttr(name="table", initial_std=0.5)),
    }


@pytest.mark.parametrize("kind", sorted(_checkgrad_nodes()))
def test_checkgrad_on_each_new_layer(kind):
    node = _checkgrad_nodes()[kind]()
    feed = {"x": _seq(10, t=9, width=6, lengths=(9, 6))}
    if kind == "gated_rms_norm":
        feed["z"] = _seq(11, t=9, width=6, lengths=(9, 6))
    assert check_layer_grad(node, feed, rtol=5e-3, atol=1e-5)


def test_checkgrad_on_the_token_cost():
    logits = L.lm_head(input=_input(6), vocab=7,
                       param_attr=ParamAttr(name="table", initial_std=0.5))
    targets = L.data(name="y", type=data_type.integer_value_sequence(7))
    cost = L.lm_cost(input=logits, label=targets)
    y = SequenceBatch(jnp.asarray(np.random.default_rng(0).integers(
        0, 7, (2, 9)), jnp.int32), jnp.asarray([9, 6], jnp.int32))
    feed = {"x": _seq(12, t=9, width=6, lengths=(9, 6)), "y": y}
    assert check_layer_grad(cost, feed, rtol=5e-3, atol=1e-5)


def test_the_token_cost_is_the_mean_over_valid_positions():
    rng = np.random.default_rng(13)
    logits = rng.standard_normal((2, 9, 7)).astype(np.float32)
    y = rng.integers(0, 7, (2, 9))
    lengths = np.asarray([9, 5])
    node = L.lm_cost(
        input=L.data(name="l", type=data_type.dense_vector_sequence(7)),
        label=L.data(name="y", type=data_type.integer_value_sequence(7)))
    topo = Topology(node)

    def run(logits, y):
        feed = {"l": SequenceBatch(jnp.asarray(logits), jnp.asarray(lengths)),
                "y": SequenceBatch(jnp.asarray(y, jnp.int32),
                                   jnp.asarray(lengths))}
        return topo.apply({}, feed)[0][node.name]

    rows = run(logits, y)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    picked = np.take_along_axis(logp, y[..., None], -1)[..., 0]
    valid = np.arange(9)[None, :] < lengths[:, None]
    assert rows.shape == (2,)
    np.testing.assert_allclose(float(jnp.mean(rows)),
                               -(picked * valid).sum() / valid.sum(),
                               rtol=1e-5)
    # what lies in the padded tail counts for nothing
    logits[1, 5:] += 3.0
    y[1, 5:] = 0
    np.testing.assert_array_equal(run(logits, y), rows)


def test_the_token_cost_reads_float32_logits_under_bfloat16_compute():
    """12,544-way logits must not be rounded to bfloat16 before p - y."""
    paddle.init(use_tpu=False, compute_dtype="bfloat16")
    logits = L.lm_head(input=_input(8), vocab=11, name="head",
                       param_attr=ParamAttr(name="table"))
    topo = Topology(logits)
    params = topo.init_params(jax.random.PRNGKey(0))
    out = topo.apply(params, {"x": _seq(14)})[0]["head"]
    assert out.data.dtype == jnp.float32


def _block(recompute):
    L.reset_name_counters()
    x = _input(8)
    inside = L.gated_mlp(input=L.rms_norm(input=x, name="b.norm"), size=12,
                         name="b.mlp")
    out = L.addto(input=[x, L.slope_intercept(input=inside, slope=0.22)])
    return L.recompute(out, inputs=[x], enabled=recompute, name="b")


def test_a_recomputed_block_owns_its_parameters_and_keeps_its_gradients():
    feed = {"x": _seq(15)}
    grads, values = [], []
    for recompute in (True, False):
        node = _block(recompute)
        topo = Topology(node)
        assert sorted(topo.param_specs()) == ["b.mlp.w0", "b.mlp.w1",
                                              "b.norm.w0"]
        assert [n.name for n in topo.nodes] == ["x", "b"]
        params = topo.init_params(jax.random.PRNGKey(3))

        def loss(p):
            return jnp.sum(jnp.sin(topo.apply(p, feed)[0]["b"].data))

        values.append(loss(params))
        grads.append(jax.grad(loss)(params))
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
        assert ("checkpoint" in text or "remat" in text) is recompute
    assert float(values[0]) == float(values[1])
    for name in grads[0]:
        np.testing.assert_allclose(grads[0][name], grads[1][name], atol=1e-6)


def _layer_block(how):
    """A decoder layer's shape at width 8: mixer (here two products, in
    and out) into the residual stream, then the MLP. ``how``: "none" no
    checkpoint,
    "full" all of the inside made again, "keep" but the residual after the
    mixer and the MLP's first product, "unnamed" ``keep=[]`` spelt out."""
    L.reset_name_counters()
    x = _input(8)
    linear = {"act": paddle.activation.Linear(), "bias_attr": False}
    mixed = L.fc(input=L.fc(input=L.rms_norm(input=x, name="b.norm1"),
                            size=10, name="b.in", **linear),
                 size=8, name="b.out", **linear)
    mid = L.addto(input=[x, L.slope_intercept(input=mixed, slope=0.22)])
    mlp = L.gated_mlp(input=L.rms_norm(input=mid, name="b.norm2"), size=12,
                      name="b.mlp")
    out = L.addto(input=[mid, L.slope_intercept(input=mlp, slope=0.22)])
    keep = {"keep": [mid, decoder.GATED_MLP_PRODUCT], "unnamed": []}
    if how in keep:
        return L.recompute(out, inputs=[x], keep=keep[how], name="b")
    return L.recompute(out, inputs=[x], enabled=how != "none", name="b")


def _block_loss(how, feed, block=_layer_block):
    topo = Topology(block(how))
    params = topo.init_params(jax.random.PRNGKey(3))
    return (lambda p: jnp.sum(jnp.sin(
        topo.apply(p, feed, mode="train")[0]["b"].data))), params


def _lowered_grad(how, feed, block=_layer_block):
    loss, params = _block_loss(how, feed, block)
    text = jax.jit(jax.grad(loss)).lower(params).as_text()
    # a private function's number counts the lowerings of the process
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


def _dots(jaxpr):
    """The output shape of every dot_general, sub-programs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(tuple(eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _dots(sub)
    return out


def _kept_bytes():
    return observe_metrics.get_registry().snapshot()["gauges"][
        "paddle_tpu_recompute_kept_bytes"]


@pytest.mark.parametrize("other", ["full", "none"])
def test_a_block_that_keeps_two_values_keeps_its_loss_and_gradients(other):
    feed = {"x": _seq(15)}
    out = []
    for how in ("keep", other):
        loss, params = _block_loss(how, feed)
        out.append(jax.value_and_grad(loss)(params))
    (loss_a, grads_a), (loss_b, grads_b) = out
    assert float(loss_a) == float(loss_b)
    assert sorted(grads_a) == sorted(grads_b) and len(grads_a) == 6
    for name in grads_a:
        np.testing.assert_allclose(grads_a[name], grads_b[name], atol=1e-6)


def test_a_kept_value_is_not_made_again_in_backward():
    """Full recompute makes three of the four products twice (the MLP's
    second is dead in backward); with the residual after the mixer and the
    MLP's first product kept, the mixer's second product, which only fed
    the kept sum, and the MLP's first are made once."""
    feed = {"x": _seq(15)}
    dots = {}
    for how in ("none", "keep", "full"):
        loss, params = _block_loss(how, feed)
        dots[how] = _dots(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    count = {how: len(d) for how, d in dots.items()}
    # forward 4 and backward 8, and of the second forward 0, 1 and 3
    assert count == {"none": 12, "keep": 13, "full": 15}
    first_product = (2, 12, 24)     # [rows, time, 2 * size] of b.mlp
    assert [d.count(first_product) for d in dots.values()] == [1, 1, 2]


@pytest.mark.parametrize("how", ["none", "full", "unnamed"])
def test_a_name_that_no_block_keeps_changes_nothing_in_the_program(
        how, monkeypatch):
    """``checkpoint_name`` outside a checkpoint, and inside one whose block
    lists nothing, lowers to what the layers lowered to before they named
    anything."""
    feed = {"x": _seq(15)}
    named = _lowered_grad(how, feed)
    # what holds a second forward apart from the first in the lowered text
    assert ("optimization_barrier" in named) is (how != "none")
    if how == "unnamed":
        assert _lowered_grad("full", feed) == named
    monkeypatch.setattr(decoder, "checkpoint_name", lambda x, name: x)
    assert _lowered_grad(how, feed) == named


def test_a_block_counts_the_bytes_it_keeps():
    feed = {"x": _seq(15)}
    rows, time = feed["x"].data.shape[:2]
    kept = 8 + 2 * 12      # the residual stream and b.mlp's first product
    for how, values in (("keep", kept), ("full", 0), ("keep", kept),
                        ("none", 0)):
        loss, params = _block_loss(how, feed)
        jax.eval_shape(jax.grad(loss), params)
        assert _kept_bytes() == rows * time * values * 4, how


def _mamba_block(how):
    """A Mamba-2 layer's first half at width 8: norm, mixer, residual.
    ``how``: "bare" no block at all, "none" a block without the
    checkpoint, "full" all of the inside made again, "keep" but the
    mixer's first product, "unnamed" ``keep=[]`` spelt out."""
    L.reset_name_counters()
    x = _input(8)
    mixed = L.mamba2(input=L.rms_norm(input=x, name="b.norm"), heads=2,
                     head_dim=4, state=4, chunk=4, name="b.mixer")
    out = L.addto(input=[x, L.slope_intercept(input=mixed, slope=0.22)],
                  name="b")
    keep = {"keep": [decoder.MAMBA_IN_PRODUCT], "unnamed": []}
    if how == "bare":
        return out
    if how in keep:
        return L.recompute(out, inputs=[x], keep=keep[how], name="b")
    return L.recompute(out, inputs=[x], enabled=how != "none", name="b")


# [rows, time, z + xBC + dt] of b.mixer: 8 + (8 + 2 * 4) + 2
_MAMBA_IN_PRODUCT_SHAPE = (2, 12, 26)


@pytest.mark.parametrize("other", ["full", "none", "bare"])
def test_a_mamba_block_that_keeps_its_input_product_keeps_its_gradients(
        other):
    feed = {"x": _seq(15)}
    out = []
    for how in ("keep", other):
        loss, params = _block_loss(how, feed, _mamba_block)
        out.append(jax.value_and_grad(loss)(params))
    (loss_a, grads_a), (loss_b, grads_b) = out
    assert float(loss_a) == float(loss_b)
    assert sorted(grads_a) == sorted(grads_b) and len(grads_a) == 9
    for name in grads_a:
        np.testing.assert_allclose(grads_a[name], grads_b[name], atol=1e-6)


def test_a_kept_input_product_is_not_made_again_in_backward():
    """One ``dot_general`` of ``in_proj``'s shape fewer than full
    recompute, and no other product changes its count."""
    feed = {"x": _seq(15)}
    dots = {}
    for how in ("none", "keep", "full"):
        loss, params = _block_loss(how, feed, _mamba_block)
        dots[how] = _dots(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert [d.count(_MAMBA_IN_PRODUCT_SHAPE) for d in dots.values()] \
        == [1, 1, 2]
    assert len(dots["full"]) - len(dots["keep"]) == 1
    assert len(dots["none"]) < len(dots["keep"])


@pytest.mark.parametrize("how", ["bare", "none", "full", "unnamed"])
def test_the_mixers_name_changes_nothing_where_no_block_keeps_it(
        how, monkeypatch):
    """A bare ``mamba2``, a block without the checkpoint and one that
    lists nothing lower to what they lowered to before the mixer named
    its product."""
    feed = {"x": _seq(15)}
    named = _lowered_grad(how, feed, _mamba_block)
    assert ("optimization_barrier" in named) is (how in ("full", "unnamed"))
    if how == "unnamed":
        assert _lowered_grad("full", feed, _mamba_block) == named
    monkeypatch.setattr(decoder, "checkpoint_name", lambda x, name: x)
    assert _lowered_grad(how, feed, _mamba_block) == named


def test_a_mamba_block_counts_the_product_it_keeps():
    feed = {"x": _seq(15)}
    rows, time, width = _MAMBA_IN_PRODUCT_SHAPE
    for how, values in (("keep", width), ("full", 0), ("bare", 0),
                        ("keep", width), ("none", 0)):
        loss, params = _block_loss(how, feed, _mamba_block)
        jax.eval_shape(jax.grad(loss), params)
        assert _kept_bytes() == rows * time * values * 4, how


def test_a_block_keeps_only_what_is_inside_it():
    x = _input(8)
    outside = L.rms_norm(input=x, name="outside")
    inner = L.rms_norm(input=outside, name="inner")
    with pytest.raises(EnforceError, match="not inside the block"):
        L.recompute(inner, inputs=[outside], keep=[x])


def test_a_block_refuses_a_data_layer_inside_it():
    x = _input(8)
    with pytest.raises(EnforceError, match="data layer"):
        L.recompute(L.rms_norm(input=x), inputs=[])


def test_the_analyzer_finds_the_time_mixing_layers_guarded():
    from paddle_tpu.analyze.topology_check import (scan_layer_modules,
                                                  verify_reject_packed_coverage)

    scan_layer_modules.cache_clear()
    coverage = verify_reject_packed_coverage()
    assert coverage["missing"] == [] and coverage["extra"] == []
    assert {"mamba2", "gqa_attention", "gated_delta_net"} \
        <= set(coverage["expected"])
    assert not {"rms_norm", "gated_mlp", "lm_head", "lm_cost",
                "recompute"} & set(coverage["expected"])
