"""What `phi4flash` (SambaY with differential attention) brought to
`layer/decoder.py` and `ops/attention.py`: window attention against masked
full attention and the key blocks it does not visit, differential
attention against its two softmaxes written out, LayerNorm, the Mamba-1
mixer and the Gated Memory Unit against their equations, cross-attention
over another layer's keys and values, and blocks that hand a value to two
later blocks, whose gradients add up in it. Float32 at `highest`."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import phi4_flash as ref
from paddle_tpu import data_type
from paddle_tpu import layer as L
from paddle_tpu.checkgrad import check_layer_grad
from paddle_tpu.core.sequence import PackedSequenceBatch, SequenceBatch
from paddle_tpu.graph import Context
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


def _params(topo, seed=0, std=0.3):
    """Every parameter normal(std) but the norms' scales near one: biases
    and lambdas that start at a constant would leave their terms
    untested."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, spec in sorted(topo.param_specs().items()):
        value = std * rng.standard_normal(spec.shape)
        if name.endswith((".w0", "subln")) and len(spec.shape) == 1:
            value = 1.0 + value
        if name.endswith("A_log"):
            value = np.log(np.arange(1, spec.shape[1] + 1)) + 0 * value
        out[name] = jnp.asarray(value, jnp.float32)
    return out


def _qkv(seed, b=2, t=23, h=4, kv=2, d=4, dv=None):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(s), jnp.float32) for s in (
        (b, t, h, d), (b, t, kv, d), (b, t, kv, dv or d)))


def full_attention(q, k, v, scale, lengths, window=None):
    """softmax(q k^T * scale + mask) v with the whole [T, T] of scores and
    the mask written out."""
    t = q.shape[1]
    groups = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, groups, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    at, key = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = key <= at
    if window is not None:
        seen = seen & (at - key < window)
    seen = seen[None, None] & (key[None] < lengths[:, None, None])[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window,block", [(1, 4), (3, 4), (4, 4), (5, 4),
                                          (9, 8), (12, 5), (64, 4)])
def test_window_attention_is_masked_full_attention(window, block):
    """Windows narrower than a block, a block wide, wider, no multiple of
    it, and wider than the row; 23 tokens are no multiple of the block."""
    q, k, v = _qkv(0)
    lengths = jnp.asarray([23, 17])
    want = full_attention(q, k, v, 0.5, lengths, window)
    got = attention_ops.blockwise_attention(q, k, v, 0.5, True, lengths,
                                            block, window)
    valid = (jnp.arange(23)[None, :] < lengths[:, None])[..., None, None]
    np.testing.assert_allclose(jnp.where(valid, got, 0),
                               jnp.where(valid, want, 0), atol=2e-5)


def test_window_attention_has_masked_full_attentions_gradients():
    q, k, v = _qkv(1)
    lengths = jnp.asarray([23, 23])
    weight = jnp.asarray(np.random.default_rng(2).standard_normal(q.shape),
                         jnp.float32)
    want = jax.grad(lambda *a: jnp.sum(full_attention(
        *a, 0.5, lengths, 6) * weight), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: jnp.sum(attention_ops.blockwise_attention(
        *a, 0.5, True, lengths, 4, 6) * weight), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


def _scan_lengths(jaxpr):
    """The lengths of the `scan`s of a jaxpr, outermost only."""
    return [eqn.params["length"] for eqn in jaxpr.eqns
            if eqn.primitive.name == "scan"]


def test_key_blocks_outside_the_window_are_not_visited():
    """4,096 positions in blocks of 512 with a window of 512: a query
    block visits its own key block and the one before, 15 in all where
    causal attention visits 36. Counted in the traced program: the scan
    over key blocks of each query block is that long, so a window that
    only masked would fail here."""
    assert attention_ops.key_blocks(4096, 512, True, 512) == \
        [(0, 1)] + [(i - 1, i + 1) for i in range(1, 8)]
    assert attention_ops.key_blocks(4096, 512) == \
        [(0, i + 1) for i in range(8)]
    # a block's first query sees a third block once the window is two
    # keys wider than a block
    assert attention_ops.key_blocks(4096, 512, True, 513)[2] == (1, 3)
    assert attention_ops.key_blocks(4096, 512, True, 514)[2] == (0, 3)
    assert attention_ops.key_blocks(4096, 512, True, 1)[5] == (5, 6)
    q, k, v = _qkv(3, b=1, t=64, h=2, kv=2, d=4)

    def visited(window):
        return _scan_lengths(jax.make_jaxpr(
            lambda *a: attention_ops.blockwise_attention(
                *a, 0.5, True, None, 8, window))(q, k, v).jaxpr)

    assert visited(None) == list(range(1, 9))
    assert visited(8) == [1] + [2] * 7
    assert visited(9) == [1] + [2] * 7
    assert visited(10) == [1, 2] + [3] * 6
    assert sum(visited(8)) == 15 and sum(visited(None)) == 36


def test_values_may_be_wider_than_keys():
    q, k, v = _qkv(4, dv=6)
    lengths = jnp.asarray([23, 20])
    got = attention_ops.blockwise_attention(q, k, v, 0.5, True, lengths, 8)
    assert got.shape == (2, 23, 4, 6)
    np.testing.assert_allclose(
        got, full_attention(q, k, v, 0.5, lengths), atol=2e-5)


def differential(u, p, heads, kv, hd, lam_init, window=None, kv_given=None,
                 eps=1e-5):
    """The layer written out a pair at a time: two softmaxes over whole
    rows of scores, their difference times the pair's values, the norm of
    a pair, the output projection."""
    b, t, _ = u.shape
    q = u @ p["mix.q"] + p["mix.q_b"]
    if kv_given is None:
        k = u @ p["mix.k"] + p["mix.k_b"]
        v = u @ p["mix.v"] + p["mix.v_b"]
    else:
        k, v = np.split(kv_given, 2, axis=-1)
    q = q.reshape(b, t, heads, hd)
    k, v = k.reshape(b, t, kv, hd), v.reshape(b, t, kv, hd)
    lam = math.exp(float(p["mix.lambda_q1"] @ p["mix.lambda_k1"])) \
        - math.exp(float(p["mix.lambda_q2"] @ p["mix.lambda_k2"])) + lam_init
    at, key = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = key <= at
    if window is not None:
        seen = seen & (at - key < window)
    outs = []
    for pair in range(heads // 2):
        g = pair // (heads // kv)
        maps = []
        for j in (0, 1):
            s = np.einsum("bqd,bkd->bqk", q[:, :, 2 * pair + j],
                          k[:, :, 2 * g + j]) / math.sqrt(hd)
            s = np.where(seen, s, -np.inf)
            e = np.exp(s - s.max(-1, keepdims=True))
            maps.append(e / e.sum(-1, keepdims=True))
        values = np.concatenate([v[:, :, 2 * g], v[:, :, 2 * g + 1]], -1)
        o = np.einsum("bqk,bkd->bqd", maps[0] - lam * maps[1], values)
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + eps) \
            * p["mix.subln"] * (1.0 - lam_init)
        outs.append(o)
    return np.concatenate(outs, -1) @ p["mix.o"] + p["mix.o_b"]


@pytest.mark.parametrize("window,block", [(None, 512), (None, 4), (5, 4)])
def test_differential_attention_is_its_two_softmaxes(window, block):
    heads, kv, hd = 8, 4, 4
    lam_init = decoder.lambda_init(17)
    assert abs(lam_init - (0.8 - 0.6 * math.exp(-5.1))) < 1e-12
    x = _seq(5, t=14, width=12, lengths=(14, 14))
    node = L.gqa_attention(input=_input(12), heads=heads, kv_heads=kv,
                           head_dim=hd, block=block, window=window,
                           differential=lam_init, bias=True, name="mix")
    topo = Topology(node)
    assert set(topo.param_specs()) == {"mix." + n for n in (
        "q", "k", "v", "o", "q_b", "k_b", "v_b", "o_b", "lambda_q1",
        "lambda_k1", "lambda_q2", "lambda_k2", "subln")}
    assert topo.param_specs()["mix.subln"].shape == (2 * hd,)
    params = _params(topo, 6)
    got = topo.apply(params, {"x": x}, mode="test")[0]["mix"].data
    want = differential(np.asarray(x.data),
                        {k: np.asarray(v) for k, v in params.items()},
                        heads, kv, hd, lam_init, window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_attention_without_the_new_forms_has_no_new_parameters():
    node = L.gqa_attention(input=_input(8), heads=4, kv_heads=2, head_dim=4,
                           name="mix")
    assert sorted(Topology(node).param_specs()) == [
        "mix.k", "mix.o", "mix.q", "mix.v"]
    with pytest.raises(EnforceError, match="pairs heads"):
        L.gqa_attention(input=_input(8), heads=3, kv_heads=3, head_dim=4,
                        differential=0.2)


def test_cross_attention_reads_the_keys_and_values_handed_out():
    """The layer that hands out gives a second node, its keys and values
    side by side after their biases; the layer that reads has a query and
    an output projection only, and is the formula over those values."""
    heads, kv, hd = 4, 2, 4
    x, y = _seq(7, t=10, width=8, lengths=(10, 10)), \
        _seq(8, t=10, width=8, lengths=(10, 10))
    made, handed = L.gqa_attention(
        input=_input(8), heads=heads, kv_heads=kv, head_dim=hd,
        differential=0.3, bias=True, hand_out=True, name="full")
    assert handed.size == 2 * kv * hd and made.size == 8
    reader = L.gqa_attention(
        input=_input(8, "y"), heads=heads, kv_heads=kv, head_dim=hd,
        differential=0.4, bias=True, kv=handed, name="mix")
    topo = Topology([made, reader])
    assert sorted(n for n in topo.param_specs() if n.startswith("mix.")) == \
        sorted("mix." + n for n in ("q", "o", "q_b", "o_b", "lambda_q1",
                                    "lambda_k1", "lambda_q2", "lambda_k2",
                                    "subln"))
    params = _params(topo, 9)
    values = topo.apply(params, {"x": x, "y": y}, mode="test",
                        outputs=[handed.name, reader.name, made.name])[0]
    p = {k: np.asarray(v) for k, v in params.items()}
    kv_want = np.concatenate([
        np.asarray(x.data) @ p["full.k"] + p["full.k_b"],
        np.asarray(x.data) @ p["full.v"] + p["full.v_b"]], -1)
    np.testing.assert_allclose(values[handed.name].data, kv_want, atol=2e-5)
    want = differential(np.asarray(y.data), p, heads, kv, hd, 0.4,
                        kv_given=kv_want)
    np.testing.assert_allclose(values[reader.name].data, want, rtol=2e-4,
                               atol=2e-5)


def test_layer_norm_is_its_formula():
    x = _seq(10)
    node = L.layer_norm(input=_input(8), eps=1e-5, name="ln")
    topo = Topology(node)
    assert sorted(topo.param_specs()) == ["ln.w0", "ln.wbias"]
    start = topo.init_params(jax.random.PRNGKey(0))
    assert float(start["ln.w0"].min()) == 1.0 == float(start["ln.w0"].max())
    assert float(jnp.abs(start["ln.wbias"]).max()) == 0.0
    params = _params(topo, 11)
    got = topo.apply(params, {"x": x}, mode="test")[0]["ln"].data
    d = np.asarray(x.data, np.float64)
    want = (d - d.mean(-1, keepdims=True)) / np.sqrt(
        d.var(-1, keepdims=True) + 1e-5) * np.asarray(params["ln.w0"]) \
        + np.asarray(params["ln.wbias"])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def silu(x):
    return x / (1.0 + np.exp(-x))


def test_the_mamba1_mixer_is_its_equations_token_by_token():
    d, n, taps, rank = 8, 4, 4, 3
    inner = 2 * d
    x = _seq(12, t=11, width=d, lengths=(11, 8))
    node, memory = L.mamba1(input=_input(d), state=n, conv_width=taps,
                            dt_rank=rank, chunk=4, hand_out=True, name="mix")
    assert memory.size == inner
    topo = Topology([node, memory])
    shapes = {k: s.shape for k, s in topo.param_specs().items()}
    assert shapes == {
        "mix.in_proj": (d, 2 * inner), "mix.conv_w": (inner, taps),
        "mix.conv_b": (inner,), "mix.x_proj": (inner, rank + 2 * n),
        "mix.dt_proj": (rank, inner), "mix.dt_bias": (inner,),
        "mix.A_log": (inner, n), "mix.D": (inner,),
        "mix.out_proj": (inner, d)}
    start = topo.init_params(jax.random.PRNGKey(0))
    np.testing.assert_allclose(start["mix.A_log"][5],
                               np.log(np.arange(1, n + 1)), rtol=1e-6)
    assert float(jnp.abs(start["mix.dt_proj"]).max()) <= rank ** -0.5
    params = _params(topo, 13)
    values = topo.apply(params, {"x": x}, mode="test",
                        outputs=[node.name, memory.name])[0]
    p = {k[4:]: np.asarray(v, np.float64) for k, v in params.items()}
    for row, length in enumerate((11, 8)):
        u = np.asarray(x.data[row, :length], np.float64)
        xs, z = np.split(u @ p["in_proj"], 2, axis=-1)
        padded = np.concatenate([np.zeros((taps - 1, inner)), xs])
        xs = silu(sum(padded[k:k + length] * p["conv_w"][:, k]
                      for k in range(taps)) + p["conv_b"])
        r, b_mat, c_mat = np.split(xs @ p["x_proj"], [rank, rank + n], -1)
        dt = np.log1p(np.exp(r @ p["dt_proj"] + p["dt_bias"]))
        a = -np.exp(p["A_log"])
        state, ys = np.zeros((inner, n)), []
        for t in range(length):
            state = np.exp(dt[t][:, None] * a) * state \
                + (dt[t] * xs[t])[:, None] * b_mat[t][None, :]
            ys.append(state @ c_mat[t] + p["D"] * xs[t])
        ys = np.stack(ys)
        np.testing.assert_allclose(values[memory.name].data[row, :length],
                                   ys, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(values[node.name].data[row, :length],
                                   (ys * silu(z)) @ p["out_proj"],
                                   rtol=2e-4, atol=2e-5)
    # the same layer without the second value is one node with its output
    L.reset_name_counters()
    alone = L.mamba1(input=_input(d), state=n, conv_width=taps, dt_rank=rank,
                     chunk=4, name="mix")
    out = Topology(alone).apply(params, {"x": x}, mode="test")[0]["mix"]
    np.testing.assert_array_equal(out.data, values[node.name].data)
    assert L.mamba1(input=_input(32), name="r").param_specs[4].shape == \
        (2, 64)   # dt rank 32 / 16, E = 64


def test_the_gated_memory_unit_is_its_formula():
    x, m = _seq(14), _seq(15, width=6)
    node = L.gmu(input=_input(8), memory=_input(6, "m"), name="mix")
    topo = Topology(node)
    assert {k: s.shape for k, s in topo.param_specs().items()} == {
        "mix.in_proj": (8, 6), "mix.out_proj": (6, 8)}
    params = _params(topo, 16)
    got = topo.apply(params, {"x": x, "m": m}, mode="test")[0]["mix"].data
    want = (np.asarray(m.data) * silu(np.asarray(x.data) @ np.asarray(
        params["mix.in_proj"]))) @ np.asarray(params["mix.out_proj"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _new_mixers():
    return {
        "mamba1": lambda x: L.mamba1(input=x, state=4, chunk=4, name="mix"),
        "window_differential": lambda x: L.gqa_attention(
            input=x, heads=4, kv_heads=2, head_dim=4, block=4, window=3,
            differential=0.35, bias=True, name="mix"),
    }


@pytest.mark.parametrize("kind", sorted(_new_mixers()))
def test_a_new_mixer_sees_no_later_token_and_refuses_packed_rows(kind):
    x = _seq(17, t=14, lengths=(14, 9))
    node = _new_mixers()[kind](_input(8))
    topo = Topology(node)
    params = _params(topo, 18)
    out = topo.apply(params, {"x": x}, mode="test")[0]["mix"]
    moved = SequenceBatch(x.data.at[:, 10].add(1.0), x.lengths)
    out2 = topo.apply(params, {"x": moved}, mode="test")[0]["mix"]
    np.testing.assert_array_equal(out2.data[:, :10], out.data[:, :10])
    assert float(jnp.abs(out2.data[0, 10:] - out.data[0, 10:]).max()) > 1e-6
    np.testing.assert_array_equal(out2.data[1, :9], out.data[1, :9])
    packed = PackedSequenceBatch(x.data, x.lengths,
                                 jnp.zeros(x.data.shape[:2], jnp.int32))
    with pytest.raises(EnforceError, match="packed"):
        topo.apply(params, {"x": packed}, mode="test")


def _checkgrad_nodes():
    def x():
        return _input(6)

    return {
        "layer_norm": lambda: L.layer_norm(input=x()),
        "gmu": lambda: L.gmu(input=x(), memory=_input(6, "z"),
                             initial_std=0.5),
    }


@pytest.mark.parametrize("kind", sorted(_checkgrad_nodes()))
def test_checkgrad_on_each_new_layer(kind):
    """The Mamba-1 mixer's and differential attention's gradients are held
    against the plain reference's below, with two readers."""
    node = _checkgrad_nodes()[kind]()
    feed = {"x": _seq(19, t=6, width=6, lengths=(6, 4))}
    if kind == "gmu":
        feed["z"] = _seq(20, t=6, width=6, lengths=(6, 4))
    assert check_layer_grad(node, feed, rtol=5e-3, atol=1e-5)


# -- a value that lives across blocks -----------------------------------------

def _two_readers(kind, recompute=True):
    """A block that makes a value (a Mamba-1 layer's memory, an attention
    layer's keys and values) and hands it out beside its output, two
    blocks that read it, and the sum of all three outputs."""
    heads, kv, hd = 4, 2, 2
    x = _input(8)
    if kind == "memory":
        out, made = L.mamba1(input=x, state=4, chunk=4, hand_out=True,
                             name="maker")
    else:
        out, made = L.gqa_attention(
            input=x, heads=heads, kv_heads=kv, head_dim=hd, block=4,
            differential=0.3, bias=True, hand_out=True, name="maker")
    out, made = L.recompute([out, made], inputs=[x], enabled=recompute,
                            name="maker.block")
    readers = []
    for i, source in enumerate(("y", "z")):
        u = _input(8, source)
        if kind == "memory":
            r = L.gmu(input=u, memory=made, name="reader%d" % i)
        else:
            r = L.gqa_attention(
                input=u, heads=heads, kv_heads=kv, head_dim=hd, block=4,
                differential=0.4, bias=True, kv=made, name="reader%d" % i)
        readers.append(L.recompute(r, inputs=[u, made], enabled=recompute,
                                   name="reader%d.block" % i))
    return L.addto(input=[out] + readers), made


def _reference_total(kind, params, feed):
    """The sum of the three outputs by the plain reference's functions."""
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 2,
           "layer_norm_eps": 1e-5, "hidden_size": 8, "mamba_d_state": 4,
           "mamba_dt_rank": 1}

    def of(prefix):
        return {k[len(prefix):]: v for k, v in params.items()
                if k.startswith(prefix)}

    x, y, z = (feed[n].data for n in ("x", "y", "z"))
    if kind == "memory":
        out, made = ref._mamba(x, of("maker."), cfg, None)
        read = [ref._gmu(u, made, of("reader%d." % i), None)
                for i, u in enumerate((y, z))]
    else:
        # the reference's lambda_init follows a layer's index: give it the
        # indices whose lambda_init the layers here were built with
        def index_of(lam_init):
            return -math.log((0.8 - lam_init) / 0.6) / 0.3

        out, made = ref._attention(x, None, of("maker."), index_of(0.3),
                                   None, cfg, None)
        read = [ref._attention(u, made, of("reader%d." % i), index_of(0.4),
                               None, cfg, None)[0]
                for i, u in enumerate((y, z))]
    return out + read[0] + read[1], made


@pytest.mark.parametrize("kind", ["memory", "kv"])
def test_gradients_of_two_readers_add_up_in_the_value_they_share(kind):
    """The gradient into every parameter of the maker and into its input
    is the plain reference's, where the shared value has two readers and
    each of the three layers is a recomputed block: what flows back into
    the memory (or the keys and values) is the sum over both readers and,
    for the memory, the maker's own gate."""
    total, made = _two_readers(kind)
    topo = Topology(total)
    params = _params(topo, 21)
    feed = {n: _seq(22 + i, t=10, lengths=(10, 10))
            for i, n in enumerate(("x", "y", "z"))}
    weight = jnp.asarray(np.random.default_rng(25).standard_normal(
        (2, 10, 8)), jnp.float32)

    def program(p, x):
        values = topo.apply(p, {**feed, "x": SequenceBatch(
            x, feed["x"].lengths)}, mode="train",
            outputs=[total.name, made.name])[0]
        return jnp.sum(values[total.name].data * weight), \
            values[made.name].data

    def plain(p, x):
        out, shared = _reference_total(kind, p, {**feed, "x": SequenceBatch(
            x, feed["x"].lengths)})
        return jnp.sum(out * weight), shared

    (got, got_made), got_grads = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(params, feed["x"].data)
    (want, want_made), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(params, feed["x"].data)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got_made, want_made, rtol=1e-4, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kind", ["memory", "kv"])
def test_blocks_that_share_a_value_keep_their_gradients(kind):
    """Recomputed or kept whole, the three blocks give one loss and one
    gradient; the gauge reads the bytes of the value handed out."""
    feed = {n: _seq(26 + i, t=10, lengths=(10, 7))
            for i, n in enumerate(("x", "y", "z"))}
    results = []
    for recompute in (True, False):
        L.reset_name_counters()
        total, made = _two_readers(kind, recompute)
        topo = Topology(total)
        params = _params(topo, 29)
        results.append(jax.value_and_grad(lambda p: jnp.sum(jnp.square(
            topo.apply(p, feed, mode="train")[0][total.name].data)))(params))
        gauges = observe_metrics.get_registry().snapshot()["gauges"]
        assert gauges["paddle_tpu_shared_across_blocks_bytes"] == \
            2 * 10 * made.size * 4
    (loss, grads), (loss_kept, grads_kept) = results
    np.testing.assert_allclose(loss, loss_kept, rtol=1e-6)
    for name in grads:
        np.testing.assert_allclose(grads[name], grads_kept[name], rtol=1e-4,
                                   atol=1e-6)


def test_a_block_with_one_output_is_the_node_it_was():
    x = _input(8)
    h = L.gated_mlp(input=x, size=5, name="mlp")
    one = L.recompute(h, inputs=[x], name="block")
    assert one.layer_type == "recompute" and one.size == 8
    L.reset_name_counters()
    x = _input(8)
    h = L.gated_mlp(input=x, size=5, name="mlp")
    several = L.recompute([h], inputs=[x], name="block")
    assert isinstance(several, list) and len(several) == 1
    assert several[0].inputs[0].layer_type == "recompute"


def test_the_attention_gauges_count_key_blocks():
    """Visited and possible, summed over a traced step's attention
    layers: a window layer visits fewer than it could, a full layer all."""
    x = _input(8)
    windowed = L.gqa_attention(input=x, heads=2, kv_heads=2, head_dim=4,
                               block=4, window=4, name="w")
    full = L.gqa_attention(input=x, heads=2, kv_heads=2, head_dim=4,
                           block=4, name="f")
    topo = Topology(L.addto(input=[windowed, full]))
    params = _params(topo, 30)
    topo.apply(params, {"x": _seq(31, t=16, lengths=(16, 16))}, mode="train")
    gauges = observe_metrics.get_registry().snapshot()["gauges"]
    # four query blocks: 1 + 2 + 2 + 2 with the window, 1 + 2 + 3 + 4 without
    assert gauges["paddle_tpu_attention_key_blocks_visited"] == 7 + 10
    assert gauges["paddle_tpu_attention_key_blocks_possible"] == 10 + 10
    ctx = Context(mode="test")
    assert ctx.attention_key_blocks == {"visited": 0, "possible": 0}
    assert ctx.shared_across_blocks_bytes == 0
