"""`ops/ssm.py selective_scan`: Mamba-1's scan against the token-by-token
recurrence it stands for, in values and gradients, with lengths, from a
given state, across chunk and block sizes, in both its forms: the plain
loops kept by chunks, and the two Pallas kernels of `ops/pallas_ssm.py`
(interpreted here on the CPU, at 128 channels and 8 states, which tile).
Then what only the fused form has: who chooses it, what its program holds,
what it costs. Float32 at `highest`."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import data_type
from paddle_tpu import layer as L
from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops import pallas_ssm
from paddle_tpu.ops import ssm
from paddle_tpu.topology import Topology

# the widths each form is tested at: 12 channels do not tile
WIDTHS = {"plain": (12, 4), "fused": (128, 8)}


@pytest.fixture(autouse=True)
def _highest():
    L.reset_name_counters()
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=["plain", "fused"])
def form(request, monkeypatch):
    return _take(request.param, monkeypatch)


def _take(form, monkeypatch):
    """Nothing selects a form but the backend and the shapes: the tests
    flip the interpret flag, as `tests/test_pallas_kernels.py` does, and
    choose widths."""
    monkeypatch.setattr(pk, "_INTERPRET", form == "fused")
    assert ssm.selective_scan_form(*WIDTHS[form]) == form
    return form


def recurrence(x, dt, a, b_mat, c_mat, d_skip, lengths, state=None):
    """S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c]
    B_t[n]; y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c], a Python loop
    over the tokens."""
    batch, t, channels = x.shape
    state = jnp.zeros((batch, channels, a.shape[1])) if state is None \
        else state
    ys = []
    for i in range(t):
        alive = (i < lengths)[:, None, None]
        stepped = jnp.exp(dt[:, i, :, None] * a) * state \
            + (dt[:, i] * x[:, i])[..., None] * b_mat[:, i, None, :]
        state = jnp.where(alive, stepped, state)
        y = jnp.sum(state * c_mat[:, i, None, :], axis=-1) \
            + d_skip * x[:, i]
        ys.append(jnp.where(alive[..., 0], y, 0))
    return jnp.stack(ys, axis=1), state


def inputs(seed, form="plain", batch=2, t=37):
    channels, n = WIDTHS[form]
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (normal(batch, t, channels),
            jax.nn.softplus(normal(batch, t, channels)),
            -jnp.exp(normal(channels, n)), normal(batch, t, n),
            normal(batch, t, n), normal(channels))


@pytest.mark.parametrize("which, chunk, t", [
    ("plain", 5, 37), ("plain", 8, 37), ("plain", 16, 37), ("plain", 64, 37),
    ("fused", 16, 37), ("fused", 16, 9), ("fused", 16, 128),
    ("fused", 16, 150)])
def test_the_scan_is_the_token_recurrence(which, chunk, t, monkeypatch):
    """37 and 29 tokens are no multiple of 5, 8 or 16, and fewer than
    64. The kernels take a short row as one block rounded up to 8 tokens
    (40, 16), 128 tokens as one whole block, 150 as two, the second
    padded."""
    _take(which, monkeypatch)
    args = inputs(0, which, t=t)
    lengths = jnp.asarray([t, t - 8])
    want, want_state = recurrence(*args, lengths)
    got, got_state = ssm.selective_scan(*args, chunk=chunk, lengths=lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5)
    assert got_state.shape == (2,) + WIDTHS[which]


@pytest.mark.parametrize("which, chunk, t", [
    ("plain", 7, 19), ("plain", 16, 19), ("fused", 16, 19),
    ("fused", 16, 140)])
def test_the_scan_has_the_recurrences_gradients(which, chunk, t,
                                                monkeypatch):
    """x, dt, A, B, C and D; 140 tokens are two blocks of the kernels,
    whose backward hands its carry from the second to the first."""
    _take(which, monkeypatch)
    args = inputs(1, which, t=t)
    lengths = jnp.asarray([t, t - 8])
    weight = jnp.asarray(np.random.default_rng(2).standard_normal(
        args[0].shape), jnp.float32)

    def loss(fn, *a):
        y, state = fn(*a)
        return jnp.sum(y * weight) + jnp.sum(state)

    want = jax.grad(lambda *a: loss(
        lambda *b: recurrence(*b, lengths), *a), argnums=range(6))(*args)
    got = jax.grad(lambda *a: loss(
        lambda *b: ssm.selective_scan(*b, chunk=chunk, lengths=lengths), *a),
        argnums=range(6))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_a_scan_goes_on_from_the_state_it_left(form):
    args = inputs(3, form, t=32)
    lengths = jnp.asarray([32, 32])
    whole, last = ssm.selective_scan(*args, chunk=8, lengths=lengths)
    first = [a[:, :20] if a.ndim == 3 and a.shape[1] == 32 else a
             for a in args]
    rest = [a[:, 20:] if a.ndim == 3 and a.shape[1] == 32 else a
            for a in args]
    head, state = ssm.selective_scan(*first, chunk=8)
    tail, end = ssm.selective_scan(*rest, chunk=8, initial_state=state)
    np.testing.assert_allclose(jnp.concatenate([head, tail], axis=1), whole,
                               atol=2e-5)
    np.testing.assert_allclose(end, last, atol=2e-5)
    # and its gradient reaches the state it started from
    g = jax.grad(lambda s: jnp.sum(ssm.selective_scan(
        *rest, chunk=8, initial_state=s)[0]))(state)
    want = jax.grad(lambda s: jnp.sum(recurrence(
        *rest, jnp.asarray([12, 12]), s)[0]))(state)
    np.testing.assert_allclose(g, want, rtol=2e-4, atol=2e-5)


def test_the_padded_tail_changes_nothing_and_reads_zero(form):
    x, dt, a, b_mat, c_mat, d = inputs(4, form, t=24)
    lengths = jnp.asarray([24, 13])
    y, state = ssm.selective_scan(x, dt, a, b_mat, c_mat, d, 8, lengths)
    noisy = x.at[1, 13:].set(99.0)
    y2, state2 = ssm.selective_scan(noisy, dt.at[1, 13:].set(7.0), a,
                                    b_mat.at[1, 13:].set(-5.0), c_mat, d, 8,
                                    lengths)
    np.testing.assert_array_equal(y, y2)
    np.testing.assert_array_equal(state, state2)
    assert float(jnp.abs(y[1, 13:]).max()) == 0.0
    short, short_state = ssm.selective_scan(
        x[1:, :13], dt[1:, :13], a, b_mat[1:, :13], c_mat[1:, :13], d, 8)
    np.testing.assert_allclose(y[1:, :13], short, atol=2e-5)
    np.testing.assert_allclose(state[1:], short_state, atol=2e-5)


def test_nothing_leaks_back_in_time(form):
    x, dt, a, b_mat, c_mat, d = inputs(5, form, t=24)
    y = ssm.selective_scan(x, dt, a, b_mat, c_mat, d, 8)[0]
    y2 = ssm.selective_scan(x.at[:, 17:].add(3.0), dt, a,
                            b_mat.at[:, 17:].add(1.0), c_mat, d, 8)[0]
    np.testing.assert_array_equal(y[:, :17], y2[:, :17])
    assert float(jnp.abs(y[:, 17:] - y2[:, 17:]).max()) > 0


def test_a_decay_that_underflows_is_exact_and_finite(form):
    """dt * |A| of 200 a token, 25,600 over a chunk of 128: a form that
    divided by the decay over a chunk would overflow; the state is
    stepped, so the output is the last token's alone."""
    x, _, _, b_mat, c_mat, d = inputs(6, form, t=16)
    dt = jnp.full(x.shape, 50.0)
    a = jnp.full(WIDTHS[form], -4.0)
    y, state = ssm.selective_scan(x, dt, a, b_mat, c_mat, d, 128)
    want = (dt * x) * jnp.sum(b_mat * c_mat, axis=-1, keepdims=True) + d * x
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(state).all())
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda dt_: jnp.sum(ssm.selective_scan(
        x, dt_, a, b_mat, c_mat, d, 128)[0]))(dt)
    assert bool(jnp.isfinite(g).all())


def test_bfloat16_operands_keep_a_float32_state(form):
    x, dt, a, b_mat, c_mat, d = inputs(7, form, t=24)
    low = [v.astype(jnp.bfloat16) for v in (x, b_mat, c_mat)]
    y, state = ssm.selective_scan(low[0], dt, a, low[1], low[2], d, 8)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    want = ssm.selective_scan(*(v.astype(jnp.float32) for v in low[:1]), dt,
                              a, *(v.astype(jnp.float32) for v in low[1:]),
                              d, 8)[0]
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2)


# ----------------------------------------------------------------------
# what only the fused form has
# ----------------------------------------------------------------------

def _mixer(width, recompute=False):
    """One Mamba-1 layer of 8 states over ``width`` inputs: 2 * width
    channels."""
    x = L.data(name="x", type=data_type.dense_vector_sequence(width))
    h = L.mamba1(input=x, state=8, name="m")
    return Topology(L.recompute(h, inputs=[x], name="block")
                    if recompute else h)


def _mixer_feed(width, seed=11, t=12):
    rng = np.random.default_rng(seed)
    return {"x": SequenceBatch(
        jnp.asarray(rng.standard_normal((2, t, width)), jnp.float32),
        jnp.asarray([t, t - 3], jnp.int32))}


def _mixer_params(topo, seed=12):
    rng = np.random.default_rng(seed)
    out = {}
    for name, spec in sorted(topo.param_specs().items()):
        value = 0.1 * rng.standard_normal(spec.shape)
        if name.endswith("A_log"):
            value = np.log(np.arange(1, spec.shape[1] + 1)) + 0 * value
        out[name] = jnp.asarray(value, jnp.float32)
    return out


def _scan_gauges():
    gauges = observe_metrics.get_registry().snapshot()["gauges"]
    return (gauges["paddle_tpu_selective_scan_fused"],
            gauges["paddle_tpu_selective_scan_plain"])


def test_the_shapes_choose_the_form_and_the_gauges_say_which(monkeypatch):
    """With Pallas at hand, 128 channels of 8 states take the kernels; 16
    channels do not tile and take the plain loops; without it (this
    backend, no interpret flag) every width is plain. The gauges count the
    scans of the step traced last."""
    for interpret, width, want in ((True, 64, (1, 0)), (True, 8, (0, 1)),
                                   (False, 64, (0, 1))):
        monkeypatch.setattr(pk, "_INTERPRET", interpret)
        L.reset_name_counters()
        topo = _mixer(width)
        topo.apply(_mixer_params(topo), _mixer_feed(width), mode="train")
        assert _scan_gauges() == want
    monkeypatch.setattr(pk, "_INTERPRET", True)
    assert not pallas_ssm.fits(128, 4) and not pallas_ssm.fits(120, 8)
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")   # the kill switch
    assert ssm.selective_scan_form(128, 8) == "plain"


def _equations(jaxpr, inside_kernels=False):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not inside_kernels:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, inside_kernels)


def test_the_fused_program_is_two_kernels_and_no_loop(monkeypatch):
    """Forward and backward are one `pallas_call` each, named for the
    trace; outside them no loop is left, and nowhere a value as large as
    [T, E, N]. The undifferentiated call writes no block states: two
    results, where the differentiated forward has a third."""
    _take("fused", monkeypatch)
    t = 300
    args = inputs(8, "fused", t=t)

    def total(*a):
        y, last = ssm.selective_scan(*a, lengths=jnp.asarray([t, t - 5]))
        return jnp.sum(y) + jnp.sum(last)

    eqns = list(_equations(jax.make_jaxpr(
        jax.grad(total, argnums=range(6)))(*args).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [c.params["name"] for c in calls] == [
        "selective_scan_fwd", "selective_scan_bwd"]
    assert len(calls[0].outvars) == 3
    assert calls[0].outvars[2].aval.shape == (2, 3, 8, 128)   # 3 blocks
    assert not [e for e in eqns if e.primitive.name in ("scan", "while")]
    channels, n = WIDTHS["fused"]
    for eqn in eqns:
        for v in eqn.outvars:
            assert v.aval.size < t * channels * n, (eqn.primitive, v.aval)
    primal = [e for e in _equations(jax.make_jaxpr(total)(*args).jaxpr)
              if e.primitive.name == "pallas_call"]
    assert [len(c.outvars) for c in primal] == [2]
    # the plain form, for contrast, is loops
    monkeypatch.setattr(pk, "_INTERPRET", False)
    plain = list(_equations(jax.make_jaxpr(        # a new trace: no cache
        lambda *a: total(*a))(*args).jaxpr))
    assert [e for e in plain if e.primitive.name == "scan"]
    assert not [e for e in plain if e.primitive.name == "pallas_call"]


def test_a_recomputed_block_round_the_kernels_has_the_plain_gradients(
        monkeypatch):
    """Inside `layer.recompute` with nothing kept, backward runs the
    forward kernel again and then the backward kernel."""
    feed = _mixer_feed(64)
    results = {}
    for which in ("plain", "fused"):
        monkeypatch.setattr(pk, "_INTERPRET", which == "fused")
        L.reset_name_counters()
        topo = _mixer(64, recompute=True)
        results[which] = jax.value_and_grad(lambda p: jnp.sum(jnp.square(
            topo.apply(p, feed, mode="train")[0]["block"].data)))(
                _mixer_params(topo))
        assert _scan_gauges() == ((1, 0) if which == "fused" else (0, 1))
    (want, want_grads), (got, got_grads) = results["plain"], results["fused"]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name in want_grads:
        # a leaf's small entries are sums that cancel: to its own scale
        np.testing.assert_allclose(
            got_grads[name], want_grads[name], rtol=2e-4,
            atol=2e-5 * max(1.0, float(jnp.abs(want_grads[name]).max())))


def test_the_cost_of_the_kernels_from_shapes():
    """Pinned for the roofline share a `benchmark` issue will add: at
    phi's [2, 4096, 5120, 16] with bf16 x."""
    assert pallas_ssm.blocks(4096, 5120, 16) == (128, 512)
    assert pallas_ssm.blocks(37, 128, 8) == (40, 128)
    cost = pallas_ssm.selective_scan_cost(2, 4096, 5120, 16, jnp.bfloat16)
    elements, rows = 2 * 4096 * 5120 * 16, 2 * 4096 * 5120
    entering = 4 * 2 * 32 * 16 * 5120
    small = 4 * (2 * 2 * 4096 * 16 + 16 * 5120 + 2 * 2 * 16 * 5120)
    assert cost == {
        "fwd": {"flops": 7 * elements + rows, "transcendentals": elements,
                "bytes": 10 * rows + small + entering},
        "bwd": {"flops": 23 * elements + 4 * rows,
                "transcendentals": 2 * elements,
                "bytes": 16 * rows + 2 * small + entering}}
    assert entering == 20_971_520 and cost["fwd"]["bytes"] == 443_088_896


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_for_v5e_at_the_published_size(one_chip,
                                                           monkeypatch):
    """Mosaic takes both kernels at phi's [2, 4096, 5120, 16], which the
    interpreter cannot show."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def total(x, dt, a, b_mat, c_mat):
        y, last = pallas_ssm.selective_scan(x, dt, a, b_mat, c_mat, None)
        return jnp.sum(y) + jnp.sum(last)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.grad(total, argnums=range(5))).lower(
            shape((2, 4096, 5120)), shape((2, 4096, 5120), jnp.float32),
            shape((5120, 16), jnp.float32), shape((2, 4096, 16)),
            shape((2, 4096, 16))).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    # dy, d dt float32 and the 21 MB of block states, no [T, E, N] (5.4 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


@pytest.mark.parametrize("form", ["plain", "fused"])
def test_the_expert_layer_compiles_for_v5e_at_the_published_size(
        one_chip, form, monkeypatch):
    """`ops/moe.py moe`, forward and backward, at the `lfm2-8b-a1b` cell's
    shapes (8,192 positions of 2,048, top-4 of 32, 8 experts of 1,792
    held) for a described v5e. It lives here because only one test file
    may describe the chip (the module's fixture). The plain form (which
    the TPU no longer takes at these shapes): the grouped products become
    Mosaic calls (`ragged-dot` with its metadata: the tiles the groups
    fill), six of them, and nothing holds a [held, rows, width]
    expansion of the buffer: that dense form, which the CPU lowers to,
    would be 1.9 GB. The fused form: the ten named kernels of
    `ops/pallas_moe.py`, and outside them nothing that makes or moves a
    row of the 32,768-row buffer as wide as an expert (the pair
    permutations are kernels too), in less temporary memory than the
    plain form's 1.03 GB."""
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu.ops import moe as moe_ops

    if form == "fused":
        monkeypatch.setattr(pk, "_INTERPRET", True)
        monkeypatch.setattr(pk, "_interpret", lambda: False)
    assert moe_ops.experts_form(2048, 1792, 8192, 4) == form

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def total(x, router, w_in, w_out, bias, valid):
        out, here, busiest, _ = moe_ops.moe(x, valid, router, bias, w_in,
                                            w_out, 4, 0)
        return jnp.sum(out.astype(jnp.float32)), (here, busiest)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.value_and_grad(total, argnums=range(4),
                                              has_aux=True)).lower(
            shape((8192, 2048)), shape((2048, 32)), shape((8, 2048, 3584)),
            shape((8, 1792, 2048)), shape((32,), jnp.float32),
            shape((8192,), jnp.bool_)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    if form == "plain":
        assert text.count('op_name="ragged-dot-metadata"') >= 1
        assert text.count("tpu_custom_call") >= 6
        # the sorted rows, two products' outputs and their gradients:
        # 32,768 rows of 2,048 to 3,584 values in bfloat16, under 1.5 GB
        assert temp < 1.5e9
        return
    names = ("moe_gather", "moe_gmm_in", "moe_gmm_out", "moe_combine",
             "moe_combine_t", "moe_gmm_out_t", "moe_tgmm_out", "moe_gmm_in_t",
             "moe_gather_t", "moe_tgmm_in")
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert re.search(r"%%%s(\.\d+)? = .*tpu_custom_call" % name, text), name
    # the step's own instructions (a fusion's insides carry no names): none
    # but the kernels makes or moves a row of the buffer
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    for line in re.findall(r"^\s*(?:ROOT )?%\S+ = \w+\[32768,\d+\][^\n]*",
                           entry, re.M):
        width = int(re.search(r"\[32768,(\d+)\]", line).group(1))
        assert width < 1792 or re.search(
            r"tpu_custom_call|parameter\(|get-tuple-element\(", line), \
            line[:200]
    # the parent's plain form read 1,026,910,208 bytes
    assert temp < 1_026_910_208


@pytest.mark.parametrize("b,heads,kv,d,dv,window", [
    (4, 48, 8, 128, 128, None), (2, 40, 20, 64, 128, 512)])
def test_the_attention_kernels_compile_for_v5e_at_the_cells_size(
        one_chip, b, heads, kv, d, dv, window, monkeypatch):
    """`ops/attention.py blockwise_attention`, forward and backward, for a
    described v5e at laguna's full layer (4 rows of 4,096 positions, 48
    query heads over 8 of 128) and phi's window layer (2 rows, 40 over 20
    of 64, values of 128, 512 keys): the three named kernels of
    `ops/pallas_attention.py` and no key-block loop. (Here, beside the
    other kernels, because only one test file may describe the chip.)"""
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu.ops import attention as attention_ops

    monkeypatch.setattr(pk, "_INTERPRET", True)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    assert attention_ops.attention_form(heads, kv, d, dv) == "fused"

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def total(q, k, v, lengths):
        return jnp.sum(attention_ops.blockwise_attention(
            q, k, v, d ** -0.5, True, lengths, 512, window).astype(
                jnp.float32))

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.grad(total, argnums=range(3))).lower(
            shape((b, 4096, heads, d)), shape((b, 4096, kv, d)),
            shape((b, 4096, kv, dv)), shape((b,), jnp.int32)
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    names = ("attention_fwd", "attention_bwd_dq", "attention_bwd_dkv")
    assert text.count("tpu_custom_call") == len(names)
    for name in names:
        assert re.search(r"%%%s(\.\d+)? = .*tpu_custom_call" % name, text), name
    assert not re.search(r" while\(", text)
