"""`ops/ssm.py`: the chunked state-space scan against the token-by-token
recurrence it stands for, in values and gradients, and the causal
depthwise convolution against its sum written out. Float32 at `highest`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssm


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(x, dt, a, b_mat, c_mat, d_skip, lengths):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t."""
    batch, t, heads, p = x.shape
    per = heads // b_mat.shape[2]
    b_mat, c_mat = (jnp.repeat(v, per, axis=2) for v in (b_mat, c_mat))
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    dt = jnp.where(valid[..., None], dt, 0)

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) \
            + d_skip[:, None] * x_t

    last, y = jax.lax.scan(
        step, jnp.zeros((batch, heads, p, b_mat.shape[-1])),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b_mat, c_mat)))
    return jnp.where(valid[..., None, None], jnp.moveaxis(y, 0, 1), 0), last


def inputs(seed, batch=2, t=37, heads=4, p=8, groups=2, n=16):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (normal(batch, t, heads, p),
            jax.nn.softplus(normal(batch, t, heads)),
            -jnp.exp(normal(heads)), normal(batch, t, groups, n),
            normal(batch, t, groups, n), normal(heads))


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_scan_is_the_token_recurrence(chunk):
    """37 and 29 tokens are no multiple of 8 or 16, and fewer than 64."""
    args = inputs(0)
    lengths = jnp.asarray([37, 29])
    want, want_state = recurrence(*args, lengths)
    got, got_state = ssm.ssd_scan(*args, chunk=chunk, lengths=lengths,
                                  return_state=True)
    np.testing.assert_allclose(got, want, atol=5e-5)
    np.testing.assert_allclose(got_state, want_state, atol=1e-5)


def test_chunked_scan_has_the_recurrences_gradients():
    x, dt, a, b_mat, c_mat, d_skip = inputs(1)
    lengths = jnp.asarray([37, 30])

    def through(fn):
        return jax.grad(
            lambda *v: jnp.sum(jnp.sin(fn(*v))), argnums=(0, 1, 2, 3, 4, 5))(
                x, dt, a, b_mat, c_mat, d_skip)

    want = through(lambda *v: recurrence(*v, lengths)[0])
    got = through(lambda *v: ssm.ssd_scan(*v, chunk=8, lengths=lengths))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.abs(w).max()))


def test_chunk_64_is_chunk_256_in_values_and_gradients():
    args = inputs(2, batch=1, t=300, heads=2, p=4, groups=1, n=8)
    # steps of 0.1 and less, as the model's: over 256 tokens the summed
    # decays stay small enough for float32 to tell them apart
    args = (args[0], 0.1 * args[1]) + args[2:]
    lengths = jnp.asarray([281])

    def run(chunk):
        fn = lambda x, dt: ssm.ssd_scan(x, dt, *args[2:], chunk=chunk,
                                        lengths=lengths)
        y, vjp = jax.vjp(fn, *args[:2])
        return (y,) + vjp(jnp.cos(y))

    for small, large in zip(run(64), run(256)):
        np.testing.assert_allclose(small, large, rtol=2e-4, atol=2e-4)


def test_nothing_leaks_back_in_time():
    x, dt, a, b_mat, c_mat, d_skip = inputs(3)
    at = 21
    y = ssm.ssd_scan(x, dt, a, b_mat, c_mat, d_skip, chunk=8)
    moved = ssm.ssd_scan(x.at[:, at].add(1.0), dt.at[:, at].mul(2.0), a,
                         b_mat.at[:, at].add(1.0), c_mat.at[:, at].add(1.0),
                         d_skip, chunk=8)
    np.testing.assert_array_equal(moved[:, :at], y[:, :at])
    assert float(jnp.abs(moved[:, at:] - y[:, at:]).max()) > 1e-3


def test_the_padded_tail_changes_nothing_and_reads_zero():
    x, dt, a, b_mat, c_mat, d_skip = inputs(4)
    lengths = jnp.asarray([20, 37])
    y, state = ssm.ssd_scan(x, dt, a, b_mat, c_mat, d_skip, chunk=8,
                            lengths=lengths, return_state=True)
    y2, state2 = ssm.ssd_scan(x.at[0, 20:].set(9.0), dt.at[0, 20:].set(3.0),
                              a, b_mat.at[0, 20:].set(9.0), c_mat, d_skip,
                              chunk=8, lengths=lengths, return_state=True)
    np.testing.assert_array_equal(y2, y)
    np.testing.assert_array_equal(state2, state)
    assert float(jnp.abs(y[0, 20:]).max()) == 0.0
    # row 0's state is the one its 20 tokens alone leave
    alone = ssm.ssd_scan(x[:1, :20], dt[:1, :20], a, b_mat[:1, :20],
                         c_mat[:1, :20], d_skip, chunk=8,
                         return_state=True)[1]
    np.testing.assert_allclose(state[:1], alone, atol=1e-5)


def test_a_scan_goes_on_from_the_state_it_left():
    x, dt, a, b_mat, c_mat, d_skip = inputs(5)
    whole, last = ssm.ssd_scan(x, dt, a, b_mat, c_mat, d_skip, chunk=8,
                               return_state=True)
    cut = 19
    head, state = ssm.ssd_scan(x[:, :cut], dt[:, :cut], a, b_mat[:, :cut],
                               c_mat[:, :cut], d_skip, chunk=8,
                               return_state=True)
    tail, end = ssm.ssd_scan(x[:, cut:], dt[:, cut:], a, b_mat[:, cut:],
                             c_mat[:, cut:], d_skip, chunk=8,
                             initial_state=state, return_state=True)
    np.testing.assert_allclose(jnp.concatenate([head, tail], axis=1), whole,
                               atol=5e-5)
    np.testing.assert_allclose(end, last, atol=1e-5)


def test_bfloat16_operands_keep_float32_decays():
    args = inputs(6, t=64)
    want = ssm.ssd_scan(*args, chunk=16)
    low = [v.astype(jnp.bfloat16) for v in args]
    low[1], low[2] = args[1], args[2]          # dt and A stay float32
    got = ssm.ssd_scan(*low, chunk=16)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.03 * scale


@pytest.mark.parametrize("with_lengths", [False, True])
def test_causal_conv_is_its_sum_written_out(with_lengths):
    rng = np.random.default_rng(7)
    x = rng.standard_normal((2, 11, 5)).astype(np.float32)
    w = rng.standard_normal((5, 4)).astype(np.float32)
    b = rng.standard_normal((5,)).astype(np.float32)
    lengths = np.asarray([11, 7])
    want = np.zeros_like(x)
    for t in range(11):
        for k in range(4):
            src = t - 3 + k
            if src >= 0:
                want[:, t] += w[:, k] * x[:, src]
        want[:, t] += b
    if with_lengths:
        want[1, 7:] = 0
    got = ssm.causal_conv1d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                            jnp.asarray(lengths) if with_lengths else None)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # a token moves nothing before it
    moved = ssm.causal_conv1d(jnp.asarray(x).at[:, 6].add(1.0),
                              jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_array_equal(
        moved[:, :6], ssm.causal_conv1d(jnp.asarray(x), jnp.asarray(w),
                                        jnp.asarray(b))[:, :6])
