"""`ops/ssm.py selective_scan`: Mamba-1's scan, kept by chunks, against
the token-by-token recurrence it stands for, in values and gradients, with
lengths, from a given state, across chunk sizes. Float32 at `highest`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import ssm


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


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


def inputs(seed, batch=2, t=37, channels=12, n=4):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (normal(batch, t, channels),
            jax.nn.softplus(normal(batch, t, channels)),
            -jnp.exp(normal(channels, n)), normal(batch, t, n),
            normal(batch, t, n), normal(channels))


@pytest.mark.parametrize("chunk", [5, 8, 16, 64])
def test_the_chunked_scan_is_the_token_recurrence(chunk):
    """37 and 29 tokens are no multiple of 5, 8 or 16, and fewer than
    64."""
    args = inputs(0)
    lengths = jnp.asarray([37, 29])
    want, want_state = recurrence(*args, lengths)
    got, got_state = ssm.selective_scan(*args, chunk=chunk, lengths=lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5)
    assert got_state.shape == (2, 12, 4)


@pytest.mark.parametrize("chunk", [7, 16])
def test_the_chunked_scan_has_the_recurrences_gradients(chunk):
    args = inputs(1, t=19)
    lengths = jnp.asarray([19, 11])
    weight = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 19, 12)), jnp.float32)

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


def test_a_scan_goes_on_from_the_state_it_left():
    args = inputs(3, t=32)
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


def test_the_padded_tail_changes_nothing_and_reads_zero():
    x, dt, a, b_mat, c_mat, d = inputs(4, t=24)
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


def test_nothing_leaks_back_in_time():
    x, dt, a, b_mat, c_mat, d = inputs(5, t=24)
    y = ssm.selective_scan(x, dt, a, b_mat, c_mat, d, 8)[0]
    y2 = ssm.selective_scan(x.at[:, 17:].add(3.0), dt, a,
                            b_mat.at[:, 17:].add(1.0), c_mat, d, 8)[0]
    np.testing.assert_array_equal(y[:, :17], y2[:, :17])
    assert float(jnp.abs(y[:, 17:] - y2[:, 17:]).max()) > 0


def test_a_decay_that_underflows_is_exact_and_finite():
    """dt * |A| of 200 a token, 25,600 over a chunk of 128: a form that
    divided by the decay over a chunk would overflow; the state is
    stepped, so the output is the last token's alone."""
    x, _, _, b_mat, c_mat, d = inputs(6, t=16)
    dt = jnp.full(x.shape, 50.0)
    a = jnp.full((12, 4), -4.0)
    y, state = ssm.selective_scan(x, dt, a, b_mat, c_mat, d, 128)
    want = (dt * x) * jnp.sum(b_mat * c_mat, axis=-1, keepdims=True) + d * x
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(state).all())
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda dt_: jnp.sum(ssm.selective_scan(
        x, dt_, a, b_mat, c_mat, d, 128)[0]))(dt)
    assert bool(jnp.isfinite(g).all())


def test_bfloat16_operands_keep_a_float32_state():
    x, dt, a, b_mat, c_mat, d = inputs(7, t=24)
    low = [v.astype(jnp.bfloat16) for v in (x, b_mat, c_mat)]
    y, state = ssm.selective_scan(low[0], dt, a, low[1], low[2], d, 8)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    want = ssm.selective_scan(*(v.astype(jnp.float32) for v in low[:1]), dt,
                              a, *(v.astype(jnp.float32) for v in low[1:]),
                              d, 8)[0]
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2)
