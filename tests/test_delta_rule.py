"""`ops/delta_rule.py`: the chunked gated delta rule against the
token-by-token recurrence it stands for, in values and gradients.
Float32 at `highest`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import delta_rule


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(q, k, v, log_alpha, beta, lengths=None, initial_state=None):
    """S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T;
    o_t = S_t^T q_t, a token at a time."""
    batch, t, heads, dk = q.shape
    valid = jnp.ones((batch, t), bool) if lengths is None \
        else jnp.arange(t)[None, :] < lengths[:, None]
    log_alpha = jnp.where(valid[..., None], log_alpha, 0)
    beta = jnp.where(valid[..., None], beta, 0)

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        held = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = jnp.exp(g_t)[..., None, None] * (
            state - b_t[..., None, None] * k_t[..., None] * held[:, :, None])
        state = state + (b_t[..., None] * k_t)[..., None] * v_t[:, :, None]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    state0 = jnp.zeros((batch, heads, dk, v.shape[-1])) \
        if initial_state is None else initial_state
    last, o = jax.lax.scan(
        step, state0,
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_alpha, beta)))
    return jnp.where(valid[..., None, None], jnp.moveaxis(o, 0, 1), 0), last


def inputs(seed, batch=2, t=37, heads=3, dk=8, dv=16, decay=1.0,
           beta_top=2.0):
    """Unit keys, queries scaled by K^-1/2, beta in (0, beta_top), decay
    exp(-decay * softplus(.)): what the layer hands the rule."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(normal(batch, t, heads, dk)) * dk ** -0.5,
            unit(normal(batch, t, heads, dk)), normal(batch, t, heads, dv),
            -decay * jax.nn.softplus(normal(batch, t, heads)),
            beta_top * jax.nn.sigmoid(normal(batch, t, heads)))


@pytest.mark.parametrize("t,chunk", [(64, 16), (37, 8), (37, 16), (37, 64)])
def test_the_chunked_rule_is_the_token_recurrence(t, chunk):
    """64 tokens are four whole chunks of 16; 37 are no multiple of 8 or
    16 and fewer than 64."""
    args = inputs(0, t=t)
    want, want_state = recurrence(*args)
    got, got_state = delta_rule.gated_delta_rule(*args, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5)


def test_rows_shorter_than_the_padded_length():
    """Beyond a row's length the state stands still and outputs are 0."""
    args = inputs(1)
    lengths = jnp.asarray([37, 21])
    want, want_state = recurrence(*args, lengths=lengths)
    got, got_state = delta_rule.gated_delta_rule(*args, chunk=8,
                                                 lengths=lengths)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5)
    assert not np.any(np.asarray(got[1, 21:]))
    # the short row's state is that of its 21 tokens alone
    alone = delta_rule.gated_delta_rule(*(x[1:, :21] for x in args),
                                        chunk=8)[1]
    np.testing.assert_allclose(got_state[1:], alone, atol=2e-5)


def test_the_last_state_chains_two_calls():
    args = inputs(2, t=48)
    whole, whole_state = delta_rule.gated_delta_rule(*args, chunk=16)
    first, state = delta_rule.gated_delta_rule(
        *(x[:, :20] for x in args), chunk=16)
    second, last = delta_rule.gated_delta_rule(
        *(x[:, 20:] for x in args), chunk=16, initial_state=state)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1),
                               whole, atol=2e-5)
    np.testing.assert_allclose(last, whole_state, atol=2e-5)
    want, want_state = recurrence(*(x[:, 20:] for x in args),
                                  initial_state=state)
    np.testing.assert_allclose(second, want, atol=2e-5)
    np.testing.assert_allclose(last, want_state, atol=2e-5)


@pytest.mark.parametrize("case", ["negative_eigenvalues", "strong_decay",
                                  "one_key"])
def test_the_hard_corners(case):
    """beta at 2 makes I - beta k k^T a reflection (eigenvalue -1); a
    decay of exp(-30) a token empties the state; one key repeated through
    a chunk with beta at 2 is where the triangular system is worst."""
    q, k, v, g, beta = inputs(3, t=40)
    if case == "negative_eigenvalues":
        beta = jnp.full_like(beta, 2.0)
        g = jnp.zeros_like(g)
    elif case == "strong_decay":
        g = jnp.full_like(g, -30.0)
    else:
        k = jnp.broadcast_to(k[:, :1], k.shape)
        beta = jnp.full_like(beta, 2.0)
        g = jnp.zeros_like(g)
    want, want_state = recurrence(q, k, v, g, beta)
    got, got_state = delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=16)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=2e-5 * max(scale, 1.0))
    np.testing.assert_allclose(got_state, want_state,
                               atol=2e-5 * max(scale, 1.0))


@pytest.mark.parametrize("lengths", [None, (37, 30)])
def test_the_chunked_rule_has_the_recurrences_gradients(lengths):
    args = inputs(4)
    state = 0.1 * jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 3, 8, 16)), jnp.float32)
    lengths = None if lengths is None else jnp.asarray(lengths)

    def through(fn):
        def cost(*values):
            o, last = fn(*values[:5], lengths=lengths,
                         initial_state=values[5])
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(last))
        return jax.grad(cost, argnums=tuple(range(6)))(*args, state)

    want = through(recurrence)
    got = through(lambda *a, **kw: delta_rule.gated_delta_rule(
        *a, chunk=8, **kw))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4 * float(jnp.abs(w).max()))


def test_bfloat16_operands_keep_decays_and_state_wide():
    args = inputs(6, t=32)
    want = recurrence(*args)[0]
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    got, state = delta_rule.gated_delta_rule(*low, chunk=16)
    assert got.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < 0.05 * float(jnp.abs(want).max())
