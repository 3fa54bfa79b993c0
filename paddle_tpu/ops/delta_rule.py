"""The gated delta rule of Gated DeltaNet linear-attention layers (Yang,
Kautz & Hatamizadeh, arXiv:2412.06464), in chunks.

The recurrence, a head at a time (q_t and k_t [K], v_t [V], alpha_t in
(0, 1] and beta_t in [0, 2] scalars, state S [K, V]):

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Each token first forgets (alpha), then replaces what the state holds
under its key by its value (the Householder-like factor; beta above 1
gives it a negative eigenvalue). Unlike ``ops/ssm.py``'s scalar decay the
factor does not commute into a cumulative product, so the chunked form
(the paper's section 3, the WY / UT transform) goes through a triangular
system. With u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t), what token t
really writes, S_t = alpha_t S_{t-1} + k_t u_t^T, and inside a chunk
that enters with state S and has cumulative log-decay g:

    A = tril(diag(beta) (Gamma * K K^T), -1)      Gamma_ij = exp(g_i - g_j)
    T = (I + A)^-1                                unit lower triangular
    W = T diag(beta) (exp(g) * K);  U = T diag(beta) V
    V' = U - W S                                  the rows u_t
    O  = (exp(g) * Q) S + tril(Gamma * Q K^T) V'
    S <- exp(g_last) S + (exp(g_last - g) * K)^T V'

One state a chunk is carried by ``lax.scan``; everything that does not
read the state is computed for all chunks at once. Plain XLA; its backward
is what autodiff makes of it. Decays, their exponentials (never above 1
where they are used) and T are float32 whatever the operands are; matrix
products take the operands' dtype and sum in float32. T comes from
``solve_triangular`` (forward substitution): the product form
prod_i (I + (-A)^(2^i)) cancels catastrophically where keys repeat and
beta is near 2, which is what negative eigenvalues are for.
"""

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.ops.ssm import _valid


def gated_delta_rule(q, k, v, log_alpha, beta, chunk=64, lengths=None,
                     initial_state=None):
    """The recurrence above for q and k [B, T, H, K], v [B, T, H, V],
    log_alpha (log of the decay, at most 0) and beta [B, T, H], in chunks
    of ``chunk`` tokens. Positions beyond ``lengths`` [B] take beta 0 and
    alpha 1, so the state stands still there, and give zeros.
    ``initial_state`` [B, H, K, V] is the state before the first token.
    Returns (o [B, T, H, V], the state after the last valid token
    [B, H, K, V], float32 at least)."""
    with jax.named_scope("paddle_tpu.delta_rule"):
        return _gated_delta_rule(q, k, v, log_alpha, beta, chunk, lengths,
                                 initial_state)


def _gated_delta_rule(q, k, v, log_alpha, beta, chunk, lengths,
                      initial_state):
    batch, t, heads, dk = q.shape
    dv = v.shape[-1]
    dtype = v.dtype
    wide = dtype_mod.wide(dtype)
    g, beta = log_alpha.astype(wide), beta.astype(wide)
    if lengths is not None:
        valid = _valid(lengths, t)[..., None]
        g, beta = jnp.where(valid, g, 0), jnp.where(valid, beta, 0)
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:  # beta 0 and alpha 1 over the padding
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nc = (t + pad) // chunk

    def chunks(x):                                # [B, nc, H, L, ...]
        x = x.reshape((batch, nc, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-1)                            # [B, nc, H, L]
    gap = cum[..., :, None] - cum[..., None, :]             # [.., L(l), L(s)]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    gamma = jnp.exp(jnp.where(lower, gap, -jnp.inf))        # 0 above diagonal

    # T = (I + A)^-1, A the strictly lower part of diag(beta) (gamma * K K^T)
    kk = jnp.einsum("bchld,bchsd->bchls", k, k, preferred_element_type=wide)
    a = jnp.tril(beta[..., None] * gamma * kk, -1)
    eye = jnp.eye(chunk, dtype=wide)
    t_inv = solve_triangular(a + eye, jnp.broadcast_to(eye, a.shape),
                             lower=True, unit_diagonal=True).astype(dtype)
    w = jnp.einsum("bchls,bchsd->bchld", t_inv,
                   (k * (beta * jnp.exp(cum))[..., None]).astype(dtype),
                   preferred_element_type=wide).astype(dtype)
    u = jnp.einsum("bchls,bchsd->bchld", t_inv,
                   (v * beta[..., None]).astype(dtype),
                   preferred_element_type=wide)

    qk = jnp.einsum("bchld,bchsd->bchls", q, k, preferred_element_type=wide)
    scores = (gamma * qk).astype(dtype)                     # s <= l
    q_in = (q * jnp.exp(cum)[..., None]).astype(dtype)
    k_out = (k * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dtype)
    chunk_decay = jnp.exp(cum[..., -1])                     # [B, nc, H]

    def one_chunk(state, xs):
        w_c, u_c, q_c, k_c, scores_c, decay_c = xs
        low = state.astype(dtype)
        wrote = (u_c - jnp.einsum("bhld,bhde->bhle", w_c, low,
                                  preferred_element_type=wide)).astype(dtype)
        o_c = jnp.einsum("bhld,bhde->bhle", q_c, low,
                         preferred_element_type=wide) \
            + jnp.einsum("bhls,bhse->bhle", scores_c, wrote,
                         preferred_element_type=wide)
        state = state * decay_c[..., None, None] + jnp.einsum(
            "bhld,bhle->bhde", k_c, wrote, preferred_element_type=wide)
        return state, o_c.astype(dtype)

    state0 = jnp.zeros((batch, heads, dk, dv), wide) \
        if initial_state is None else initial_state.astype(wide)
    last, o = jax.lax.scan(
        one_chunk, state0,
        tuple(jnp.moveaxis(x, 1, 0)
              for x in (w, u, q_in, k_out, scores, chunk_decay)))
    o = jnp.transpose(o, (1, 0, 3, 2, 4))                   # [B, nc, L, H, V]
    o = o.reshape(batch, t + pad, heads, dv)[:, :t]
    if lengths is not None:
        o = jnp.where(_valid(lengths, t)[..., None, None], o, 0)
    return o, last
