"""State-space mixing for Mamba-2 layers: the chunked scan and the causal
depthwise convolution in front of it.

The recurrence, a head at a time (x_t [P], B_t and C_t [N], dt_t and A
scalars, state S [P, N]):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

``ops/rnn.py`` would step it token by token. :func:`ssd_scan` computes the
same values in chunks (Dao & Gu 2024, "state-space duality"): inside a
chunk every output is a masked, decayed product over the chunk's own
tokens, each chunk adds one state, and only those states are carried from
chunk to chunk by ``lax.scan``. Plain XLA; its backward is what autodiff
makes of it. Decays and their exponentials are float32 whatever the
operands are.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core import dtype as dtype_mod


def causal_conv1d(x, weight, bias=None, lengths=None):
    """Depthwise causal convolution over time: x [B, T, C], weight [C, K]
    (tap K-1 is the current token), bias [C]:
    y_t = bias + sum_k weight[:, k] * x_{t-K+1+k}, with zeros before the
    sequence starts. ``lengths`` [B] zeroes the padded tail."""
    with jax.named_scope("paddle_tpu.causal_conv1d"):
        taps = weight.shape[1]
        t = x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        y = sum(padded[:, k:k + t] * weight[:, k] for k in range(taps))
        if bias is not None:
            y = y + bias
        if lengths is not None:
            y = jnp.where(_valid(lengths, t)[..., None], y, 0)
        return y


def _valid(lengths, t):
    return jnp.arange(t)[None, :] < lengths[:, None]


def ssd_scan(x, dt, a, b_mat, c_mat, d_skip=None, chunk=256, lengths=None,
             initial_state=None, return_state=False):
    """The recurrence above for x [B, T, H, P], dt [B, T, H] (positive),
    a [H] (negative), b_mat and c_mat [B, T, G, N] (H a multiple of G:
    each group of heads shares one B and C), d_skip [H], in chunks of
    ``chunk`` tokens. ``lengths`` [B] freezes the state over the padded
    tail and zeroes its outputs. Returns y [B, T, H, P], and the state
    after the last valid token [B, H, P, N] with ``return_state``."""
    with jax.named_scope("paddle_tpu.ssd_scan"):
        return _ssd_scan(x, dt, a, b_mat, c_mat, d_skip, chunk, lengths,
                         initial_state, return_state)


def _ssd_scan(x, dt, a, b_mat, c_mat, d_skip, chunk, lengths, initial_state,
              return_state):
    batch, t, heads, p = x.shape
    groups, n = b_mat.shape[2], b_mat.shape[3]
    wide = dtype_mod.wide(x.dtype)
    dt = dt.astype(wide)
    if lengths is not None:
        dt = jnp.where(_valid(lengths, t)[..., None], dt, 0)
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:  # dt 0 over the padding: the state stands still
        x, dt, b_mat, c_mat = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b_mat, c_mat))
    nc = (t + pad) // chunk

    def chunks(v):
        return v.reshape((batch, nc, chunk) + v.shape[2:])

    # heads as [G, H/G], so a group's B and C meet its heads without a copy
    per = heads // groups
    xc = chunks(x).reshape(batch, nc, chunk, groups, per, p)
    dtc = chunks(dt).reshape(batch, nc, chunk, groups, per)
    bc, cc = chunks(b_mat), chunks(c_mat)
    log_decay = dtc * a.astype(wide).reshape(groups, per)   # [B, nc, L, G, R]
    cum = jnp.cumsum(log_decay, axis=2)
    x_dt = xc.astype(wide) * dtc[..., None]

    # inside a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                    preferred_element_type=wide)
    cum_h = jnp.moveaxis(cum, 2, -1)                        # [B, nc, G, R, L]
    gap = cum_h[..., :, None] - cum_h[..., None, :]         # [.., L(l), L(s)]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, gap, -jnp.inf))
    scores = (cb[:, :, :, None] * decay).astype(x.dtype)    # [B,nc,G,R,L,L]
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", scores, x_dt.astype(x.dtype),
                   preferred_element_type=wide)

    # what each chunk adds to the state by its end
    to_end = jnp.exp(cum[:, :, -1:] - cum)                  # [B, nc, L, G, R]
    added = jnp.einsum("bclgn,bclgrp->bcgrpn", bc,
                       (x_dt * to_end[..., None]).astype(x.dtype),
                       preferred_element_type=wide)

    # from chunk to chunk: the one sequential part
    chunk_decay = jnp.exp(cum[:, :, -1])                    # [B, nc, G, R]
    if initial_state is None:
        state0 = jnp.zeros((batch, groups, per, p, n), wide)
    else:
        state0 = initial_state.astype(wide).reshape(batch, groups, per, p, n)

    def carry_state(state, xs):
        decay_c, added_c = xs
        return state * decay_c[..., None, None] + added_c, state

    last, entering = jax.lax.scan(
        carry_state, state0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # [B,nc,G,R,P,N]

    # the entering state, decayed to each token, read through C
    y = y + jnp.einsum(
        "bclgn,bcgrpn->bclgrp", cc, entering.astype(x.dtype),
        preferred_element_type=wide) * jnp.exp(cum)[..., None]
    y = y.reshape(batch, t + pad, heads, p)[:, :t]
    x = x[:, :t]
    if d_skip is not None:
        y = y + x.astype(wide) * d_skip.astype(wide)[:, None]
    if lengths is not None:
        y = jnp.where(_valid(lengths, t)[..., None, None], y, 0)
    y = y.astype(x.dtype)
    if return_state:
        return y, last.reshape(batch, heads, p, n)
    return y
