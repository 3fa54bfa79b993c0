"""State-space mixing for Mamba layers: the chunked scan of Mamba-2, the
selective scan of Mamba-1, and the causal depthwise convolution in front
of both.

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

Mamba-1 (Gu & Dao 2023, arXiv:2312.00752, Algorithm 2) has a decay of its
own for every channel c and state n, so no chunk is a matrix product:

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]

:func:`selective_scan` steps it token by token and keeps for backward the
state that enters each block of tokens only: on the TPU, at widths that
tile, as two fused Pallas kernels (``ops/pallas_ssm.py``); everywhere
else as two nested ``lax.scan``s, which are also the kernels' oracle.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.ops import pallas_ssm


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


def selective_scan_form(channels, states):
    """"fused" where :func:`selective_scan` runs ``ops/pallas_ssm.py``'s
    two kernels, "plain" where it runs the loops of this module: the
    backend and the shapes decide (``pallas_ssm.fits``), nothing else."""
    return "fused" if pallas_ssm.fits(channels, states) else "plain"


def selective_scan(x, dt, a, b_mat, c_mat, d_skip=None, chunk=16,
                   lengths=None, initial_state=None):
    """Mamba-1's recurrence for x [B, T, E], dt [B, T, E] (positive),
    a [E, N] (negative), b_mat and c_mat [B, T, N], d_skip [E]: the state
    [B, E, N] is stepped token by token in float32, exactly (no quotient
    of decays, so no dt * |a| is too large). ``lengths`` [B] freezes the
    state over the padded tail and zeroes its outputs. Returns
    (y [B, T, E], the state after the last valid token [B, E, N]).

    Two forms of the one recurrence (:func:`selective_scan_form`). On the
    TPU, where the channels are a multiple of 128 and the states of 8, the
    fused one: two Pallas kernels that keep the state in VMEM and for
    backward the state that enters each block of tokens
    (``ops/pallas_ssm.py``). Everywhere else (the CPU, narrow layers) the
    plain one, which is also the kernels' oracle: ``lax.scan`` over chunks
    of ``chunk`` tokens round ``lax.scan`` over a chunk's tokens; backward
    keeps the state that enters each chunk and steps the chunk again.
    ``chunk`` means something to the plain form only. Neither keeps a
    [T, E, N] value alive for a whole row."""
    with jax.named_scope("paddle_tpu.selective_scan"):
        t = x.shape[1]
        wide = dtype_mod.wide(x.dtype)
        dt = dt.astype(wide)
        if lengths is not None:
            dt = jnp.where(_valid(lengths, t)[..., None], dt, 0)
        if selective_scan_form(x.shape[2], a.shape[1]) == "fused":
            y, last = pallas_ssm.selective_scan(x, dt, a, b_mat, c_mat,
                                                initial_state)
        else:
            y, last = _token_loops(x, dt, a, b_mat, c_mat, chunk,
                                   initial_state)
        if d_skip is not None:
            y = y + x.astype(wide) * d_skip.astype(wide)
        if lengths is not None:
            y = jnp.where(_valid(lengths, t)[..., None], y, 0)
        return y.astype(x.dtype), last


def _token_loops(x, dt, a, b_mat, c_mat, chunk, initial_state):
    """The plain form: (y [B, T, E] wide, the last state [B, E, N])."""
    batch, t, channels = x.shape
    n = a.shape[1]
    wide = dt.dtype
    chunk = min(chunk, t)
    pad = -t % chunk
    nc = (t + pad) // chunk

    def by_chunk(v):   # [B, T, W] -> [nc, chunk, B, W]; dt 0 over the padding
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(v, 1, 0).reshape((nc, chunk) + v.shape[:1]
                                             + v.shape[2:])

    # the state as [B, N, E]: the channels lie along the lanes
    a_t = a.astype(wide).T

    def token(state, xs):
        dt_t, x_t, b_t, c_t = xs           # [B, E] [B, E] [B, N] [B, N]
        state = jnp.exp(dt_t[:, None, :] * a_t) * state \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return state, jnp.sum(state * c_t[:, :, None], axis=1)

    @jax.checkpoint
    def one_chunk(state, xs):
        return jax.lax.scan(token, state, xs)

    if initial_state is None:
        state0 = jnp.zeros((batch, n, channels), wide)
    else:
        state0 = jnp.swapaxes(initial_state.astype(wide), 1, 2)
    last, y = jax.lax.scan(
        one_chunk, state0,
        tuple(by_chunk(v.astype(wide)) for v in (dt, x, b_mat, c_mat)))
    y = jnp.moveaxis(y.reshape(t + pad, batch, channels), 0, 1)[:, :t]
    return y, jnp.swapaxes(last, 1, 2)
