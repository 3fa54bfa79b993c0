"""Softmax attention that never holds a whole score matrix.

One accumulator serves every caller: :func:`online_softmax_step` folds a
block of keys into a running (row max, normaliser, unnormalised output).
``parallel/context_parallel.py`` steps it once per ring hop (and once in
all for ``full_attention``); :func:`blockwise_attention` steps it over
blocks of keys inside one device, a block of queries at a time, which
is what ``layer.gqa_attention`` runs. Where :func:`attention_form` says
"fused" (the TPU, shapes that tile), ``blockwise_attention`` runs as
``ops/pallas_attention.py``'s kernels instead, the same arithmetic with
each tile's scores kept in VMEM; only :func:`attention_form` and that
branch import the module.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import dtype as dtype_mod

NEG = -1e30  # finite mask value: keeps exp() and grads NaN-free


def online_softmax_init(batch, q_len, heads, head_dim, operand_dtype):
    """(m [B, H, Lq] running row max, l [B, H, Lq] running normaliser,
    o [B, Lq, H, D] unnormalised output), before any key; float32 at
    least, whatever the operands are."""
    dtype = dtype_mod.wide(operand_dtype)
    return (jnp.full((batch, heads, q_len), NEG, dtype),
            jnp.zeros((batch, heads, q_len), dtype),
            jnp.zeros((batch, q_len, heads, head_dim), dtype))


def online_softmax_step(carry, q, k_blk, v_blk, scale, mask=None):
    """Folds one block of keys into the accumulator. q [B, Lq, H, D],
    k_blk and v_blk [B, Lk, H, D]; ``mask`` broadcasts against the scores
    [B, H, Lq, Lk], False where a key may not be seen."""
    m, l, o = carry
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                   preferred_element_type=m.dtype) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)                   # rescales what came before
    p = jnp.exp(s - m_new[..., None])
    l = l * alpha + jnp.sum(p, axis=-1)
    o = o * jnp.transpose(alpha, (0, 2, 1))[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=o.dtype)
    return m_new, l, o


def online_softmax_finish(carry, dtype):
    _, l, o = carry
    norm = jnp.transpose(jnp.maximum(l, 1e-30), (0, 2, 1))[..., None]
    return (o / norm).astype(dtype)


def attention_mask(q_pos, k_pos, causal, lengths, window=None):
    """[B or 1, 1, Lq, Lk] bool, or None when every key may be seen. With
    ``window`` a query sees the ``window`` keys that end with its own."""
    mask = None
    if causal:
        mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
    if window is not None:
        near = (q_pos[:, None] - k_pos[None, :] < window)[None, None]
        mask = near if mask is None else mask & near
    if lengths is not None:
        valid = (k_pos[None, :] < lengths[:, None])[:, None, None, :]
        mask = valid if mask is None else mask & valid
    return mask


def key_blocks(t, block, causal=True, window=None):
    """[(first, end)] of the key blocks each block of queries visits, for
    ``t`` positions in blocks of ``block``: a causal block stops at its
    own diagonal block, and with ``window`` it starts at the block that
    holds the oldest key its first query sees."""
    block = min(block, t)
    n = -(-t // block)
    return [(0 if window is None
             else max(0, (i * block - window + 1) // block),
             i + 1 if causal else n) for i in range(n)]


def attention_form(heads, kv_heads, d, dv):
    """"fused" where :func:`blockwise_attention` over ``heads`` query
    heads, ``kv_heads`` key-value heads, keys of ``d`` and values of
    ``dv`` runs ``ops/pallas_attention.py``'s kernels, "plain" where it
    runs the scans below: the backend and the shapes decide
    (``pallas_attention.fits``), nothing else."""
    from paddle_tpu.ops import pallas_kernels

    if not pallas_kernels.enabled():
        return "plain"
    from paddle_tpu.ops import pallas_attention

    return "fused" if pallas_attention.fits(heads, kv_heads, d, dv) \
        else "plain"


def blockwise_attention(q, k, v, scale, causal=True, lengths=None,
                        block=512, window=None):
    """Attention of q [B, T, H, D] over k [B, T, KV, D] and v
    [B, T, KV, Dv] (H a multiple of KV: each group of H // KV query heads
    shares one key-value head), scores scaled by ``scale``, without a
    [T, T] score matrix: queries go a block at a time, and for each the
    keys stream through the online softmax a block at a time, every step
    recomputed in backward. A causal query block stops at its own
    diagonal block. ``lengths`` [B] hides the keys of the padded tail.
    With ``window`` (causal) a query sees the ``window`` keys that end
    with its own: key blocks wholly older than that are not visited
    (:func:`key_blocks`), the block on the window's edge is masked. Runs
    in the form :func:`attention_form` chooses; the kernels tile by
    ``pallas_attention.tile``, not by ``block``."""
    if attention_form(q.shape[2], k.shape[2], q.shape[3],
                      v.shape[3]) == "fused":
        from paddle_tpu.ops import pallas_attention

        return pallas_attention.attention(q, k, v, scale, causal, lengths,
                                          window)
    b, t, h, d = q.shape
    groups = h // k.shape[2]
    if groups > 1:
        k, v = (jnp.repeat(a, groups, axis=2) for a in (k, v))
    block = min(block, t)
    pad = -t % block
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        if lengths is None and not causal:
            lengths = jnp.full((b,), t, jnp.int32)
    n = (t + pad) // block
    # [n, B, block, H, D]: one block of keys a scan step
    k_blocks, v_blocks = (
        jnp.moveaxis(a.reshape(b, n, block, h, a.shape[-1]), 1, 0)
        for a in (k, v))
    offsets = jnp.arange(n) * block
    within = jnp.arange(block)

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def step(carry, q_blk, xs, q_start):
        k_blk, v_blk, k_start = xs
        mask = attention_mask(q_start + within, k_start + within, causal,
                              lengths, window)
        return online_softmax_step(carry, q_blk, k_blk, v_blk, scale, mask)

    out = []
    for i, (first, end) in enumerate(key_blocks(t + pad, block, causal,
                                                window)):
        q_blk = q[:, i * block:(i + 1) * block]
        carry, _ = jax.lax.scan(
            lambda c, xs: (step(c, q_blk, xs, i * block), None),
            online_softmax_init(b, block, h, v.shape[-1], q.dtype),
            (k_blocks[first:end], v_blocks[first:end], offsets[first:end]))
        out.append(online_softmax_finish(carry, q.dtype))
    return jnp.concatenate(out, axis=1)[:, :t]


def rotary(x, theta, dims=None, inverse=None, factor=1.0):
    """Rotary positions 0..T-1 over the first ``dims`` values (all D by
    default, even) of the last axis of x [B, T, H, D], in the rotate-half
    pairing: value i turns with value i + dims/2 by the angle position *
    inverse[i], ``inverse`` [dims/2] given (a scaled table, as
    :func:`yarn_inverse_frequencies` makes) or theta^(-2i/dims). Cosines
    and sines are multiplied by ``factor``; the values past ``dims`` pass
    through as they are. Angles, sines and the turn in float32."""
    with jax.named_scope("paddle_tpu.rope"):
        dims = x.shape[-1] if dims is None else dims
        half = dims // 2
        wide = dtype_mod.wide(x.dtype)
        if inverse is None:
            inverse = theta ** (-jnp.arange(half, dtype=wide) / half)
        else:
            inverse = jnp.asarray(inverse, wide)
        angles = jnp.arange(x.shape[1], dtype=wide)[:, None] \
            * inverse[None, :]
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
        if factor != 1.0:
            cos, sin = cos * factor, sin * factor
        turned = x if dims == x.shape[-1] else x[..., :dims]
        first, second = jnp.split(turned.astype(wide), 2, axis=-1)
        out = [first * cos - second * sin, second * cos + first * sin]
        if dims != x.shape[-1]:
            out.append(x[..., dims:].astype(wide))
        return jnp.concatenate(out, axis=-1).astype(x.dtype)


def yarn_inverse_frequencies(dim, theta, factor, original, beta_fast=32,
                             beta_slow=1):
    """[dim / 2] float32 inverse frequencies of YaRN (Peng et al. 2023,
    arXiv:2309.00071) over ``dim`` rotated values, in the form of
    ``transformers``' ``_compute_yarn_parameters``: pair i blends
    theta^(-2i/dim) (extrapolated) and the same over ``factor``
    (interpolated) by a ramp between the pairs that turn ``beta_fast``
    and ``beta_slow`` times over ``original`` positions, floored and
    ceiled; the fast pairs keep their frequency, the slow ones take the
    interpolated one. The attention factor multiplies cos and sin in
    :func:`rotary`, not this table."""
    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freqs = np.float32(theta) ** (np.arange(0, dim, 2, dtype=np.float32)
                                  / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1).astype(np.float32)
    kept = 1 - ramp    # the share of the extrapolated frequency
    return ((1 / (factor * freqs)) * (1 - kept) + (1 / freqs) * kept
            ).astype(np.float32)
