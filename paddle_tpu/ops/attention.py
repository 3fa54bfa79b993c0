"""Softmax attention that never holds a whole score matrix.

One accumulator serves every caller: :func:`online_softmax_step` folds a
block of keys into a running (row max, normaliser, unnormalised output).
``parallel/context_parallel.py`` steps it once per ring hop (and once in
all for ``full_attention``); :func:`blockwise_attention` steps it over
blocks of keys inside one device, a block of queries at a time, which
is what ``layer.gqa_attention`` runs.
"""

import functools

import jax
import jax.numpy as jnp

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


def attention_mask(q_pos, k_pos, causal, lengths):
    """[B or 1, 1, Lq, Lk] bool, or None when every key may be seen."""
    mask = None
    if causal:
        mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
    if lengths is not None:
        valid = (k_pos[None, :] < lengths[:, None])[:, None, None, :]
        mask = valid if mask is None else mask & valid
    return mask


def blockwise_attention(q, k, v, scale, causal=True, lengths=None,
                        block=512):
    """Attention of q [B, T, H, D] over k, v [B, T, KV, D] (H a multiple
    of KV: each group of H // KV query heads shares one key-value head),
    scores scaled by ``scale``, without a [T, T] score matrix: queries go
    a block at a time, and for each the keys stream through the online
    softmax a block at a time, every step recomputed in backward. A
    causal query block stops at its own diagonal block. ``lengths`` [B]
    hides the keys of the padded tail."""
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
        jnp.moveaxis(a.reshape(b, n, block, h, d), 1, 0) for a in (k, v))
    offsets = jnp.arange(n) * block
    within = jnp.arange(block)

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def step(carry, q_blk, xs, q_start):
        k_blk, v_blk, k_start = xs
        mask = attention_mask(q_start + within, k_start + within, causal,
                              lengths)
        return online_softmax_step(carry, q_blk, k_blk, v_blk, scale, mask)

    out = []
    for i in range(n):
        q_blk = q[:, i * block:(i + 1) * block]
        seen = i + 1 if causal else n
        carry, _ = jax.lax.scan(
            lambda c, xs: (step(c, q_blk, xs, i * block), None),
            online_softmax_init(b, block, h, d, q.dtype),
            (k_blocks[:seen], v_blocks[:seen], offsets[:seen]))
        out.append(online_softmax_finish(carry, q.dtype))
    return jnp.concatenate(out, axis=1)[:, :t]
