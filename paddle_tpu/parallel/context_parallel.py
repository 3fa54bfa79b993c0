"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The 2017 reference scales sequences by avoiding padding (Argument
sequenceStartPositions, SequenceToBatch repacking — SURVEY.md §5
"long-context"); it has no attention and no sequence-axis sharding. This
module is the TPU-native long-context story the new framework makes
first-class: shard the *sequence* axis of attention across a mesh axis and
exchange K/V blocks over ICI.

Two strategies, both running under ``shard_map`` so XLA emits the
collectives directly on ICI:

- ``ring_attention``: K/V blocks rotate around the mesh axis with
  ``lax.ppermute`` while each device streams them through a
  flash-attention-style online-softmax accumulator. Communication is
  neighbor-to-neighbor (ring over ICI), memory is O(L/N) per device —
  the standard ring-attention construction.
- ``ulysses_attention``: two ``lax.all_to_all`` reshuffles trade the
  sequence sharding for a head sharding, compute full attention locally
  on H/N heads, and shuffle back. Cheaper collectives for moderate L,
  requires heads % axis_size == 0.

Both are differentiable (JAX transposes ppermute/all_to_all in the VJP,
so the backward pass is also a ring / all-to-all program) and match
``full_attention`` on a single device to float tolerance.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from paddle_tpu.ops import attention as attention_ops
from paddle_tpu.utils.error import enforce

def full_attention(q, k, v, causal=False, scale=None, lengths=None):
    """Reference (unsharded) scaled-dot-product attention.

    q, k, v: [B, L, H, D]; returns [B, L, H, D]. ``lengths`` ([B] int32)
    masks out padded key positions. One step of the online softmax that
    the ring takes a hop at a time (``ops/attention.py``).
    """
    b, lq, h, d = q.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    mask = attention_ops.attention_mask(
        jnp.arange(lq), jnp.arange(k.shape[1]), causal, lengths)
    carry = attention_ops.online_softmax_init(b, lq, h, d, q.dtype)
    carry = attention_ops.online_softmax_step(carry, q, k, v, scale, mask)
    return attention_ops.online_softmax_finish(carry, q.dtype)


def _ring_shard(q, k, v, axis_name, axis_size, causal, scale):
    """Per-shard body of ring attention (runs under shard_map).

    q,k,v: local sequence chunks [B, Lc, H, D]. K/V blocks make a full
    tour of the ring; softmax is accumulated online so no device ever
    materializes the full [Lq, L] score matrix.
    """
    b, lc, h, d = q.shape
    idx = jax.lax.axis_index(axis_name)
    q_pos = idx * lc + jnp.arange(lc)
    carry = attention_ops.online_softmax_init(b, lc, h, d, q.dtype)
    k_blk, v_blk = k, v
    fwd = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    for step in range(axis_size):
        src = (idx - step) % axis_size               # owner of current block
        mask = attention_ops.attention_mask(
            q_pos, src * lc + jnp.arange(lc), causal, None)
        carry = attention_ops.online_softmax_step(carry, q, k_blk, v_blk,
                                                  scale, mask)
        if step < axis_size - 1:
            k_blk = jax.lax.ppermute(k_blk, axis_name, fwd)
            v_blk = jax.lax.ppermute(v_blk, axis_name, fwd)
    return attention_ops.online_softmax_finish(carry, q.dtype)


def ring_attention(q, k, v, mesh, seq_axis="seq", causal=False, scale=None,
                   batch_axis=None):
    """Ring attention over ``mesh``'s ``seq_axis``.

    Global views q,k,v: [B, L, H, D] with L sharded on ``seq_axis``.
    Returns [B, L, H, D] sharded the same way. L must divide evenly.
    ``batch_axis`` optionally names a mesh axis B is sharded on (dp compose).
    """
    enforce(isinstance(mesh, Mesh), "ring_attention needs a jax Mesh")
    axis_size = mesh.shape[seq_axis]
    enforce(q.shape[1] % axis_size == 0,
            "seq axis size %d must divide seq len %d", axis_size, q.shape[1])
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    spec = P(batch_axis, seq_axis, None, None)
    body = functools.partial(_ring_shard, axis_name=seq_axis,
                             axis_size=axis_size, causal=causal, scale=scale)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def _ulysses_shard(q, k, v, axis_name, axis_size, causal, scale):
    """Per-shard body of Ulysses attention: all-to-all seq<->heads."""

    def seq_to_heads(x):
        # [B, Lc, H, D] -> [B, L, H/N, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        # [B, L, H/N, D] -> [B, Lc, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = full_attention(qh, kh, vh, causal=causal, scale=scale)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, mesh, seq_axis="seq", causal=False, scale=None,
                      batch_axis=None):
    """Ulysses (all-to-all) sequence parallelism over ``mesh``'s ``seq_axis``.

    Same contract as :func:`ring_attention`; additionally requires
    ``num_heads % axis_size == 0``.
    """
    enforce(isinstance(mesh, Mesh), "ulysses_attention needs a jax Mesh")
    axis_size = mesh.shape[seq_axis]
    enforce(q.shape[1] % axis_size == 0,
            "seq axis size %d must divide seq len %d", axis_size, q.shape[1])
    enforce(q.shape[2] % axis_size == 0,
            "seq axis size %d must divide num heads %d", axis_size, q.shape[2])
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    spec = P(batch_axis, seq_axis, None, None)
    body = functools.partial(_ulysses_shard, axis_name=seq_axis,
                             axis_size=axis_size, causal=causal, scale=scale)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


class SequenceParallel:
    """Convenience wrapper: pick a strategy + mesh once, call like a fn.

    >>> sp = SequenceParallel(mesh, strategy="ring")
    >>> out = sp(q, k, v, causal=True)
    """

    def __init__(self, mesh, seq_axis="seq", strategy="ring", batch_axis=None):
        enforce(strategy in ("ring", "ulysses"),
                "unknown sequence-parallel strategy %r", strategy)
        self.mesh = mesh
        self.seq_axis = seq_axis
        self.strategy = strategy
        self.batch_axis = batch_axis

    def __call__(self, q, k, v, causal=False, scale=None):
        fn = ring_attention if self.strategy == "ring" else ulysses_attention
        return fn(q, k, v, self.mesh, seq_axis=self.seq_axis, causal=causal,
                  scale=scale, batch_axis=self.batch_axis)

    def shard_sequence(self, x):
        """Place a [B, L, ...] host array with L sharded on the seq axis
        (and B on ``batch_axis`` when configured, matching __call__'s
        in_specs so no resharding happens on the hot path)."""
        spec = P(*([self.batch_axis, self.seq_axis]
                   + [None] * (x.ndim - 2)))
        return jax.device_put(x, NamedSharding(self.mesh, spec))
