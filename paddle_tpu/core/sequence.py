"""Variable-length (and nested) sequence batches, XLA-friendly.

Equivalent of the reference's sequence metadata: Argument.sequenceStartPositions
and subSequenceStartPositions (reference: paddle/parameter/Argument.h:84-90) and
the SequenceToBatch repacking machinery (gserver/layers/SequenceToBatch.cpp,
cuda hl_sequence.h). The reference stores ragged data contiguously with start
positions — pointer-chasing that is hostile to XLA's static shapes. Here the
canonical device format is *padded-with-lengths*:

  * ``SequenceBatch``: data [B, T, ...] + lengths [B]; a boolean mask and
    flat segment-ids are derived on demand. All sequence layers consume this.
  * ``NestedSequenceBatch``: data [B, S, T, ...] + outer lengths [B] + inner
    lengths [B, S] — two-level nesting parity (sub-sequences).

Host-side converters translate the reference's flat+start-positions layout to
and from the padded form, so data providers written against the reference's
semantics keep working. Both classes are registered jax pytrees, so they flow
through jit/grad/scan/pjit transparently; lengths are data (traced), shapes
are static — bucketing (``bucket_length``) keeps recompilation bounded.
"""

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.observe import spans as observe_spans
from paddle_tpu.utils.error import enforce


def bucket_length(n, buckets=(16, 32, 64, 128, 256, 512, 1024, 2048, 4096)):
    """Round a max-length up to a bucket so jit sees few distinct shapes."""
    for b in buckets:
        if n <= b:
            return b
    return int(n)


class SequenceBatch:
    """A batch of variable-length sequences: padded data + per-sequence lengths."""

    def __init__(self, data, lengths):
        self.data = data
        self.lengths = lengths

    # -- structural info ----------------------------------------------------
    @property
    def batch_size(self):
        return self.data.shape[0]

    @property
    def max_len(self):
        return self.data.shape[1]

    def mask(self, dtype=None):
        """[B, T] validity mask."""
        t = jnp.arange(self.max_len)[None, :]
        m = t < self.lengths[:, None]
        return m if dtype is None else m.astype(dtype)

    def segment_ids(self):
        """Flat [B*T] segment ids; padding gets id -1 (XLA-friendly replacement
        for sequenceStartPositions)."""
        ids = jnp.arange(self.batch_size)[:, None] * jnp.ones(
            (1, self.max_len), dtype=jnp.int32
        )
        return jnp.where(self.mask(), ids.astype(jnp.int32), -1).reshape(-1)

    # -- conversions (host side) -------------------------------------------
    @staticmethod
    def from_sequences(seqs, max_len=None, dtype=None, pad_value=0):
        """Build from a list of per-sequence numpy arrays (ragged)."""
        enforce(len(seqs) > 0, "empty sequence batch")
        seqs = [np.asarray(s) for s in seqs]
        lengths = np.array([len(s) for s in seqs], dtype=np.int32)
        tmax = max_len or bucket_length(int(lengths.max()))
        feat_shape = seqs[0].shape[1:]
        out_dtype = dtype or seqs[0].dtype
        data = np.full((len(seqs), tmax) + feat_shape, pad_value, dtype=out_dtype)
        for i, s in enumerate(seqs):
            enforce(len(s) <= tmax, "sequence %d longer than max_len %d", i, tmax)
            data[i, : len(s)] = s
        with observe_spans.span("feed_place"):
            return SequenceBatch(jnp.asarray(data), jnp.asarray(lengths))

    @staticmethod
    def from_flat(flat, start_positions, max_len=None):
        """From the reference layout: contiguous [sum(T_i), ...] rows plus
        start positions [N+1] (cf. Argument.sequenceStartPositions)."""
        flat = np.asarray(flat)
        pos = np.asarray(start_positions, dtype=np.int64)
        seqs = [flat[pos[i]: pos[i + 1]] for i in range(len(pos) - 1)]
        return SequenceBatch.from_sequences(seqs, max_len=max_len)

    def to_flat(self):
        """Back to (flat rows, start_positions) on host."""
        data = np.asarray(self.data)
        lengths = np.asarray(self.lengths)
        rows = [data[i, : lengths[i]] for i in range(len(lengths))]
        pos = np.zeros(len(lengths) + 1, dtype=np.int64)
        np.cumsum(lengths, out=pos[1:])
        return np.concatenate(rows, axis=0) if rows else data[:0, 0], pos

    def to_sequences(self):
        data = np.asarray(self.data)
        lengths = np.asarray(self.lengths)
        return [data[i, : lengths[i]] for i in range(len(lengths))]

    # -- functional helpers -------------------------------------------------
    def map_data(self, fn):
        return SequenceBatch(fn(self.data), self.lengths)

    def masked_data(self, pad_value=0.0):
        m = self.mask()
        shape = m.shape + (1,) * (self.data.ndim - 2)
        return jnp.where(m.reshape(shape), self.data, pad_value)

    def last_step(self):
        """Gather the last valid timestep of each sequence
        (cf. SequenceLastInstanceLayer)."""
        idx = jnp.maximum(self.lengths - 1, 0)
        return jnp.take_along_axis(
            self.data, idx.reshape(-1, 1, *(1,) * (self.data.ndim - 2)), axis=1
        ).squeeze(1)

    def first_step(self):
        return self.data[:, 0]

    def reverse(self):
        """Reverse each sequence in place of its valid region (for bi-RNNs)."""
        t = jnp.arange(self.max_len)[None, :]
        idx = jnp.where(t < self.lengths[:, None], self.lengths[:, None] - 1 - t, t)
        data = jnp.take_along_axis(
            self.data, idx.reshape(idx.shape + (1,) * (self.data.ndim - 2)), axis=1
        )
        return SequenceBatch(data, self.lengths)

    def __repr__(self):
        return "SequenceBatch(data=%s%s, lengths=%s)" % (
            getattr(self.data, "dtype", "?"),
            tuple(self.data.shape),
            tuple(self.lengths.shape),
        )


class PackedSequenceBatch(SequenceBatch):
    """A SequenceBatch whose rows each hold SEVERAL concatenated source
    sequences (sequence packing): ``data`` [B, T, ...], ``lengths`` [B]
    (TOTAL valid length of each packed row) plus ``segments`` [B, T] —
    the per-row ordinal of the source sequence occupying each position
    (0, 1, 2, ... within the row; -1 in padding).

    Packing is the data-side half of the bargain; the model side is the
    segment-RESET mask: recurrent scans must re-zero their carry at every
    segment start so state never leaks across packed neighbours
    (ops/rnn.py ``reset_bt``), and per-position costs mask on the packed
    ``lengths`` exactly as they do for plain batches. With both in place
    a packed batch computes bit-for-bit the same per-position outputs,
    costs and gradients as the unpacked baseline
    (tests/test_data_pipeline.py gradient-match). Built by
    ``paddle_tpu.data.bucketing.pack_feed``.
    """

    def __init__(self, data, lengths, segments):
        super().__init__(data, lengths)
        self.segments = segments

    def map_data(self, fn):
        return PackedSequenceBatch(fn(self.data), self.lengths,
                                   self.segments)

    def reset_mask(self, dtype=None):
        """[B, T] mask, 1 at every packed-segment start (the positions
        where a recurrent carry must reset to its initial state)."""
        seg = self.segments
        prev = jnp.concatenate(
            [jnp.full_like(seg[:, :1], -2), seg[:, :-1]], axis=1)
        m = (seg >= 0) & (seg != prev)
        return m if dtype is None else m.astype(dtype)

    def segment_count(self):
        """Total number of real (unpacked) sequences in the batch."""
        return jnp.sum(jnp.max(self.segments, axis=1) + 1)

    def reverse(self):
        """Reverse each PACKED SEGMENT in place (not the whole row) —
        the packed equivalent of SequenceBatch.reverse, used by
        reverse-direction recurrent layers. Segment spans are unchanged,
        so ``segments`` (and the reset mask) are preserved."""
        t_max = self.max_len
        t = jnp.arange(t_max)

        def row_index(seg_row):
            # padding gets its own segment id (t_max) so it can never
            # collide with a real segment ordinal (< t_max)
            sid = jnp.where(seg_row >= 0, seg_row, t_max)
            first = jax.ops.segment_min(t, sid, num_segments=t_max + 1)
            last = jax.ops.segment_max(t, sid, num_segments=t_max + 1)
            return jnp.where(seg_row >= 0, first[sid] + last[sid] - t, t)

        idx = jax.vmap(row_index)(self.segments)
        data = jnp.take_along_axis(
            self.data, idx.reshape(idx.shape + (1,) * (self.data.ndim - 2)),
            axis=1)
        return PackedSequenceBatch(data, self.lengths, self.segments)

    def __repr__(self):
        return "PackedSequenceBatch(data=%s%s, lengths=%s, segments=%s)" % (
            getattr(self.data, "dtype", "?"),
            tuple(self.data.shape),
            tuple(self.lengths.shape),
            tuple(self.segments.shape),
        )


class NestedSequenceBatch:
    """Two-level nested sequences: [B, S, T, ...] + outer [B] + inner [B, S].

    Parity with subSequenceStartPositions (Argument.h:88-90): a batch of
    sequences of sub-sequences, e.g. paragraphs of sentences of tokens.
    """

    def __init__(self, data, outer_lengths, inner_lengths):
        self.data = data
        self.outer_lengths = outer_lengths
        self.inner_lengths = inner_lengths

    @property
    def batch_size(self):
        return self.data.shape[0]

    @property
    def max_subseqs(self):
        return self.data.shape[1]

    @property
    def max_len(self):
        return self.data.shape[2]

    def outer_mask(self, dtype=None):
        s = jnp.arange(self.max_subseqs)[None, :]
        m = s < self.outer_lengths[:, None]
        return m if dtype is None else m.astype(dtype)

    def inner_mask(self, dtype=None):
        t = jnp.arange(self.max_len)[None, None, :]
        m = (t < self.inner_lengths[:, :, None]) & self.outer_mask()[:, :, None]
        return m if dtype is None else m.astype(dtype)

    @staticmethod
    def from_nested(nested, max_subseqs=None, max_len=None, dtype=None, pad_value=0):
        """From a list (batch) of lists (sub-sequences) of arrays (steps)."""
        enforce(len(nested) > 0, "empty nested batch")
        outer = np.array([len(subs) for subs in nested], dtype=np.int32)
        smax = max_subseqs or int(outer.max())
        all_lens = [len(s) for subs in nested for s in subs]
        tmax = max_len or bucket_length(max(all_lens))
        first = np.asarray(nested[0][0])
        out_dtype = dtype or first.dtype
        data = np.full(
            (len(nested), smax, tmax) + first.shape[1:], pad_value, dtype=out_dtype
        )
        inner = np.zeros((len(nested), smax), dtype=np.int32)
        for i, subs in enumerate(nested):
            for j, s in enumerate(subs):
                s = np.asarray(s)
                data[i, j, : len(s)] = s
                inner[i, j] = len(s)
        with observe_spans.span("feed_place"):
            return NestedSequenceBatch(
                jnp.asarray(data), jnp.asarray(outer), jnp.asarray(inner)
            )

    def flatten_to_subsequences(self):
        """Collapse to a SequenceBatch over all sub-sequences [B*S, T, ...]
        (cf. the inner-level view RecurrentGradientMachine uses for nested
        recurrent groups)."""
        b, s = self.batch_size, self.max_subseqs
        data = self.data.reshape((b * s,) + self.data.shape[2:])
        lengths = jnp.where(
            self.outer_mask().reshape(-1), self.inner_lengths.reshape(-1), 0
        )
        return SequenceBatch(data, lengths)

    def outer_sequence_of(self, per_subseq):
        """Wrap per-sub-sequence features [B*S, ...] back into an outer
        SequenceBatch [B, S, ...]."""
        b, s = self.batch_size, self.max_subseqs
        data = per_subseq.reshape((b, s) + per_subseq.shape[1:])
        return SequenceBatch(data, self.outer_lengths)

    def __repr__(self):
        return "NestedSequenceBatch(data=%s, outer=%s, inner=%s)" % (
            tuple(self.data.shape),
            tuple(self.outer_lengths.shape),
            tuple(self.inner_lengths.shape),
        )


jax.tree_util.register_pytree_node(
    SequenceBatch,
    lambda s: ((s.data, s.lengths), None),
    lambda _, children: SequenceBatch(*children),
)
jax.tree_util.register_pytree_node(
    PackedSequenceBatch,
    lambda s: ((s.data, s.lengths, s.segments), None),
    lambda _, children: PackedSequenceBatch(*children),
)
jax.tree_util.register_pytree_node(
    NestedSequenceBatch,
    lambda s: ((s.data, s.outer_lengths, s.inner_lengths), None),
    lambda _, children: NestedSequenceBatch(*children),
)
