"""True sparse input rows for high-dimensional sparse slots.

The reference served million-dimension sparse FC inputs with dedicated
sparse-matrix storage and row-wise kernels (paddle/math/SparseRowMatrix.h:
29-299, CpuSparseMatrix + sparse momentum). The TPU-native equivalent
keeps a batch of sparse rows as PADDED ID LISTS — ids [B, K] (K = max
nonzeros in the batch, padded with -1) plus optional values — and computes
``sparse @ W`` as a row gather + weighted sum over K:

    out[b] = sum_k vals[b, k] * W[ids[b, k]]        (K*size reads)

instead of densifying to [B, dim] (dim*size reads + dim*4 bytes of host
traffic per row). Gradients flow through jnp.take as a scatter-add into
dW — with ``ParamAttr(sparse_update=True)`` the optimizer's sparse-row
machinery (optimizer.py _sparse_row_step) then updates only touched rows.

K is padded to the next power of two (min 8) so batches with different
nonzero counts reuse a handful of compiled programs.
"""

import numpy as np

import jax.numpy as jnp

from paddle_tpu.observe import spans as observe_spans
from paddle_tpu.utils.error import enforce


def _next_pow2(n, lo=8):
    k = lo
    while k < n:
        k *= 2
    return k


class SparseRows:
    """A batch of sparse feature rows: ids [B, K] int32 (-1 = padding),
    vals [B, K] float32 or None (binary), dim = full feature width."""

    __slots__ = ("ids", "vals", "dim")

    def __init__(self, ids, vals, dim):
        self.ids = ids
        self.vals = vals
        self.dim = int(dim)

    @property
    def size(self):
        return self.dim

    @classmethod
    def from_rows(cls, rows, dim, with_values):
        """rows: list of id-lists (binary) or (id, value)-pair lists."""
        ids_l, vals_l = [], []
        for row in rows:
            if with_values:
                ids_l.append([int(i) for i, _ in row])
                vals_l.append([float(v) for _, v in row])
            else:
                ids_l.append([int(i) for i in row])
        k = _next_pow2(max((len(r) for r in ids_l), default=1))
        b = len(ids_l)
        ids = np.full((b, k), -1, np.int32)
        vals = np.zeros((b, k), np.float32) if with_values else None
        for i, r in enumerate(ids_l):
            ids[i, :len(r)] = r
            if with_values:
                vals[i, :len(r)] = vals_l[i]
        with observe_spans.span("feed_place"):
            return cls(jnp.asarray(ids), None if vals is None
                       else jnp.asarray(vals), dim)

    def weights(self):
        """[B, K] float32 combination weights (mask * values)."""
        m = (self.ids >= 0).astype(jnp.float32)
        return m if self.vals is None else m * self.vals

    def matmul(self, w):
        """sparse_rows @ w for w [dim, size] — gather + weighted K-sum."""
        enforce(w.shape[0] == self.dim,
                "sparse matmul: weight rows %d != sparse dim %d",
                w.shape[0], self.dim)
        safe = jnp.maximum(self.ids, 0)
        rows = jnp.take(w, safe, axis=0)          # [B, K, size]
        if rows.dtype == jnp.int8:
            # quantized weight (serve/quantize.py): dequantize AFTER
            # the gather so only the [B, K, size] slice converts and
            # the HBM-resident table stays int8 — the caller applies
            # the per-output-channel scale to the result (it commutes
            # past the row K-sum)
            rows = rows.astype(jnp.float32)
        wts = self.weights().astype(rows.dtype)
        return jnp.sum(rows * wts[..., None], axis=1)

    def to_dense(self):
        """[B, dim] dense fallback for layers without a sparse fast path.
        Guarded: at reference scale (>=1M dims) densifying is the exact
        failure mode this type exists to avoid."""
        enforce(self.dim <= 262144,
                "refusing to densify a %d-dim sparse batch (use a layer "
                "with a sparse fast path — fc — or lower the dim)",
                self.dim)
        safe = jnp.maximum(self.ids, 0)
        out = jnp.zeros((self.ids.shape[0], self.dim), jnp.float32)
        return out.at[jnp.arange(self.ids.shape[0])[:, None], safe].add(
            self.weights())

    def tree_flatten(self):
        return ((self.ids, self.vals), self.dim)

    @classmethod
    def tree_unflatten(cls, dim, children):
        ids, vals = children
        return cls(ids, vals, dim)


from jax import tree_util  # noqa: E402

tree_util.register_pytree_node(
    SparseRows,
    lambda s: s.tree_flatten(),
    SparseRows.tree_unflatten,
)
