"""Blockwise attention as Pallas TPU kernels that keep a tile's scores in
VMEM: the fused form of ``ops/attention.py blockwise_attention``.

Operands are laid out a head at a time, q [B, H, T, D], k [B, KV, T, D],
v [B, KV, T, Dv], T a multiple of the tile; query head h reads key-value
head h // (H / KV) by its block index (no key or value is repeated). The
query and key tiles are square, and the (query tile, key tile) pairs a
call visits are :func:`ops.attention.key_blocks`' list: a causal query
tile stops at its diagonal tile, and with ``window`` starts at the tile
that holds the oldest key its first query sees. The pairs go to the
kernels as scalar prefetch with ``lengths`` [B]; a key tile wholly past
``lengths[b]`` is skipped, and its index map repeats the step before's
block, so that no DMA is issued for it. Tiles on the diagonal, on the
window's edge or across the length are masked; the others are not.

* ``attention_fwd``: grid (B, H, pairs), the pairs of one query tile in a
  row. Float32 running max, normaliser and output live in VMEM scratch
  across the row; it writes the output in the operands' dtype and the
  float32 log-sum-exp [B, H, 1, T].
* ``attention_bwd_dq``: the same grid; each step makes the tile's
  probabilities again from the log-sum-exp and adds ``dS k`` into dq.
* ``attention_bwd_dkv``: grid (B, KV, steps), the steps of one key tile in
  a row: each query head of the group and each query tile that sees it.
  It works on transposed scores [key, query], so that the log-sum-exp and
  ``D = rowsum(dO * O)`` (float32, made once in XLA) broadcast as rows,
  and sums dk and dv over the group in VMEM.

Scores, exponentials and sums are float32; p is cast to v's dtype for the
second product, as ``online_softmax_step`` does, and dS to the operands'
for the backward products. What chooses this form over the plain one is
:func:`fits`, from the backend and the shapes alone.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core import mesh_scope
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.attention import NEG, key_blocks

_TILE = 512          # query and key positions a tile
_LANES = 128
_WIDEST = 256        # head widths a tile holds in VMEM
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))     # a [m, k] . b [n, k]^T


def fits(heads, kv_heads, d, dv):
    """Whether the fused form runs: Pallas can lower here (the TPU
    backend, or the tests' interpret flag), each key-value head serves a
    whole group of query heads, the key and value widths are multiples of
    64 up to 256, and the step is traced for one device: XLA cannot
    partition a Mosaic kernel."""
    scope = mesh_scope.current()
    return pk.enabled() and heads % kv_heads == 0 \
        and all(w % 64 == 0 and w <= _WIDEST for w in (d, dv)) \
        and (scope is None or scope[0].size == 1)


def tile(t):
    """Positions a tile: 512, or a short sequence rounded up to the lanes."""
    return min(_TILE, -(-t // _LANES) * _LANES)


def pairs(n, causal, window, size):
    """[(query tile, key tile)] over ``n`` tiles of ``size``, query tile
    by query tile: :func:`key_blocks`' list."""
    return [(i, j) for i, (first, end) in enumerate(
        key_blocks(n * size, size, causal, window)) for j in range(first, end)]


def _visits(t, causal, window, lengths):
    """[(query tile, key tile)] the kernels compute for each row of
    ``lengths`` (None: every key), at the tile ``t`` takes."""
    size = tile(t)
    n = -(-t // size)
    every = pairs(n, causal, window, size)
    return [[(i, j) for i, j in every if j * size < length]
            for length in (lengths if lengths is not None else [t])]


def attention_kernel_cost(b, t, h, kv, d, dv, causal=True, window=None,
                          lengths=None, itemsize=2):
    """{kernel name: {"flops", "bytes"}} of one attention call, forward
    and backward, over the tiles the kernels visit (``lengths``: the
    rows' lengths, None for whole rows): each visited tile's products,
    and each operand's tiles read as the kernels read them (a query
    tile once a row, a key and value tile once a visit, dk and dv once a
    key tile and query head), each result written once. The floor a
    roofline share reads against; the masks' and exponentials' vector
    work is not counted."""
    size = tile(t)
    n = -(-t // size)
    rows = b if lengths is None else 1
    visits = _visits(t, causal, window, lengths)
    tiles = rows * sum(len(v) for v in visits) * h
    key_tiles = rows * sum(len({j for _, j in v}) for v in visits) * h
    area = size * size
    seq = b * h * n * size         # query positions over the heads
    keys = b * kv * n * size
    return {
        "attention_fwd": {
            "flops": tiles * 2 * area * (d + dv),
            "bytes": itemsize * (seq * (d + dv) + tiles * size * (d + dv))
            + 4 * seq},
        "attention_bwd_dq": {
            "flops": tiles * 2 * area * (2 * d + dv),
            "bytes": itemsize * (seq * (2 * d + dv)
                                 + tiles * size * (d + dv)) + 8 * seq},
        "attention_bwd_dkv": {
            "flops": tiles * 2 * area * (2 * d + 2 * dv),
            "bytes": itemsize * (tiles * size * (d + dv)
                                 + key_tiles * size * (d + dv)
                                 + keys * (d + dv)) + tiles * size * 8},
    }


# ======================================================================
# the kernels' pieces
# ======================================================================

def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """a . b in float32; bfloat16 operands ask for the default precision
    themselves (Mosaic refuses "highest" for them: "Bad lhs type")."""
    precision = lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=_F32)


def _lanes(col, n):
    """A [rows, 128] lane-replicated column as [rows, n]."""
    if n % _LANES == 0:
        return jnp.tile(col, (1, n // _LANES))
    return col[:, :n]


def _keep(q0, k0, shape, length, causal, window, transposed=False):
    """[rows, cols] bool: which (query, key) a tile may see; queries from
    ``q0`` and keys from ``k0`` down the rows and along the lanes, or the
    other way round ``transposed``."""
    qa, ka = (1, 0) if transposed else (0, 1)
    q = lax.broadcasted_iota(jnp.int32, shape, qa) + q0
    k = lax.broadcasted_iota(jnp.int32, shape, ka) + k0
    keep = k < length
    if causal:
        keep &= k <= q
    if window is not None:
        keep &= q - k < window
    return keep


def _edge(q0, k0, size, length, causal, window):
    """Whether a tile needs its mask: it crosses the diagonal, the
    window's edge or the length."""
    edge = k0 + size > length
    if causal:
        edge |= k0 + size - 1 > q0
    if window is not None:
        edge |= q0 + size - 1 - k0 >= window
    return edge


def _visit(body, q0, k0, size, length, causal, window):
    """``body(masked)`` for a tile that holds a key under the length, the
    mask made only where the tile needs it."""
    edge = _edge(q0, k0, size, length, causal, window)

    @pl.when((k0 < length) & edge)
    def _():
        body(True)

    @pl.when((k0 < length) & jnp.logical_not(edge))
    def _():
        body(False)


def _starts(p, ids):
    return (p == 0) | (ids[p] != ids[jnp.maximum(p - 1, 0)])


def _ends(p, ids, steps):
    return (p == steps - 1) | (ids[p] != ids[jnp.minimum(p + 1, steps - 1)])


def _column(row):
    """A [1, n] row as a [n, 128] lane-replicated column."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))


def _row(col):
    """A [n, 128] lane-replicated column as a [1, n] row."""
    return jnp.transpose(col)[:1]


def _kv_tile(size, lens, b, j):
    """The key tile a step reads: its own, or past the length the last
    one that holds a key (already resident: no DMA)."""
    return jnp.minimum(j, jnp.maximum(lens[b] - 1, 0) // size)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=pk._VMEM_LIMIT)


def _cost(cost):
    return pl.CostEstimate(flops=cost["flops"], transcendentals=0,
                           bytes_accessed=cost["bytes"])


# ======================================================================
# the calls
# ======================================================================

@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "size", "interpret"))
def _forward(q, k, v, lens, scale, causal, window, size, interpret):
    """(out [B, H, T, Dv] in q's dtype, log-sum-exp [B, H, 1, T] float32)."""
    b, h, t, d = q.shape
    kv, dv = k.shape[1], v.shape[-1]
    groups = h // kv
    visited = np.asarray(pairs(t // size, causal, window, size), np.int32)
    steps = len(visited)

    def kernel(lens, qi, kj, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
               l_ref, acc_ref):
        bi, p = pl.program_id(0), pl.program_id(2)
        q0, k0 = qi[p] * size, kj[p] * size

        @pl.when(_starts(p, qi))
        def _():
            m_ref[...] = jnp.full(m_ref.shape, NEG, _F32)
            l_ref[...] = jnp.zeros(l_ref.shape, _F32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

        def fold(masked):
            s = _dot(q_ref[...], k_ref[...], _NT) * scale
            if masked:
                keep = _keep(q0, k0, s.shape, lens[bi], causal, window)
                s = jnp.where(keep, s, NEG)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            e = jnp.exp(s - _lanes(m_new, size))
            if masked:
                e = jnp.where(keep, e, 0.0)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(e, axis=1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * _lanes(alpha, dv) + _dot(
                e.astype(v_ref.dtype), v_ref[...])
            m_ref[...] = m_new

        _visit(fold, q0, k0, size, lens[bi], causal, window)

        @pl.when(_ends(p, qi, steps))
        def _():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[...] = (acc_ref[...] / _lanes(l, dv)).astype(o_ref.dtype)
            lse_ref[...] = _row(m_ref[...] + jnp.log(l))

    kv_map = lambda bi, hi, p, lens, qi, kj: (
        bi, hi // groups, _kv_tile(size, lens, bi, kj[p]), 0)
    q_map = lambda bi, hi, p, lens, qi, kj: (bi, hi, qi[p], 0)
    cost = attention_kernel_cost(b, t, h, kv, d, dv, causal, window, None,
                                 q.dtype.itemsize)["attention_fwd"]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h, steps),
            in_specs=[pl.BlockSpec((None, None, size, d), q_map),
                      pl.BlockSpec((None, None, size, d), kv_map),
                      pl.BlockSpec((None, None, size, dv), kv_map)],
            out_specs=[pl.BlockSpec((None, None, size, dv), q_map),
                       pl.BlockSpec((None, None, 1, size),
                                    lambda bi, hi, p, lens, qi, kj:
                                    (bi, hi, 0, qi[p]))],
            scratch_shapes=[pltpu.VMEM((size, _LANES), _F32),
                            pltpu.VMEM((size, _LANES), _F32),
                            pltpu.VMEM((size, dv), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, t), _F32)],
        compiler_params=_params(), cost_estimate=_cost(cost),
        interpret=interpret, name="attention_fwd",
    )(lens, jnp.asarray(visited[:, 0]), jnp.asarray(visited[:, 1]), q, k, v)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "size", "interpret"))
def _backward_dq(q, k, v, do, lse, di, lens, scale, causal, window, size,
                 interpret):
    """dq [B, H, T, D] in q's dtype."""
    b, h, t, d = q.shape
    kv, dv = k.shape[1], v.shape[-1]
    groups = h // kv
    visited = np.asarray(pairs(t // size, causal, window, size), np.int32)
    steps = len(visited)

    def kernel(lens, qi, kj, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, lse_col, di_col, acc_ref):
        bi, p = pl.program_id(0), pl.program_id(2)
        q0, k0 = qi[p] * size, kj[p] * size

        @pl.when(_starts(p, qi))
        def _():
            lse_col[...] = _column(lse_ref[...])
            di_col[...] = _column(di_ref[...])
            acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

        def add(masked):
            s = _dot(q_ref[...], k_ref[...], _NT) * scale
            e = jnp.exp(s - _lanes(lse_col[...], size))
            if masked:
                e = jnp.where(_keep(q0, k0, s.shape, lens[bi], causal,
                                    window), e, 0.0)
            dp = _dot(do_ref[...], v_ref[...], _NT)
            ds = e * (dp - _lanes(di_col[...], size))
            acc_ref[...] += _dot(ds.astype(k_ref.dtype), k_ref[...])

        _visit(add, q0, k0, size, lens[bi], causal, window)

        @pl.when(_ends(p, qi, steps))
        def _():
            dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)

    kv_map = lambda bi, hi, p, lens, qi, kj: (
        bi, hi // groups, _kv_tile(size, lens, bi, kj[p]), 0)
    q_map = lambda bi, hi, p, lens, qi, kj: (bi, hi, qi[p], 0)
    row_map = lambda bi, hi, p, lens, qi, kj: (bi, hi, 0, qi[p])
    cost = attention_kernel_cost(b, t, h, kv, d, dv, causal, window, None,
                                 q.dtype.itemsize)["attention_bwd_dq"]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h, steps),
            in_specs=[pl.BlockSpec((None, None, size, d), q_map),
                      pl.BlockSpec((None, None, size, d), kv_map),
                      pl.BlockSpec((None, None, size, dv), kv_map),
                      pl.BlockSpec((None, None, size, dv), q_map),
                      pl.BlockSpec((None, None, 1, size), row_map),
                      pl.BlockSpec((None, None, 1, size), row_map)],
            out_specs=pl.BlockSpec((None, None, size, d), q_map),
            scratch_shapes=[pltpu.VMEM((size, _LANES), _F32),
                            pltpu.VMEM((size, _LANES), _F32),
                            pltpu.VMEM((size, d), _F32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params(), cost_estimate=_cost(cost),
        interpret=interpret, name="attention_bwd_dq",
    )(lens, jnp.asarray(visited[:, 0]), jnp.asarray(visited[:, 1]), q, k, v,
      do, lse, di)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "size", "interpret"))
def _backward_dkv(q, k, v, do, lse, di, lens, scale, causal, window, size,
                  interpret):
    """(dk [B, KV, T, D], dv [B, KV, T, Dv]) in k's and v's dtypes, each
    summed over its group of query heads."""
    b, h, t, d = q.shape
    kv, dv = k.shape[1], v.shape[-1]
    groups = h // kv
    # key tile by key tile, each query head of the group, each query tile
    # that sees the key tile
    visited = np.asarray(sorted((j, g, i) for i, j in pairs(
        t // size, causal, window, size) for g in range(groups)), np.int32)
    steps = len(visited)

    def kernel(lens, kj, gi, qi, q_ref, k_ref, v_ref, do_ref, lse_ref,
               di_ref, dk_ref, dv_ref, dk_acc, dv_acc):
        bi, p = pl.program_id(0), pl.program_id(2)
        q0, k0 = qi[p] * size, kj[p] * size

        @pl.when(_starts(p, kj))
        def _():
            dk_acc[...] = jnp.zeros(dk_acc.shape, _F32)
            dv_acc[...] = jnp.zeros(dv_acc.shape, _F32)

        def add(masked):
            # [key, query]: the rows' statistics broadcast down the keys
            s = _dot(k_ref[...], q_ref[...], _NT) * scale
            e = jnp.exp(s - lse_ref[...])
            if masked:
                e = jnp.where(_keep(q0, k0, s.shape, lens[bi], causal,
                                    window, transposed=True), e, 0.0)
            dv_acc[...] += _dot(e.astype(do_ref.dtype), do_ref[...])
            ds = e * (_dot(v_ref[...], do_ref[...], _NT) - di_ref[...])
            dk_acc[...] += _dot(ds.astype(q_ref.dtype), q_ref[...])

        _visit(add, q0, k0, size, lens[bi], causal, window)

        @pl.when(_ends(p, kj, steps))
        def _():
            dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    kv_map = lambda bi, hi, p, lens, kj, gi, qi: (bi, hi, kj[p], 0)
    q_map = lambda bi, hi, p, lens, kj, gi, qi: (
        bi, hi * groups + gi[p], qi[p], 0)
    row_map = lambda bi, hi, p, lens, kj, gi, qi: (
        bi, hi * groups + gi[p], 0, qi[p])
    cost = attention_kernel_cost(b, t, h, kv, d, dv, causal, window, None,
                                 q.dtype.itemsize)["attention_bwd_dkv"]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, kv, steps),
            in_specs=[pl.BlockSpec((None, None, size, d), q_map),
                      pl.BlockSpec((None, None, size, d), kv_map),
                      pl.BlockSpec((None, None, size, dv), kv_map),
                      pl.BlockSpec((None, None, size, dv), q_map),
                      pl.BlockSpec((None, None, 1, size), row_map),
                      pl.BlockSpec((None, None, 1, size), row_map)],
            out_specs=[pl.BlockSpec((None, None, size, d), kv_map),
                       pl.BlockSpec((None, None, size, dv), kv_map)],
            scratch_shapes=[pltpu.VMEM((size, d), _F32),
                            pltpu.VMEM((size, dv), _F32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(), cost_estimate=_cost(cost),
        interpret=interpret, name="attention_bwd_dkv",
    )(lens, *(jnp.asarray(visited[:, c]) for c in range(3)), q, k, v, do,
      lse, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _attention(q, k, v, lens, scale, causal, window, size, interpret):
    return _forward(q, k, v, lens, scale, causal, window, size,
                    interpret)[0]


def _attention_fwd(q, k, v, lens, scale, causal, window, size, interpret):
    out, lse = _forward(q, k, v, lens, scale, causal, window, size,
                        interpret)
    return out, (q, k, v, lens, out, lse)


def _attention_bwd(scale, causal, window, size, interpret, res, do):
    q, k, v, lens, out, lse = res
    di = jnp.sum(do.astype(_F32) * out.astype(_F32), axis=-1)[:, :, None]
    args = (q, k, v, do, lse, di, lens, scale, causal, window, size,
            interpret)
    dk, dv = _backward_dkv(*args)
    return _backward_dq(*args), dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


def attention(q, k, v, scale, causal=True, lengths=None, window=None):
    """``blockwise_attention(q, k, v, scale, causal, lengths, window=)``
    through the kernels: q [B, T, H, D], k [B, T, KV, D], v [B, T, KV,
    Dv] in, [B, T, H, Dv] out. Positions are padded to a multiple of the
    tile; the padded keys are masked by the length."""
    b, t = q.shape[:2]
    size = tile(t)
    pad = -t % size
    heads_first = [jnp.transpose(jnp.pad(a, ((0, 0), (0, pad), (0, 0),
                                             (0, 0))), (0, 2, 1, 3))
                   for a in (q, k, v)]
    lens = jnp.full((b,), t, jnp.int32) if lengths is None \
        else jnp.minimum(lengths.astype(jnp.int32), t)
    out = _attention(*heads_first, lens, float(scale), causal, window, size,
                     pk._interpret())
    return jnp.transpose(out, (0, 2, 1, 3))[:, :t]
