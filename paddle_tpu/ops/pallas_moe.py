"""The expert layer's passes over its sorted rows as Pallas TPU kernels
that visit only the rows the held experts' groups fill: the fused form
of ``ops/moe.py``'s gather, ``experts`` and ``combine``.

The sorted buffer has ``k * N`` rows, the groups' ``sum(sizes)`` first.
The products take the groups by scalar prefetch, in the form
``jax.experimental.pallas.ops.tpu.megablox`` gives them (``offsets``
[held + 1], and for each step of the grid's last axis the group and the
row tile it works on), and their grid's last axis has as many steps as
the groups fill tiles of 256 rows: none where no pair is routed here. A
tile that two groups share is visited once by each, one after the other,
and each visit writes its own group's rows.

* ``moe_gather`` copies the rows of x behind the sorted rows, ``x[order //
  k]``, a DMA a row, over the tiles that hold the groups. A row of a [N,
  d] array cannot be a DMA's slice (the chip tiles rows by eight), so
  what a kernel reads or writes a row at a time is laid out [rows, d /
  128, 128], a tile a row, and the kernel lays it out again in VMEM.
* ``moe_gmm_in`` is the first product, ``h = xs W_in[e]`` [rows, 2 width]:
  the value ``MOE_PRODUCT`` names.
* ``moe_gmm_out`` is the second, ``y = (silu(a) b) W_out[e]`` with
  ``[a, b] = h``: the gate is made in float32 on the tile in VMEM and is
  never written; y is written a row a tile.
* ``moe_combine`` gives each token the weighted sum of its pairs' rows of
  y, a DMA a pair here: a tile of tokens visits only its pairs here.
* Backward, ``moe_combine_t`` gives each sorted row its token's gradient
  times the pair's weight, and the weight's gradient; ``moe_gmm_out_t``
  ``d h`` from ``d y W_out[e]^T`` with the gate's derivative on the tile;
  ``moe_gmm_in_t`` the rows' gradient ``d h W_in[e]^T``, a row a tile;
  ``moe_gather_t`` x's gradient, each token's sum over its pairs here;
  ``moe_tgmm_out`` and ``moe_tgmm_in`` the experts' weight gradients,
  summing each group's rows (an expert that no row reached gets zeros).

Operands are the rows' dtype (bfloat16 in the cells), products and sums
add in float32. Rows past ``sum(sizes)`` inside a visited tile are written
as zeros by the first visit; tiles past the groups are never written, so
what they hold is never read: the weight gradients select by group, and
the two sums over a token's pairs read only the pairs whose row lies in
the groups. No pair is dropped. What chooses this form over the plain one
is :func:`fits`, from the backend and the shapes alone. The kernel
callers but one are jitted, so that layers of one shape share one trace.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core import mesh_scope
from paddle_tpu.ops import pallas_kernels as pk

_LANES = 128
_ROW_TILE = 256        # rows a tile
_WEIGHT_BLOCK = 1 << 22   # d x width at most: an expert's half of W_in in VMEM
# k x N pairs at most: a list of 4 bytes a pair, prefetched whole, takes
# half of the 1 MiB scalar memory at top-8 of 16,384 positions (compiled
# for v5e); twice that cannot fit beside anything
_SMEM_PAIRS = 1 << 17
_F32 = jnp.float32
_TRANSPOSED_RHS = (((1,), (1,)), ((), ()))   # a [m, k] . b [n, k]
_TRANSPOSED_LHS = (((0,), (0,)), ((), ()))   # a [m, k]^T . b [m, n]


def fits(d, width, tokens, k):
    """Whether the fused form runs over ``tokens`` rows routed ``k`` times
    each: Pallas can lower here (the TPU backend, or the tests' interpret
    flag), the widths tile the lanes, the tokens (and so the sorted rows)
    tile, an expert's [d, width] block fits VMEM, the pairs' int32 lists
    that the gathers and pair sums prefetch whole fit the scalar memory,
    and the step is traced for one device: XLA cannot partition a Mosaic
    kernel."""
    scope = mesh_scope.current()
    return pk.enabled() and d % _LANES == 0 and width % _LANES == 0 \
        and tokens % _ROW_TILE == 0 and d * width <= _WEIGHT_BLOCK \
        and k * tokens <= _SMEM_PAIRS \
        and (scope is None or scope[0].size == 1)


def _block(d):
    """Columns of d a weight-gradient or row-gradient block takes."""
    return next(b for b in (1024, 512, 256, _LANES) if d % b == 0)


def moe_kernel_cost(rows, d, width, held, tokens, itemsize=2):
    """{kernel name: {"flops", "bytes"}} of one expert layer's calls with
    ``rows`` rows in the groups over ``tokens`` positions: each row, each
    position and each expert's matrices read or written once, the floor a
    roofline share reads against (a tile that two groups share and the
    blocks a kernel reads again are not counted)."""
    w_in, w_out = held * d * 2 * width, held * width * d
    first, second = 2 * rows * d * 2 * width, 2 * rows * width * d
    return {
        "moe_gather": {"flops": 0, "bytes": itemsize * 2 * rows * d},
        "moe_gmm_in": {"flops": first,
                       "bytes": itemsize * (rows * (d + 2 * width) + w_in)},
        "moe_gmm_out": {"flops": second, "bytes": itemsize * (
            rows * (2 * width + d) + w_out)},
        "moe_combine": {"flops": 2 * rows * d,
                        "bytes": itemsize * (rows + tokens) * d},
        "moe_combine_t": {"flops": 4 * rows * d,
                          "bytes": itemsize * 3 * rows * d},
        "moe_gmm_out_t": {"flops": second, "bytes": itemsize * (
            rows * (d + 2 * width + 2 * width) + w_out)},
        "moe_tgmm_out": {"flops": second, "bytes": itemsize * (
            rows * (2 * width + d) + w_out)},
        "moe_gmm_in_t": {"flops": first, "bytes": itemsize * (
            rows * (2 * width + d) + w_in)},
        "moe_gather_t": {"flops": rows * d,
                         "bytes": itemsize * (rows + tokens) * d},
        "moe_tgmm_in": {"flops": first, "bytes": itemsize * (
            rows * (d + 2 * width) + w_in)},
    }


@functools.partial(jax.jit, static_argnames=("rows", "visit_empty"))
def groups(sizes, rows, visit_empty):
    """((offsets [held + 1], group a step, tile a step), steps): the
    scalar-prefetch operands and the grid's last extent for a buffer of
    ``rows`` rows. A group takes the tiles from the one its first row lies
    in to the one its last row lies in, so a tile two groups share is
    visited by each, one after the other; ``visit_empty`` gives an empty
    group one step, for the weight gradients, which must write its zeros.
    (The layout of ``jax.experimental.pallas.ops.tpu.megablox``, made with
    a few operations: a process traces it before it can ask the compile
    cache for its step.)"""
    held = sizes.shape[0]
    last_tile = rows // _ROW_TILE - 1
    ends = jnp.cumsum(sizes)
    first = jnp.minimum((ends - sizes) // _ROW_TILE, last_tile)
    count = jnp.where(sizes == 0, int(visit_empty),
                      -(-ends // _ROW_TILE) - first)
    ends_at = jnp.cumsum(count)
    step = jnp.arange(last_tile + held)
    ids = jnp.minimum(jnp.sum(step[:, None] >= ends_at[None, :], axis=1),
                      held - 1)
    tiles = first[ids] + step - (ends_at - count)[ids]
    offsets = jnp.concatenate([jnp.zeros(1, ends.dtype), ends])
    return (offsets, ids.astype(jnp.int32), tiles.astype(jnp.int32)), \
        ends_at[-1]


# ======================================================================
# the kernels' pieces
# ======================================================================

def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """a . b in float32. bfloat16 operands have no higher precision to
    ask for, and Mosaic refuses the request ("highest" set process-wide:
    "Bad lhs type"), as XLA's own grouped product does."""
    precision = lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=_F32)


def _in_group(i, offsets, ids, tiles, shape):
    """[tm, cols] bool: the rows of step i's tile that are its group's."""
    g = ids[i]
    row = lax.broadcasted_iota(jnp.int32, shape, 0) + tiles[i] * shape[0]
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _store(ref, value, i, offsets, ids, tiles):
    """The group's rows of ``value`` into ``ref``; the tile's other rows
    as this tile's earlier visit left them, or zero at its first."""
    first = (i == 0) | (tiles[i] != tiles[jnp.maximum(i - 1, 0)])
    before = jnp.where(first, 0.0, ref[...].astype(_F32))
    ref[...] = jnp.where(_in_group(i, offsets, ids, tiles, value.shape),
                         value, before).astype(ref.dtype)


def _silu_parts(a_ref, b_ref):
    """(a, b, sigmoid(a)) of a tile of the first product, float32."""
    a = a_ref[...].astype(_F32)
    return a, b_ref[...].astype(_F32), jax.nn.sigmoid(a)


def _gate(a_ref, b_ref):
    a, b, s = _silu_parts(a_ref, b_ref)
    return (a * s * b).astype(a_ref.dtype)


def _add_group(i, offsets, ids, tiles, left, right, acc_ref):
    """acc += left^T right over the rows of step i's group (the others
    selected away, not multiplied: they may hold anything)."""
    def masked(v):
        keep = _in_group(i, offsets, ids, tiles, v.shape)
        return jnp.where(keep, v.astype(_F32), 0.0).astype(v.dtype)

    acc_ref[...] += _dot(masked(left), masked(right), _TRANSPOSED_LHS)


def _weight_grad(i, last, offsets, ids, tiles, left, right, out_ref,
                 acc_ref):
    """One step of a weight gradient: zero the sum where the group
    starts, add the tile's rows, write where the group ends."""
    g = ids[i]

    @pl.when((i == 0) | (g != ids[jnp.maximum(i - 1, 0)]))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        _add_group(i, offsets, ids, tiles, left(), right(), acc_ref)

    @pl.when((i == last) | (g != ids[jnp.minimum(i + 1, last)]))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _copy_rows(src_hbm, dst_ref, src_row, dst_row, first, last, sem):
    """DMAs row ``src_row(j)`` of ``src_hbm`` to row ``dst_row(j)`` of
    ``dst_ref`` for ``first <= j < last``, all in flight at once, and
    waits for them (rows of one size: each wait counts one)."""
    @pl.loop(first, last)
    def _(j):
        pltpu.make_async_copy(src_hbm.at[pl.ds(src_row(j), 1)],
                              dst_ref.at[pl.ds(dst_row(j), 1)], sem).start()

    @pl.loop(first, last)
    def _(j):
        pltpu.make_async_copy(src_hbm.at[pl.ds(0, 1)],
                              dst_ref.at[pl.ds(0, 1)], sem).wait()


def _call(kernel, meta, steps, grid, in_specs, out_specs, out_shape, name,
          cost, interpret, scratch=()):
    """A pallas_call, to be called on its operands, whose grid ends with
    the groups' steps and whose index maps and body take the groups'
    three arrays first."""
    (flops, nbytes) = cost
    return functools.partial(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(*grid, steps), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch)),
        out_shape=out_shape, compiler_params=pk._COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(flops=flops, transcendentals=0,
                                      bytes_accessed=nbytes),
        interpret=interpret, name=name), *meta)


def _rows(cols, col=0):
    """A [tm, cols] block of a row-major buffer at the step's tile."""
    return pl.BlockSpec((_ROW_TILE, cols),
                        lambda *a: (a[-1][a[-4]], col))


def _rows_at(cols, axis):
    """A [tm, cols] block at the step's tile and grid axis ``axis``'s
    column block."""
    return pl.BlockSpec((_ROW_TILE, cols),
                        lambda *a: (a[-1][a[-4]], a[axis]))


def _row_tiles(d):
    """A tile's rows of a [rows, d / 128, 128] buffer: a row a tile."""
    return pl.BlockSpec((_ROW_TILE, d // _LANES, _LANES),
                        lambda *a: (a[-1][a[-4]], 0, 0))


def _row_tiles_shape(rows, d, dtype):
    return jax.ShapeDtypeStruct((rows, d // _LANES, _LANES), dtype)


def _bytes(*arrays):
    return sum(a.size * a.dtype.itemsize for a in arrays)


# ======================================================================
# the calls (jitted: layers of one shape share one trace)
# ======================================================================

@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather(x, tokens, steps, interpret):
    """[rows, d]: x's row tokens[r] for the tiles holding the groups."""
    n, d = x.shape
    rows = tokens.shape[0]

    def kernel(tokens_ref, x_hbm, out_ref, rows_ref, sem):
        base = pl.program_id(0) * _ROW_TILE
        _copy_rows(x_hbm, rows_ref, lambda r: tokens_ref[base + r],
                   lambda r: r, 0, _ROW_TILE, sem)
        out_ref[...] = rows_ref[...].reshape(out_ref.shape)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((_ROW_TILE, d), lambda i, t: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((_ROW_TILE, d // _LANES, _LANES), x.dtype),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pk._COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=2 * _bytes(x)),
        interpret=interpret, name="moe_gather",
    )(tokens, x.reshape(n, d // _LANES, _LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_t(g, ys, tokens, scale, steps, interpret):
    """The combine's backward in sorted order: (d ys [rows, d] =
    scale[r] g[tokens[r]], the weights' gradient [rows, 1] float32 =
    ys[r] . g[tokens[r]]) for the tiles holding the groups."""
    n, d = g.shape
    rows = tokens.shape[0]

    def kernel(tokens_ref, g_hbm, ys_ref, scale_ref, dys_ref, dw_ref,
               rows_ref, sem):
        base = pl.program_id(0) * _ROW_TILE
        _copy_rows(g_hbm, rows_ref, lambda r: tokens_ref[base + r],
                   lambda r: r, 0, _ROW_TILE, sem)

        grad = rows_ref[...].reshape(dys_ref.shape).astype(_F32)
        dys_ref[...] = (grad * scale_ref[...]).astype(dys_ref.dtype)
        dw_ref[...] = jnp.sum(
            grad * ys_ref[...].reshape(dys_ref.shape).astype(_F32), axis=1,
            keepdims=True)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((_ROW_TILE, d // _LANES, _LANES),
                                   lambda i, t: (i, 0, 0)),
                      pl.BlockSpec((_ROW_TILE, 1), lambda i, t: (i, 0))],
            out_specs=[pl.BlockSpec((_ROW_TILE, d), lambda i, t: (i, 0)),
                       pl.BlockSpec((_ROW_TILE, 1), lambda i, t: (i, 0))],
            scratch_shapes=[
                pltpu.VMEM((_ROW_TILE, d // _LANES, _LANES), g.dtype),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((rows, d), ys.dtype),
                   jax.ShapeDtypeStruct((rows, 1), _F32)],
        compiler_params=pk._COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(flops=2 * rows * d, transcendentals=0,
                                      bytes_accessed=3 * rows * d * 2),
        interpret=interpret, name="moe_combine_t",
    )(tokens, g.reshape(n, d // _LANES, _LANES), ys, scale)


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def _pair_sum(ys, coef, place, total, name, interpret):
    """[N, d]: token n's sum over its k pairs of coef[n, c] ys[place[n *
    k + c]], over the pairs whose row lies before ``total``; the others'
    rows are never read. A tile of tokens visits only its pairs here: the
    pairs here, listed in token order (one sort of the k N pair keys),
    each packed as (row, pair within the tile), and where each tile's
    run of them starts. The list and the starts are prefetched whole
    into the scalar memory (1 MiB: 4 bytes a pair, 131,072 pairs at top-8
    of 16,384 positions), the tile's coefficients a block a step."""
    rows, blocks, lanes = ys.shape
    n, k = coef.shape
    d = blocks * lanes
    tile = _ROW_TILE * k     # pairs a tile of tokens
    assert rows * tile < 2 ** 31, "a packed (row, pair) overflows int32"
    here = place < total
    listed = jnp.argsort(~here, stable=True).astype(jnp.int32)
    packed = place[listed] * tile + listed % tile
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(
        jnp.sum(here.reshape(-1, tile), axis=1, dtype=jnp.int32))])

    def kernel(packed_ref, starts_ref, weights_ref, ys_hbm, out_ref,
               rows_ref, acc_ref, sem):
        i = pl.program_id(0)
        first, last = starts_ref[i], starts_ref[i + 1]
        _copy_rows(ys_hbm, rows_ref, lambda j: packed_ref[j] // tile,
                   lambda j: j - first, first, last, sem)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.loop(first, last)
        def _(j):
            pair = packed_ref[j] % tile
            acc_ref[pair // k] += rows_ref[j - first].astype(_F32) \
                * weights_ref[pair]

        out_ref[...] = acc_ref[...].astype(out_ref.dtype).reshape(
            out_ref.shape)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // _ROW_TILE,),
            in_specs=[pl.BlockSpec((tile,), lambda i, p, s: (i,),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((_ROW_TILE, d),
                                   lambda i, p, s: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((tile, blocks, lanes), ys.dtype),
                pltpu.VMEM((_ROW_TILE, blocks, lanes), _F32),
                pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((n, d), ys.dtype),
        compiler_params=pk._COMPILER_PARAMS,
        cost_estimate=pl.CostEstimate(flops=2 * rows * d, transcendentals=0,
                                      bytes_accessed=(rows + n) * d * 2),
        interpret=interpret, name=name,
    )(packed, starts, coef.reshape(-1).astype(_F32), ys)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gmm_in(xs, w_in, meta, steps, interpret):
    """h [rows, 2 width] = xs W_in[e], a half of the columns a pass."""
    rows, d = xs.shape
    width = w_in.shape[2] // 2

    def kernel(offsets, ids, tiles, xs_ref, w_ref, h_ref):
        _store(h_ref, _dot(xs_ref[...], w_ref[...]), pl.program_id(1),
               offsets, ids, tiles)

    return _call(
        kernel, meta, steps, (2,),
        [_rows(d), pl.BlockSpec((None, d, width),
                                lambda n, i, o, g, t: (g[i], 0, n))],
        _rows_at(width, 0),
        jax.ShapeDtypeStruct((rows, 2 * width), xs.dtype), "moe_gmm_in",
        (2 * rows * d * 2 * width, _bytes(xs, w_in) * 2), interpret,
    )(xs, w_in)


# not jitted: under a recomputed block a jitted call forwards the first
# product it reads as a second value the block keeps
def _gmm_out(h, w_out, meta, steps, interpret):
    """y [rows, d / 128, 128] = (silu(a) b) W_out[e], the gate made on the
    tile; written a row a tile, for the combine's DMAs."""
    rows = h.shape[0]
    width, d = w_out.shape[1:]

    def kernel(offsets, ids, tiles, a_ref, b_ref, w_ref, y_ref, acc_ref):
        _store(acc_ref, _dot(_gate(a_ref, b_ref), w_ref[...]),
               pl.program_id(0), offsets, ids, tiles)
        y_ref[...] = acc_ref[...].reshape(y_ref.shape)

    return _call(
        kernel, meta, steps, (),
        [_rows(width, 0), _rows(width, 1),
         pl.BlockSpec((None, width, d), lambda i, o, g, t: (g[i], 0, 0))],
        _row_tiles(d), _row_tiles_shape(rows, d, h.dtype), "moe_gmm_out",
        (2 * rows * width * d, _bytes(h, w_out) + rows * d * 2), interpret,
        scratch=[pltpu.VMEM((_ROW_TILE, d), h.dtype)],
    )(h, h, w_out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gmm_out_t(dy, w_out, h, meta, steps, interpret):
    """d h [rows, 2 width]: d z = d y W_out[e]^T through the gate,
    d a = d z b silu'(a) and d b = d z silu(a)."""
    rows, d = dy.shape
    width = w_out.shape[1]

    def kernel(offsets, ids, tiles, dy_ref, w_ref, a_ref, b_ref, dh_ref):
        i = pl.program_id(0)
        dz = _dot(dy_ref[...], w_ref[...], _TRANSPOSED_RHS)
        a, b, s = _silu_parts(a_ref, b_ref)
        _store(dh_ref.at[:, :width], dz * b * s * (1.0 + a * (1.0 - s)), i,
               offsets, ids, tiles)
        _store(dh_ref.at[:, width:], dz * a * s, i, offsets, ids, tiles)

    return _call(
        kernel, meta, steps, (),
        [_rows(d), pl.BlockSpec((None, width, d),
                                lambda i, o, g, t: (g[i], 0, 0)),
         _rows(width, 0), _rows(width, 1)],
        _rows(2 * width), jax.ShapeDtypeStruct((rows, 2 * width), h.dtype),
        "moe_gmm_out_t",
        (2 * rows * width * d, _bytes(dy, w_out) + 2 * _bytes(h)), interpret,
    )(dy, w_out, h, h)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gmm_in_t(dh, w_in, meta, steps, interpret):
    """d xs [rows, d / 128, 128] = d h W_in[e]^T, a block of d's columns
    a pass; written a row a tile, for the gather's backward."""
    rows, wide = dh.shape
    d = w_in.shape[1]
    block = _block(d)

    def kernel(offsets, ids, tiles, dh_ref, w_ref, dx_ref, acc_ref):
        _store(acc_ref, _dot(dh_ref[...], w_ref[...], _TRANSPOSED_RHS),
               pl.program_id(1), offsets, ids, tiles)
        dx_ref[...] = acc_ref[...].reshape(dx_ref.shape)

    return _call(
        kernel, meta, steps, (d // block,),
        [_rows(wide), pl.BlockSpec((None, block, wide),
                                   lambda n, i, o, g, t: (g[i], n, 0))],
        pl.BlockSpec((_ROW_TILE, block // _LANES, _LANES),
                     lambda n, i, o, g, t: (t[i], n, 0)),
        _row_tiles_shape(rows, d, dh.dtype), "moe_gmm_in_t",
        (2 * rows * wide * d, _bytes(dh, w_in) + rows * d * 2), interpret,
        scratch=[pltpu.VMEM((_ROW_TILE, block), dh.dtype)],
    )(dh, w_in)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _tgmm_out(h, dy, w_out, meta, steps, interpret):
    """d W_out [held, width, d]: each group's gate^T d y."""
    rows, d = dy.shape
    held, width = w_out.shape[:2]
    block = _block(d)

    def kernel(offsets, ids, tiles, a_ref, b_ref, dy_ref, out_ref, acc_ref):
        i = pl.program_id(1)
        _weight_grad(i, pl.num_programs(1) - 1, offsets, ids, tiles,
                     lambda: _gate(a_ref, b_ref), lambda: dy_ref[...],
                     out_ref, acc_ref)

    return _call(
        kernel, meta, steps, (d // block,),
        [_rows(width, 0), _rows(width, 1), _rows_at(block, 0)],
        pl.BlockSpec((None, width, block), lambda n, i, o, g, t: (g[i], 0, n)),
        jax.ShapeDtypeStruct((held, width, d), w_out.dtype), "moe_tgmm_out",
        (2 * rows * width * d, _bytes(h, dy, w_out)), interpret,
        scratch=[pltpu.VMEM((width, block), _F32)],
    )(h, h, dy)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _tgmm_in(xs, dh, w_in, meta, steps, interpret):
    """d W_in [held, d, 2 width]: each group's xs^T d h, a block of d's
    rows and a half of the columns a pass."""
    rows, d = xs.shape
    held, _, wide = w_in.shape
    width = wide // 2
    block = _block(d)

    def kernel(offsets, ids, tiles, xs_ref, dh_ref, out_ref, acc_ref):
        i = pl.program_id(2)
        _weight_grad(i, pl.num_programs(2) - 1, offsets, ids, tiles,
                     lambda: xs_ref[...], lambda: dh_ref[...], out_ref,
                     acc_ref)

    return _call(
        kernel, meta, steps, (2, d // block),
        [_rows_at(block, 1), _rows_at(width, 0)],
        pl.BlockSpec((None, block, width),
                     lambda n, k, i, o, g, t: (g[i], k, n)),
        jax.ShapeDtypeStruct((held, d, wide), w_in.dtype), "moe_tgmm_in",
        (2 * rows * d * wide, _bytes(xs, dh, w_in)), interpret,
        scratch=[pltpu.VMEM((block, width), _F32)],
    )(xs, dh)


# ======================================================================
# the three steps behind custom_vjps (interpreted or not is part of a
# jitted call's key: the tests flip it)
# ======================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _first(x, w_in, order, place, total, meta, steps, k):
    """h [rows, 2 width] = x[order // k] W_in[e]: the gather, then the
    first product."""
    return _first_fwd(x, w_in, order, place, total, meta, steps, k)[0]


def _first_fwd(x, w_in, order, place, total, meta, steps, k):
    interpret = pk._interpret()
    xs = _gather(x, order // k, pl.cdiv(total, _ROW_TILE),
                 interpret=interpret)
    h = _gmm_in(xs, w_in, meta[0], steps[0], interpret=interpret)
    return h, (xs, w_in, place, total, meta, steps)


def _first_bwd(k, residual, dh):
    """The weights' gradient from the gathered rows; the rows' gradient
    summed to their tokens over the pairs whose row lies in the groups."""
    xs, w_in, place, total, meta, steps = residual
    interpret = pk._interpret()
    dxs = _gmm_in_t(dh, w_in, meta[0], steps[0], interpret=interpret)
    here = (place < total).reshape(-1, k).astype(_F32)
    return (_pair_sum(dxs, here, place, total, name="moe_gather_t",
                      interpret=interpret),
            _tgmm_in(xs, dh, w_in, meta[1], steps[1], interpret=interpret),
            None, None, None, None, None)


_first.defvjp(_first_fwd, _first_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _second(h, w_out, weights, order, place, total, meta, steps, k):
    """[N, d]: the second product, then each token's weighted sum over
    its pairs here."""
    return _second_fwd(h, w_out, weights, order, place, total, meta, steps,
                       k)[0]


def _coefficients(weights, place, total):
    """weights [N, k], zero where the pair's row lies past the groups."""
    return jnp.where(place.reshape(weights.shape) < total, weights, 0.0)


def _second_fwd(h, w_out, weights, order, place, total, meta, steps, k):
    interpret = pk._interpret()
    ys = _gmm_out(h, w_out, meta[0], steps[0], interpret=interpret)
    out = _pair_sum(ys, _coefficients(weights, place, total), place, total,
                    name="moe_combine", interpret=interpret)
    return out, (h, w_out, ys, weights, order, place, total, meta, steps)


def _second_bwd(k, residual, g):
    """d ys in sorted order (each row its token's gradient times the
    pair's weight) and the weights' gradient, then the two products'."""
    h, w_out, ys, weights, order, place, total, meta, steps = residual
    interpret = pk._interpret()
    rows = order.shape[0]
    scale = jnp.where(jnp.arange(rows) < total,
                      weights.reshape(-1)[order], 0.0)[:, None]
    dys, dw = _gather_t(g, ys, order // k, scale.astype(_F32),
                        pl.cdiv(total, _ROW_TILE), interpret=interpret)
    dweights = _coefficients(dw[:, 0][place].reshape(weights.shape), place,
                             total)
    return (_gmm_out_t(dys, w_out, h, meta[0], steps[0], interpret=interpret),
            _tgmm_out(h, dys, w_out, meta[1], steps[1], interpret=interpret),
            dweights.astype(weights.dtype), None, None, None, None, None)


_second.defvjp(_second_fwd, _second_bwd)


def experts(x, order, place, sizes, weights, w_in, w_out, k, kept):
    """The fused form of ``ops/moe.py``'s gather, ``experts`` and
    ``combine``: ([N, d], each token's weighted sum over its pairs here;
    the rows the products' row tiles cover, int32: a tile that two groups
    share counts once for each). ``kept`` names the first product for a
    recomputed block's policy."""
    rows = order.shape[0]
    meta, steps = groups(sizes, rows=rows, visit_empty=False)
    meta_t, steps_t = groups(sizes, rows=rows, visit_empty=True)
    total = jnp.sum(sizes)
    h = kept(_first(x, w_in, order, place, total, (meta, meta_t),
                    (steps, steps_t), k))
    return _second(h, w_out, weights, order, place, total, (meta, meta_t),
                   (steps, steps_t), k), steps * _ROW_TILE
