"""Mamba-1's selective scan as two Pallas TPU kernels behind one
``jax.custom_vjp``: the fused form of ``ops/ssm.py selective_scan``.

The recurrence, a batch row at a time (dt_t, x_t [E], B_t, C_t [N],
A [N, E], the state S [N, E] with the channels along the lanes):

    S_t = exp(dt_t (x) A) * S_{t-1} + B_t (x) (dt_t x_t)      y_t = C_t . S_t

Both kernels run a grid (batch, token blocks, channel blocks), the batch
parallel, the other two in order. A grid step takes [N, E_blk] of the
state and steps its block of tokens through a ``fori_loop``, eight tokens
a trip (rows are loaded and stored as aligned tiles of eight); the states
of a row's other channel blocks wait in VMEM. Nothing of the shape
[T, E, N] is written to HBM in either direction.

* ``selective_scan_fwd`` reads dt and x, writes y (float32) and the last
  state, and when called for differentiation the state that enters each
  token block, [B, T / T_blk, N, E] float32.
* ``selective_scan_bwd`` takes the token blocks in reverse. For a block it
  steps forward again from its entering state, the per-token states held
  in VMEM, then walks the tokens backward with
  ``H_t = dy_t (x) C_t + a_{t+1} * H_{t+1}``, writing d dt and d x a token
  and adding into d A and, over the channel blocks, into d B and d C.

B_t[n] and C_t[n] multiply a whole row of channels, so a kernel needs them
spread along the lanes. They come as columns, [T * N, 1], one value a
sublane, and the first channel block of a token block spreads them once,
[T_blk * N, 128] in VMEM, for all the others. d B and d C are summed over
the channels the other way round: [N, 128] a token added up in VMEM over
the channel blocks, and one product with a matrix of ones at the last.

dt, the decays and the state are float32; ``D x``, the lengths' mask and
the padding to the token block stay with the caller in XLA. What chooses
this form over the plain one is :func:`fits`, from the backend and the
shapes alone. The kernels' bodies are kept short (whole [N, E_blk] values,
no loop over lane groups) and the two calls are jitted, so that layers of
one shape share one trace: a process traces and lowers its kernels before
it can ask the compile cache for its step, and the benchmark's ``setup_s``
pays for every equation (PERF.md, PR 34).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core import mesh_scope
from paddle_tpu.ops import pallas_kernels as pk

_LANES = 128
_TOKENS_A_TRIP = 8     # a float32 tile's sublanes: one aligned load a trip
_F32 = jnp.float32


def fits(channels, states):
    """Whether the fused form runs: Pallas can lower here (the TPU
    backend, or the tests' interpret flag), the state tiles (channels a
    multiple of the 128 lanes, states a multiple of the 8 sublanes), and
    the step is traced for one device: XLA cannot partition a Mosaic
    kernel, and the plain loops it can."""
    scope = mesh_scope.current()
    return pk.enabled() and channels % _LANES == 0 and states % 8 == 0 \
        and (scope is None or scope[0].size == 1)


def blocks(tokens, channels, states):
    """(token block, channel block) from the shapes: 128 tokens, or a
    whole short row rounded up to a trip; the widest of 512, 256 and 128
    channels whose states of a token block, which the backward holds in
    VMEM ([T_blk + 1, N, E_blk] float32), stay within
    ``pallas_kernels._VMEM_BUDGET``. On the chip at [2, 4096, 5120, 16]
    (PERF.md, PR 34) 512 and 1,024 channels were the fastest and 256
    tokens no faster than 128."""
    t_blk = _LANES if tokens > _LANES \
        else -(-tokens // _TOKENS_A_TRIP) * _TOKENS_A_TRIP
    e_blk = next(w for w in (512, 256, _LANES) if channels % w == 0 and (
        w == _LANES or 4 * (t_blk + 1) * states * w <= pk._VMEM_BUDGET))
    return t_blk, e_blk


def selective_scan_cost(batch, tokens, channels, states, dtype):
    """Operations and bytes that one forward and one backward call need,
    from shapes: ``{"fwd": {"flops", "transcendentals", "bytes"}, "bwd":
    ...}``. A state element and token costs the forward dt * A, its
    exponential, two multiply-adds for the state and one for y; the
    backward the forward's state again and eleven multiply-adds or
    multiplies for H, d C, d z, d A, d dt, d u, d B and the carry. Bytes
    are each operand and result once (x in ``dtype``, the rest float32),
    the entering states of the token blocks written by the forward and
    read by the backward. The kernels are bound by the vector unit, for
    which ``chipbench/peaks.json`` has no peak; against its two peaks
    their floor is the bytes."""
    elements = batch * tokens * channels * states
    rows = batch * tokens * channels
    narrow = jnp.dtype(dtype).itemsize
    t_blk = blocks(tokens, channels, states)[0]
    entering = 4 * batch * -(-tokens // t_blk) * states * channels
    small = 4 * (2 * batch * tokens * states + states * channels
                 + 2 * batch * states * channels)
    return {
        "fwd": {"flops": 7 * elements + rows, "transcendentals": elements,
                "bytes": rows * (4 + narrow + 4) + small + entering},
        "bwd": {"flops": (4 + 19) * elements + 4 * rows,
                "transcendentals": 2 * elements,
                "bytes": rows * (4 + narrow + 4 + 4 + narrow) + 2 * small
                + entering},
    }


def _cost(kind, batch, tokens, channels, states, dtype):
    c = selective_scan_cost(batch, tokens, channels, states, dtype)[kind]
    return pl.CostEstimate(flops=c["flops"],
                           transcendentals=c["transcendentals"],
                           bytes_accessed=c["bytes"])


_PARAMS = dataclasses.replace(
    pk._COMPILER_PARAMS,
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _spread(column_ref, dst_ref):
    """dst[t * N + n, :] = column[t * N + n, 0] along all 128 lanes."""
    dst_ref[...] = jnp.broadcast_to(column_ref[0], dst_ref.shape)


def _of_token(ref, t, n):
    """The [N, 128] of token ``t`` in a [T_blk * N, 128] scratch."""
    return ref.at[pl.ds(pl.multiple_of(t * n, 8), n), :]


# The bodies of the token loops are unrolled eight tokens a trip, and a
# process traces them before it can ask the compile cache for its step.
# Every ``jnp`` function and operator is a jitted function of its own to
# trace (about 1 ms each on the benchmark's host, 600 of them a step:
# PERF.md, PR 34), so these bodies speak ``lax`` and broadcast by hand.
_mul, _add, _exp = lax.mul, lax.add, lax.exp


def _along(spread_ref, t, n, e_blk):
    """Token ``t``'s [N, 128] of spread B or C, as wide as the state."""
    return lax.concatenate(
        [_of_token(spread_ref, t, n)[...]] * (e_blk // _LANES), 1)


def _row(tile, s, n):
    """Row ``s`` of ``tile`` [8, E_blk], over ``n`` sublanes."""
    return lax.broadcast_in_dim(lax.slice_in_dim(tile, s, s + 1, axis=0),
                                (n, tile.shape[1]), (0, 1))


def _row_into(tile, is_row, over_states):
    """``tile`` [8, E_blk] with the row that ``is_row`` marks =
    ``over_states`` [N, E_blk] summed over its states. A trip's eight
    rows are gathered so and stored as one tile: a row alone cannot be
    stored at a sublane the program only knows when it runs."""
    summed = lax.reduce(over_states, np.float32(0), lax.add, (0,))
    return lax.select(is_row, lax.broadcast_in_dim(summed, tile.shape, (1,)),
                      tile)


def _rows_of_a_trip(e_blk):
    """(a tile of zeros [8, E_blk], for each of its rows the mask that
    marks it)."""
    shape = (_TOKENS_A_TRIP, e_blk)
    sublane = lax.broadcasted_iota(jnp.int32, shape, 0)
    return lax.full(shape, 0, _F32), [
        lax.eq(sublane, lax.full(shape, s, jnp.int32))
        for s in range(_TOKENS_A_TRIP)]


def _trip(g):
    """The first token of trip ``g``, a multiple of 8 to the compiler."""
    return pl.multiple_of(g * _TOKENS_A_TRIP, _TOKENS_A_TRIP)


def _specs(t_blk, e_blk, n, token_block):
    """The block specs both calls share, by what they hold: rows of
    channels [T_blk, E_blk], B or C as a column, A, a state, the states
    entering the token blocks. ``token_block`` maps the grid's second
    index to a token block: the backward takes them in reverse."""
    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    return dict(
        rows=spec((1, t_blk, e_blk), lambda b, t, e: (b, token_block(t), e)),
        column=spec((1, t_blk * n, 1),
                    lambda b, t, e: (b, token_block(t), 0)),
        a=spec((n, e_blk), lambda b, t, e: (0, e)),
        state=spec((1, n, e_blk), lambda b, t, e: (b, 0, e)),
        entering=spec((1, 1, n, e_blk),
                      lambda b, t, e: (b, token_block(t), 0, e)))


# ======================================================================
# forward
# ======================================================================

def _fwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, s0_ref, y_ref,
                last_ref, *rest, keep):
    entering_ref = rest[0] if keep else None
    state_scr, bb, cb, u_scr = rest[-4:]
    tb, e = pl.program_id(1), pl.program_id(2)
    t_blk, e_blk = dt_ref.shape[1], dt_ref.shape[2]
    n = a_ref.shape[0]

    @pl.when(tb == 0)
    def _():
        state_scr[e] = s0_ref[0]

    @pl.when(e == 0)
    def _():
        _spread(b_ref, bb)
        _spread(c_ref, cb)

    if keep:
        entering_ref[0, 0] = state_scr[e]
    u_scr[...] = dt_ref[0] * x_ref[0].astype(_F32)
    a = a_ref[...]
    zeros, is_row = _rows_of_a_trip(e_blk)

    def trip(g, state):
        base = _trip(g)
        dt_g = dt_ref[0, pl.ds(base, _TOKENS_A_TRIP), :]
        u_g = u_scr[pl.ds(base, _TOKENS_A_TRIP), :]
        y = zeros
        for s in range(_TOKENS_A_TRIP):
            state = _add(
                _mul(_exp(_mul(_row(dt_g, s, n), a)), state),
                _mul(_along(bb, base + s, n, e_blk), _row(u_g, s, n)))
            y = _row_into(y, is_row[s],
                          _mul(state, _along(cb, base + s, n, e_blk)))
        y_ref[0, pl.ds(base, _TOKENS_A_TRIP), :] = y
        return state

    state_scr[e] = jax.lax.fori_loop(0, t_blk // _TOKENS_A_TRIP, trip,
                                     state_scr[e])
    last_ref[0] = state_scr[e]


@functools.partial(jax.jit, static_argnames=("keep", "interpret"))
def _forward(dt, x, a_t, b_col, c_col, s0, keep, interpret):
    batch, tokens, channels = dt.shape
    n = a_t.shape[0]
    t_blk, e_blk = blocks(tokens, channels, n)
    n_t, n_e = tokens // t_blk, channels // e_blk

    spec = _specs(t_blk, e_blk, n, lambda t: t)
    out_specs = [spec["rows"], spec["state"]]
    out_shape = [jax.ShapeDtypeStruct((batch, tokens, channels), _F32),
                 jax.ShapeDtypeStruct((batch, n, channels), _F32)]
    if keep:
        out_specs.append(spec["entering"])
        out_shape.append(jax.ShapeDtypeStruct(
            (batch, n_t, n, channels), _F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, keep=keep),
        grid=(batch, n_t, n_e),
        in_specs=[spec["rows"], spec["rows"], spec["column"],
                  spec["column"], spec["a"], spec["state"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n_e, n, e_blk), _F32),
                        pltpu.VMEM((t_blk * n, _LANES), _F32),
                        pltpu.VMEM((t_blk * n, _LANES), _F32),
                        pltpu.VMEM((t_blk, e_blk), _F32)],
        compiler_params=_PARAMS,
        cost_estimate=_cost("fwd", batch, tokens, channels, n, x.dtype),
        interpret=interpret, name="selective_scan_fwd",
    )(dt, x, b_col, c_col, a_t, s0)


# ======================================================================
# backward
# ======================================================================

def _bwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, entering_ref, dy_ref,
                dlast_ref, ddt_ref, dx_ref, db_ref, dc_ref, da_ref,
                ds0_ref, carry_scr, da_scr, states_scr, bb, cb, db_acc,
                dc_acc, u_scr, du_scr):
    tb, e = pl.program_id(1), pl.program_id(2)
    t_blk, e_blk = dt_ref.shape[1], dt_ref.shape[2]
    n = a_ref.shape[0]
    trips = t_blk // _TOKENS_A_TRIP

    @pl.when(tb == 0)       # the row's last tokens: the blocks come reversed
    def _():
        carry_scr[e] = dlast_ref[0]
        da_scr[e] = jnp.zeros((n, e_blk), _F32)

    @pl.when(e == 0)
    def _():
        _spread(b_ref, bb)
        _spread(c_ref, cb)
        db_acc[...] = jnp.zeros(db_acc.shape, _F32)
        dc_acc[...] = jnp.zeros(dc_acc.shape, _F32)

    x = x_ref[0].astype(_F32)
    u_scr[...] = dt_ref[0] * x
    a = a_ref[...]
    zeros, is_row = _rows_of_a_trip(e_blk)

    # forward again from the entering state: states_scr[t + 1] = S_t
    states_scr[0] = entering_ref[0, 0]

    def step(g, state):
        base = _trip(g)
        dt_g = dt_ref[0, pl.ds(base, _TOKENS_A_TRIP), :]
        u_g = u_scr[pl.ds(base, _TOKENS_A_TRIP), :]
        for s in range(_TOKENS_A_TRIP):
            state = _add(
                _mul(_exp(_mul(_row(dt_g, s, n), a)), state),
                _mul(_along(bb, base + s, n, e_blk), _row(u_g, s, n)))
            states_scr[base + s + 1] = state
        return state

    after = jax.lax.fori_loop(0, trips, step, states_scr[0])

    def over_lanes(v):      # [N, E_blk] -> [N, 128]: the lane groups added
        groups = [lax.slice_in_dim(v, j, j + _LANES, axis=1)
                  for j in range(0, e_blk, _LANES)]
        return functools.reduce(_add, groups)

    # and back: carry = a_{t+1} * H_{t+1}, what the later tokens hand S_t
    def back(i, carried):
        base = _trip(trips - 1 - i)
        dt_g = dt_ref[0, pl.ds(base, _TOKENS_A_TRIP), :]
        u_g = u_scr[pl.ds(base, _TOKENS_A_TRIP), :]
        dy_g = dy_ref[0, pl.ds(base, _TOKENS_A_TRIP), :]
        carry, s_t, da = carried
        dz_a = du = zeros
        for s in reversed(range(_TOKENS_A_TRIP)):
            t = base + s
            dt_row, dy_row = _row(dt_g, s, n), _row(dy_g, s, n)
            h = _add(carry, _mul(_along(cb, t, n, e_blk), dy_row))
            dc_t, db_t = _of_token(dc_acc, t, n), _of_token(db_acc, t, n)
            dc_t[...] = _add(dc_t[...], over_lanes(_mul(s_t, dy_row)))
            db_t[...] = _add(db_t[...],
                             over_lanes(_mul(h, _row(u_g, s, n))))
            s_t = states_scr[t]
            carry = _mul(_exp(_mul(dt_row, a)), h)
            dz = _mul(carry, s_t)
            da = _add(da, _mul(dz, dt_row))
            dz_a = _row_into(dz_a, is_row[s], _mul(dz, a))
            du = _row_into(du, is_row[s],
                           _mul(h, _along(bb, t, n, e_blk)))
        ddt_ref[0, pl.ds(base, _TOKENS_A_TRIP), :] = dz_a
        du_scr[pl.ds(base, _TOKENS_A_TRIP), :] = du
        return carry, s_t, da

    carry_scr[e], _, da_scr[e] = jax.lax.fori_loop(
        0, trips, back, (carry_scr[e], after, da_scr[e]))
    ds0_ref[0] = carry_scr[e]
    da_ref[0] = da_scr[e]
    du = du_scr[...]
    ddt_ref[0] = ddt_ref[0] + du * x
    dx_ref[0] = (du * dt_ref[0]).astype(dx_ref.dtype)

    @pl.when(e == pl.num_programs(2) - 1)
    def _():
        # over the lanes by the matrix unit: [8, 128] of ones against
        # [T_blk * N, 128] leaves the sums along the lanes, (t, n) flat
        ones = jnp.ones((8, _LANES), _F32)
        for acc, out in ((db_acc, db_ref), (dc_acc, dc_ref)):
            out[0] = jax.lax.dot_general(
                ones, acc[...], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=_F32)


@functools.partial(jax.jit, static_argnames="interpret")
def _backward(dt, x, a_t, b_col, c_col, entering, dy, dlast, interpret):
    batch, tokens, channels = dt.shape
    n = a_t.shape[0]
    t_blk, e_blk = blocks(tokens, channels, n)
    n_t, n_e = tokens // t_blk, channels // e_blk

    spec = _specs(t_blk, e_blk, n, lambda t: n_t - 1 - t)
    flat = pl.BlockSpec((1, 8, t_blk * n),
                        lambda b, t, e: (b, 0, n_t - 1 - t),
                        memory_space=pltpu.VMEM)
    ddt, dx, db, dc, da, ds0 = pl.pallas_call(
        _bwd_kernel,
        grid=(batch, n_t, n_e),
        in_specs=[spec["rows"], spec["rows"], spec["column"],
                  spec["column"], spec["a"], spec["entering"],
                  spec["rows"], spec["state"]],
        out_specs=[spec["rows"], spec["rows"], flat, flat, spec["state"],
                   spec["state"]],
        out_shape=[
            jax.ShapeDtypeStruct((batch, tokens, channels), _F32),
            jax.ShapeDtypeStruct((batch, tokens, channels), x.dtype),
            jax.ShapeDtypeStruct((batch, 8, tokens * n), _F32),
            jax.ShapeDtypeStruct((batch, 8, tokens * n), _F32),
            jax.ShapeDtypeStruct((batch, n, channels), _F32),
            jax.ShapeDtypeStruct((batch, n, channels), _F32)],
        scratch_shapes=[pltpu.VMEM((n_e, n, e_blk), _F32),
                        pltpu.VMEM((n_e, n, e_blk), _F32),
                        pltpu.VMEM((t_blk + 1, n, e_blk), _F32),
                        pltpu.VMEM((t_blk * n, _LANES), _F32),
                        pltpu.VMEM((t_blk * n, _LANES), _F32),
                        pltpu.VMEM((t_blk * n, _LANES), _F32),
                        pltpu.VMEM((t_blk * n, _LANES), _F32),
                        pltpu.VMEM((t_blk, e_blk), _F32),
                        pltpu.VMEM((t_blk, e_blk), _F32)],
        compiler_params=_PARAMS,
        cost_estimate=_cost("bwd", batch, tokens, channels, n, x.dtype),
        interpret=interpret, name="selective_scan_bwd",
    )(dt, x, b_col, c_col, a_t, entering, dy, dlast)

    # d B, d C: [B, 8, T * N], every row the same -> the columns they came as
    return ddt, dx, jnp.sum(da, axis=0), db[:, 0, :, None], \
        dc[:, 0, :, None], ds0


# ======================================================================
# the two behind one custom_vjp (interpreted or not is part of a jitted
# call's key: the tests flip it)
# ======================================================================

@jax.custom_vjp
def _scan(dt, x, a_t, b_col, c_col, s0):
    return tuple(_forward(dt, x, a_t, b_col, c_col, s0, keep=False,
                          interpret=pk._interpret()))


def _scan_fwd(dt, x, a_t, b_col, c_col, s0):
    y, last, entering = _forward(dt, x, a_t, b_col, c_col, s0, keep=True,
                                 interpret=pk._interpret())
    return (y, last), (dt, x, a_t, b_col, c_col, entering)


def _scan_bwd(kept, cotangents):
    return _backward(*kept, *cotangents, interpret=pk._interpret())


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, a, b_mat, c_mat, initial_state):
    """x [B, T, E], dt [B, T, E] float32 (0 where the state must stand
    still), a [E, N], b_mat and c_mat [B, T, N], initial_state [B, E, N]
    or None. Returns (y [B, T, E] float32, without ``D x``; the last state
    [B, E, N] float32). The caller has checked :func:`fits`."""
    batch, tokens, channels = x.shape
    n = a.shape[1]
    t_blk = blocks(tokens, channels, n)[0]
    pad = -tokens % t_blk

    def padded(v):      # dt 0 over the padding: the state stands still
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0)))

    def column(v):      # [B, T, N] -> [B, T * N, 1]: one value a sublane
        return padded(v.astype(_F32)).reshape(batch, -1, 1)

    s0 = jnp.zeros((batch, n, channels), _F32) if initial_state is None \
        else jnp.swapaxes(initial_state.astype(_F32), 1, 2)
    y, last = _scan(padded(dt.astype(_F32)), padded(x), a.astype(_F32).T,
                    column(b_mat), column(c_mat), s0)
    return y[:, :tokens], jnp.swapaxes(last, 1, 2)
