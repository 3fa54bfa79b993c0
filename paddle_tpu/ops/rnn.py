"""Recurrent kernels: LSTM/GRU/vanilla-RNN steps and masked scan runners.

Replaces the reference's fused recurrent CUDA kernels (paddle/cuda/
hl_cuda_lstm.cu ~700 LoC, hl_gpu_gru.cuh, LstmCompute.cu/GruCompute.cu) and
the SequenceToBatch batch-major repacking (gserver/layers/SequenceToBatch.cpp).
TPU-native shape: the input-to-hidden projection for ALL timesteps is one big
[B*T, D] x [D, 4H] matmul (MXU-friendly), then a lax.scan carries only the
small recurrent h/c state with the [H, 4H] recurrent matmul per step; masking
freezes state past each sequence's end — exactly the effect the reference got
from sorting sequences by length and shrinking the active batch.

Gate layout here is [input, forget, cell(candidate), output] on the last
axis. (The reference's native buffer order is [candidate, input, forget,
output] — hl_cpu_lstm.cuh:42-45; checkpoint interop performs exactly that
gate-block column remap on import/export: paddle_tpu/interop.py
_REF_TO_TPU / _TPU_TO_REF, golden-tested in tests/test_interop.py.)
"""

from functools import cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import mesh_scope
from paddle_tpu.core.dtype import matmul_precision
from paddle_tpu.utils.logger import logger


def _mm(a, b):
    return jnp.matmul(a, b, precision=matmul_precision())


def lstm_step(carry, gates_t, w_rec, mask_t, gate_act, state_act,
              use_peephole=False, w_peep=None, out_act=None):
    """One LSTM step. carry=(h, c); gates_t [B, 4H] is the precomputed
    input projection (+bias); w_rec [H, 4H]. Matches the reference's
    hl_lstm gate math (hl_cuda_lstm.cu): i,f = sigmoid, candidate g via
    ``state_act``, cell output via ``out_act`` (both tanh by default)."""
    h_prev, c_prev = carry
    hidden = gates_t.shape[-1] // 4
    z = gates_t + _mm(h_prev, w_rec)
    zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
    if use_peephole:
        pi, pf, po = jnp.split(w_peep, 3, axis=-1)
        zi = zi + c_prev * pi
        zf = zf + c_prev * pf
    i = gate_act(zi)
    f = gate_act(zf)
    g = state_act(zg)
    c = f * c_prev + i * g
    if use_peephole:
        zo = zo + c * po
    o = gate_act(zo)
    h = o * (out_act or state_act)(c)
    m = mask_t[:, None]
    # f32 peephole checks promote the elementwise chain; the carry keeps
    # the compute dtype (the fused kernel equally stores state in dt)
    h = jnp.where(m, h, h_prev).astype(h_prev.dtype)
    c = jnp.where(m, c, c_prev).astype(c_prev.dtype)
    return (h, c), h


def gru_step(carry, inp_t, w_rec_rz, w_rec_c, mask_t, gate_act, state_act):
    """One GRU step, reference gate order (hl_gpu_gru.cuh): update z,
    reset r, candidate c. inp_t [B, 3H] precomputed input projection."""
    h_prev = carry
    xu, xr, xc = jnp.split(inp_t, 3, axis=-1)
    rz = _mm(h_prev, w_rec_rz)
    zu_r, zr_r = jnp.split(rz, 2, axis=-1)
    u = gate_act(xu + zu_r)
    r = gate_act(xr + zr_r)
    c = state_act(xc + _mm(r * h_prev, w_rec_c))
    h = u * h_prev + (1.0 - u) * c
    m = mask_t[:, None]
    h = jnp.where(m, h, h_prev)
    return h, h


def rnn_step(carry, inp_t, w_rec, mask_t, act):
    h_prev = carry
    h = act(inp_t + _mm(h_prev, w_rec))
    m = mask_t[:, None]
    h = jnp.where(m, h, h_prev)
    return h, h


def _scan_time_major(step_fn, init_carry, inputs_tm, mask_tm, reverse=False):
    def body(carry, xs):
        inp_t, m_t = xs
        return step_fn(carry, inp_t, m_t)

    carry, ys = lax.scan(body, init_carry, (inputs_tm, mask_tm), reverse=reverse)
    return carry, ys


def _check_reset(reset_bt, reverse):
    """Packed-sequence reset masks compose with reverse only when the
    caller pre-reverses per segment (PackedSequenceBatch.reverse) — the
    scans' internal whole-row reverse would mix packed neighbours."""
    if reset_bt is not None and reverse:
        raise ValueError(
            "reset_bt (packed sequences) cannot combine with reverse=True "
            "inside the scan; pre-reverse per segment with "
            "PackedSequenceBatch.reverse() and scan forward")


def _per_device(fused, batch, arg_bdims, out_bdims):
    """How a fused Pallas scan runs over a batch of ``batch`` rows:
    ``(fn, rows)`` — the callable and the rows one kernel instance sees —
    or ``(None, None)`` when it cannot run fused at all.

    XLA cannot partition a Mosaic kernel, so under a multi-device mesh
    (core.mesh_scope.use_mesh) the kernel is shard_mapped over the batch
    axis the scope names and each device scans its own rows; ``*_bdims``
    give the batch dimension of every argument and output (None =
    replicated). With no batch axis named the scan path runs, which XLA
    partitions itself."""
    scope = mesh_scope.current()
    if scope is None or scope[0].size == 1:
        return fused, batch
    mesh, axis = scope
    if axis is None:
        _log_scan_once(mesh)
        return None, None
    shards = mesh.shape[axis]
    if batch % shards:  # DataParallel.shard_batch replicates such a batch
        return None, None

    def spec(bdim):
        return P() if bdim is None else P(*([None] * bdim + [axis]))

    return (jax.shard_map(fused, mesh=mesh,
                          in_specs=tuple(spec(d) for d in arg_bdims),
                          out_specs=tuple(spec(d) for d in out_bdims),
                          check_vma=False),
            batch // shards)


@cache
def _log_scan_once(mesh):
    logger.warning(
        "recurrent layers run as lax.scan under mesh %s: a fused kernel "
        "cannot be partitioned by XLA; name the batch axis "
        "(use_mesh(mesh, batch_axis=...)) to run it per device",
        dict(mesh.shape))


def lstm_scan(x_btd, mask_bt, w_in, b, w_rec, h0=None, c0=None,
              gate_act=jax.nn.sigmoid, state_act=jnp.tanh, reverse=False,
              use_peephole=False, w_peep=None, standard_acts=None,
              out_act=None, reset_bt=None):
    """Full-sequence LSTM. x [B, T, D] -> h_seq [B, T, H], (h_T, c_T).

    The [B*T, D]x[D, 4H] projection runs outside the scan (one MXU GEMM);
    the scan body is the small [B, H]x[H, 4H] recurrent GEMM + elementwise.
    ``reverse=True`` runs right-to-left *within each sequence* — because
    state updates are masked, trailing padding passes through untouched,
    reproducing the reference's length-sorted reverse traversal.

    ``reset_bt`` [B, T] (packed sequences, core/sequence.py
    PackedSequenceBatch): positions where the carry re-zeroes to (h0, c0)
    BEFORE the cell computes, so packed neighbours never see each
    other's state. Takes the lax.scan path (no fused kernel).

    When ``standard_acts`` (sigmoid gates + tanh states) and no peephole,
    the whole scan runs as one fused Pallas kernel (ops/pallas_kernels.py —
    hl_cuda_lstm.cu parity, TPU-shaped); otherwise lax.scan.
    """
    _check_reset(reset_bt, reverse)
    b_, t, d = x_btd.shape
    hidden = w_rec.shape[0]
    if w_in is None:  # input already projected to 4H (lstmemory contract)
        gates = x_btd
    else:
        gates = _mm(x_btd.reshape(b_ * t, d), w_in).reshape(b_, t, 4 * hidden)
    if b is not None:
        gates = gates + b
    if h0 is None:
        h0 = jnp.zeros((b_, hidden), x_btd.dtype)
    if c0 is None:
        c0 = jnp.zeros((b_, hidden), x_btd.dtype)
    if reverse:
        # reverse within valid region so step 0 sees the last valid frame
        from paddle_tpu.core.sequence import SequenceBatch

        sb = SequenceBatch(gates, jnp.sum(mask_bt, axis=1).astype(jnp.int32))
        gates = sb.reverse().data
    gates_tm = jnp.swapaxes(gates, 0, 1)
    mask_tm = jnp.swapaxes(mask_bt, 0, 1)

    if standard_acts is None:
        standard_acts = (gate_act is jax.nn.sigmoid and state_act is jnp.tanh
                         and (out_act is None or out_act is jnp.tanh))
    from paddle_tpu.ops import pallas_kernels as pk

    # fused-path eligibility (pk.lstm_mode): resident when w_rec fits VMEM
    # alongside the streaming blocks, hidden-column-tiled otherwise — all
    # benchmark sizes (H up to 1280+, f32 and bf16) stay fused (reference
    # hl_cuda_lstm.cu handles all sizes). Only the real TPU backend (or the
    # tests' explicit interpret flag) takes this path — other backends
    # where pallas merely imports would fail at lowering.
    fused = rows = None
    if (reset_bt is None and pk.enabled() and standard_acts
            and gates_tm.dtype in (jnp.float32, jnp.bfloat16)):
        # args: gates [T,B,4H], mask [T,B], w_rec, h0 [B,H], c0 [B,H]
        # (, w_peep) -> h_seq [T,B,H], h_f [B,H], c_f [B,H]
        fused, rows = _per_device(
            pk.lstm_fused, b_,
            (1, 1, None, 0, 0) + ((None,) if use_peephole else ()),
            (1, 0, 0))
    if fused is not None and pk.lstm_mode(rows, hidden,
                                          gates_tm.dtype) is not None:
        h_seq_tm, h_f, c_f = fused(
            gates_tm, mask_tm.astype(jnp.float32), w_rec, h0, c0,
            *((w_peep,) if use_peephole else ()))
        ys = h_seq_tm
    else:
        step = partial(lstm_step, w_rec=w_rec, gate_act=gate_act,
                       state_act=state_act, use_peephole=use_peephole,
                       w_peep=w_peep, out_act=out_act)

        if reset_bt is None:
            def body(carry, xs):
                g_t, m_t = xs
                return step(carry, g_t, mask_t=m_t)

            (h_f, c_f), ys = lax.scan(body, (h0, c0), (gates_tm, mask_tm))
        else:
            reset_tm = jnp.swapaxes(
                reset_bt.astype(gates_tm.dtype), 0, 1)

            def body(carry, xs):
                g_t, m_t, r_t = xs
                h_prev, c_prev = carry
                keep = (1.0 - r_t)[:, None]
                carry = (h_prev * keep + h0 * r_t[:, None],
                         c_prev * keep + c0 * r_t[:, None])
                return step(carry, g_t, mask_t=m_t)

            (h_f, c_f), ys = lax.scan(body, (h0, c0),
                                      (gates_tm, mask_tm, reset_tm))
    h_seq = jnp.swapaxes(ys, 0, 1)
    if reverse:
        from paddle_tpu.core.sequence import SequenceBatch

        sb = SequenceBatch(h_seq, jnp.sum(mask_bt, axis=1).astype(jnp.int32))
        h_seq = sb.reverse().data
    return h_seq * mask_bt[..., None].astype(h_seq.dtype), (h_f, c_f)


def gru_scan(x_btd, mask_bt, w_in, b, w_rec_rz, w_rec_c, h0=None,
             gate_act=jax.nn.sigmoid, state_act=jnp.tanh, reverse=False,
             reset_bt=None):
    """Full-sequence GRU; same batching strategy as lstm_scan.
    ``reset_bt`` re-zeroes the carry to h0 at packed-segment starts
    (see lstm_scan)."""
    _check_reset(reset_bt, reverse)
    b_, t, d = x_btd.shape
    hidden = w_rec_c.shape[0]
    if w_in is None:  # input already projected to 3H (grumemory contract)
        proj = x_btd
    else:
        proj = _mm(x_btd.reshape(b_ * t, d), w_in).reshape(b_, t, 3 * hidden)
    if b is not None:
        proj = proj + b
    if h0 is None:
        h0 = jnp.zeros((b_, hidden), x_btd.dtype)
    if reverse:
        from paddle_tpu.core.sequence import SequenceBatch

        sb = SequenceBatch(proj, jnp.sum(mask_bt, axis=1).astype(jnp.int32))
        proj = sb.reverse().data
    proj_tm = jnp.swapaxes(proj, 0, 1)
    mask_tm = jnp.swapaxes(mask_bt, 0, 1)

    from paddle_tpu.ops import pallas_kernels as pk

    standard = gate_act is jax.nn.sigmoid and state_act is jnp.tanh
    fused = rows = None
    if (reset_bt is None and pk.enabled() and standard
            and proj_tm.dtype in (jnp.float32, jnp.bfloat16)):
        # args: proj [T,B,3H], mask [T,B], w_rz, w_c, h0 [B,H]
        # -> h_seq [T,B,H], h_f [B,H]
        fused, rows = _per_device(pk.gru_fused, b_,
                                  (1, 1, None, None, 0), (1, 0))
    if fused is not None and pk.gru_mode(rows, hidden,
                                         proj_tm.dtype) is not None:
        # fused whole-sequence GRU kernel (hl_gpu_gru.cuh parity)
        ys, h_f = fused(proj_tm, mask_tm.astype(jnp.float32),
                        w_rec_rz, w_rec_c, h0)
    elif reset_bt is None:
        def body(carry, xs):
            p_t, m_t = xs
            return gru_step(carry, p_t, w_rec_rz, w_rec_c, m_t, gate_act,
                            state_act)

        h_f, ys = lax.scan(body, h0, (proj_tm, mask_tm))
    else:
        reset_tm = jnp.swapaxes(reset_bt.astype(proj_tm.dtype), 0, 1)

        def body(carry, xs):
            p_t, m_t, r_t = xs
            carry = carry * (1.0 - r_t)[:, None] + h0 * r_t[:, None]
            return gru_step(carry, p_t, w_rec_rz, w_rec_c, m_t, gate_act,
                            state_act)

        h_f, ys = lax.scan(body, h0, (proj_tm, mask_tm, reset_tm))
    h_seq = jnp.swapaxes(ys, 0, 1)
    if reverse:
        from paddle_tpu.core.sequence import SequenceBatch

        sb = SequenceBatch(h_seq, jnp.sum(mask_bt, axis=1).astype(jnp.int32))
        h_seq = sb.reverse().data
    return h_seq * mask_bt[..., None].astype(h_seq.dtype), h_f


def rnn_scan(x_btd, mask_bt, w_rec, h0=None, act=jnp.tanh, reverse=False,
             reset_bt=None):
    """Vanilla RNN over a precomputed input projection x [B, T, H]
    (reference: RecurrentLayer — input is already projected by a preceding
    fc/mixed layer, matching its 'input must equal hidden size' contract).
    ``reset_bt`` re-zeroes the carry to h0 at packed-segment starts
    (see lstm_scan)."""
    _check_reset(reset_bt, reverse)
    b_, t, hidden = x_btd.shape
    if h0 is None:
        h0 = jnp.zeros((b_, hidden), x_btd.dtype)
    inp = x_btd
    if reverse:
        from paddle_tpu.core.sequence import SequenceBatch

        sb = SequenceBatch(inp, jnp.sum(mask_bt, axis=1).astype(jnp.int32))
        inp = sb.reverse().data
    inp_tm = jnp.swapaxes(inp, 0, 1)
    mask_tm = jnp.swapaxes(mask_bt, 0, 1)

    if reset_bt is None:
        def body(carry, xs):
            i_t, m_t = xs
            return rnn_step(carry, i_t, w_rec, m_t, act)

        h_f, ys = lax.scan(body, h0, (inp_tm, mask_tm))
    else:
        reset_tm = jnp.swapaxes(reset_bt.astype(inp_tm.dtype), 0, 1)

        def body(carry, xs):
            i_t, m_t, r_t = xs
            carry = carry * (1.0 - r_t)[:, None] + h0 * r_t[:, None]
            return rnn_step(carry, i_t, w_rec, m_t, act)

        h_f, ys = lax.scan(body, h0, (inp_tm, mask_tm, reset_tm))
    h_seq = jnp.swapaxes(ys, 0, 1)
    if reverse:
        from paddle_tpu.core.sequence import SequenceBatch

        sb = SequenceBatch(h_seq, jnp.sum(mask_bt, axis=1).astype(jnp.int32))
        h_seq = sb.reverse().data
    return h_seq * mask_bt[..., None].astype(h_seq.dtype), h_f


def mdlstm_2d(x_img, w_x, w_h_up, w_h_left, bias, size):
    """Two-dimensional LSTM sweep (reference: MDLstmLayer.cpp — Graves-style
    multi-dimensional LSTM): every cell sees its up and left neighbors,

        c[i,j] = f1*c[i-1,j] + f2*c[i,j-1] + i*g
        h[i,j] = o * tanh(c[i,j])

    with gates (i, f_up, f_left, o, g) from x[i,j], h[i-1,j], h[i,j-1].
    Implemented as a scan over rows whose body scans over columns — the
    true dependency wavefront, compiled by XLA into two nested fori loops.

    x_img: [B, H, W, C]; w_x: [C, 5*size]; w_h_up/w_h_left: [size, 5*size];
    bias: [5*size]. Returns h: [B, H, W, size].
    """
    batch, height, width, _ = x_img.shape
    gx = jnp.einsum("bhwc,cg->bhwg", x_img, w_x) + bias  # [B,H,W,5S]
    gx_hm = jnp.moveaxis(gx, 1, 0)  # [H, B, W, 5S]
    zeros_row = (jnp.zeros((batch, width, size), gx.dtype),
                 jnp.zeros((batch, width, size), gx.dtype))

    def split(g):
        return (g[..., :size], g[..., size:2 * size],
                g[..., 2 * size:3 * size], g[..., 3 * size:4 * size],
                g[..., 4 * size:])

    def row_body(row_carry, gx_row):
        h_up_row, c_up_row = row_carry        # [B, W, S] from row above
        gx_wm = jnp.moveaxis(gx_row, 1, 0)    # [W, B, 5S]
        h_up_wm = jnp.moveaxis(h_up_row, 1, 0)
        c_up_wm = jnp.moveaxis(c_up_row, 1, 0)

        def col_body(col_carry, inp):
            h_left, c_left = col_carry        # [B, S]
            gx_t, h_up, c_up = inp
            g = gx_t + h_up @ w_h_up + h_left @ w_h_left
            i, f_up, f_left, o, cand = split(g)
            c = (jax.nn.sigmoid(f_up) * c_up
                 + jax.nn.sigmoid(f_left) * c_left
                 + jax.nn.sigmoid(i) * jnp.tanh(cand))
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), (h, c)

        init = (jnp.zeros((batch, size), gx.dtype),
                jnp.zeros((batch, size), gx.dtype))
        _, (h_wm, c_wm) = lax.scan(col_body, init,
                                   (gx_wm, h_up_wm, c_up_wm))
        h_row = jnp.moveaxis(h_wm, 0, 1)      # [B, W, S]
        c_row = jnp.moveaxis(c_wm, 0, 1)
        return (h_row, c_row), h_row

    _, h_hm = lax.scan(row_body, zeros_row, gx_hm)  # [H, B, W, S]
    return jnp.moveaxis(h_hm, 0, 1)                 # [B, H, W, S]
