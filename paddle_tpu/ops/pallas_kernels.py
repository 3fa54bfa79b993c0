"""Pallas TPU kernels for the recurrent hot path.

Replaces the reference's hand-fused CUDA RNN kernels (paddle/cuda/
hl_cuda_lstm.cu ~700 LoC: one kernel per LSTM step with gate math fused;
hl_gpu_gru.cuh) with one Pallas kernel per *whole sequence*: the recurrent
weight and the h/c state live in VMEM for the scan, per-step gate
pre-activations stream from HBM, and the small [B,H]x[H,4H] recurrent GEMM
plus all gate elementwise math fuse into a single program — no per-step
kernel launches or fusion boundaries (the XLA lax.scan path compiles to a
while-loop with per-iteration boundaries; this kernel removes them).

Two LSTM variants cover every size (the reference's hl_cuda_lstm.cu handles
all sizes; round 1 hard-bailed outside 64 <= H <= 512 f32):

* **resident** — w_rec [H, 4H] fits VMEM alongside the streaming blocks;
  grid (T,), one iteration per timestep.
* **tiled** — grid (T, NJ): the hidden axis is cut into 128-wide column
  blocks. LSTM gate math is elementwise per hidden unit, so block j only
  needs the w_rec columns of gates i,f,g,o restricted to units j*128..;
  those four strided column groups are pre-gathered into a [NJ, H, 4*128]
  layout so each block is one contiguous VMEM window. The full [B, H]
  h-state lives in scratch (double-buffered across j), c-state updates
  block-diagonally in place.

Mixed precision: blocks stream in the input dtype (bfloat16 under the
compute_dtype policy — half the HBM traffic, single-pass MXU dots with f32
accumulation via preferred_element_type); the c state is always f32 scratch.

Training support is a custom VJP whose backward is a second Pallas kernel
running the reverse scan (gate activations recomputed from the streamed
pre-activations — one extra GEMM per step instead of materializing 4 gate
tensors, the standard rematerialization trade). Weight gradients are NOT
accumulated in-kernel: the backward kernel emits per-step dz, and
dw = einsum(h_prev, dz) runs as one big MXU GEMM outside — avoids
non-consecutive output-block accumulation (undefined in Pallas) and is
faster than a per-step rank-B update anyway.

Used automatically by ops.rnn.lstm_scan / gru_scan for the standard
sigmoid/tanh configuration; anything exotic falls back to the lax.scan
path. CPU tests run the same kernels with interpret=True.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.utils import flags as _flags

_INTERPRET = False  # flipped by tests on CPU

# VMEM working-set budget (bytes) for kernel-path eligibility. It counts
# each block once; what Mosaic really allocates is several times that
# (every input block double-buffered, the weight matrices again as loaded
# values and as their transposes, f32 operands split for multi-pass dots):
# the GRU backward at f32 h=768 b=64 counts 10.2 MB here and asked for
# 38.2 MB on the chip (my chip run, PR 21), over Mosaic's default scoped
# limit of 16 MiB. So the recurrent kernels raise that limit to
# _VMEM_LIMIT, half of a v5e core's 128 MiB of VMEM, which covers every
# shape the *_mode functions below admit.
_VMEM_BUDGET = 10 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)
_BLK = 128  # tiled-path hidden column block (lane width)


def available():
    """False only under the operator's kill switch
    (``PADDLE_TPU_DISABLE_PALLAS``); chip_smoke.py refuses to run with it."""
    import os

    return not os.environ.get("PADDLE_TPU_DISABLE_PALLAS")


def enabled():
    """Take the fused path only where it can actually lower: the TPU
    backend, or anywhere under the tests' explicit interpret flag. On other
    backends (e.g. gpu) pallas imports fine but Mosaic lowering would fail."""
    return available() and (jax.default_backend() == "tpu" or _INTERPRET)


def _interpret():
    return _INTERPRET or jax.default_backend() == "cpu"


def _sigmoid(x):
    return jax.nn.sigmoid(x)


def _dot_precision(dtype):
    """In-kernel dot precision: f32 inputs honor the framework's
    matmul_precision flag (so the f32 path is reference-accurate for
    gradient checks / the bench numeric gate); bf16 inputs are always
    single-pass MXU."""
    if dtype == jnp.float32:
        from paddle_tpu.utils import flags

        name = flags.get_flag("matmul_precision")
        if name in ("high", "highest"):
            return getattr(jax.lax.Precision, name.upper())
    return None


def _f32(x):
    return x.astype(jnp.float32)


def _itemsize(dt):
    return jnp.dtype(dt).itemsize


def lstm_mode(batch, hidden, dtype):
    """'resident' | 'tiled' | None (fall back to lax.scan).

    Resident covers any 8-aligned hidden whose weights fit VMEM (Mosaic
    pads odd lane widths — the round-1 coverage, 64 <= H <= 512, and
    beyond for bf16); the tiled path needs 128-aligned hidden for its
    column blocks. Anything else falls back to lax.scan."""
    if _INTERPRET:  # CPU interpret tests: no VMEM/lane constraints
        return "tiled" if hidden % _BLK == 0 and hidden > _BLK else "resident"
    if hidden < 8 or hidden % 8 != 0:
        return None
    isz = _itemsize(dtype)
    # resident: w + 2x streamed gate blocks + state scratches + h/c out blocks
    resident = (hidden * 4 * hidden * isz
                + 4 * batch * 4 * hidden * isz
                + 4 * batch * hidden * 4
                + 4 * batch * hidden * isz)
    if resident <= _VMEM_BUDGET:
        return "resident"
    if hidden % _BLK != 0:
        return None
    tiled = (2 * hidden * 4 * _BLK * isz       # w column block, dbl-buffered
             + 4 * batch * 4 * _BLK * isz      # gate blocks
             + 3 * batch * hidden * 4          # h x2 + c scratches (f32)
             + 6 * batch * _BLK * isz)         # h/c out + misc blocks
    if tiled <= _VMEM_BUDGET:
        return "tiled"
    return None


# ======================================================================
# LSTM forward — resident
# ======================================================================

def _lstm_fwd_kernel(gates_ref, mask_ref, w_ref, peep_ref, h0_ref, c0_ref,
                     hseq_ref, cseq_ref, h_scr, c_scr):
    t = pl.program_id(0)
    dt = hseq_ref.dtype

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = _f32(c0_ref[:])

    h_prev = h_scr[:]
    c_prev = c_scr[:]
    z = _f32(gates_ref[0]) + jnp.dot(h_prev, w_ref[:],
                                     preferred_element_type=jnp.float32,
                                     precision=_dot_precision(h_prev.dtype))
    hidden = h_prev.shape[-1]
    # peephole checks (reference hl_lstm_ops.cuh:61-64): i/f gates see
    # c_{t-1}, o gate sees c_t; zero rows = plain LSTM, exactly
    pi = peep_ref[0:1, :]
    pf = peep_ref[1:2, :]
    po = peep_ref[2:3, :]
    i = _sigmoid(z[:, :hidden] + c_prev * pi)
    f = _sigmoid(z[:, hidden:2 * hidden] + c_prev * pf)
    g = jnp.tanh(z[:, 2 * hidden:3 * hidden])
    c_new = f * c_prev + i * g
    o = _sigmoid(z[:, 3 * hidden:] + c_new * po)
    h_new = o * jnp.tanh(c_new)
    m = mask_ref[0]
    h = jnp.where(m > 0, h_new.astype(dt), h_prev)
    c = jnp.where(m > 0, c_new, c_prev)
    h_scr[:] = h
    c_scr[:] = c
    hseq_ref[0] = h
    cseq_ref[0] = c.astype(dt)


def _lstm_fwd_resident(gates_tm, mask_tm, w_rec, peep, h0, c0):
    t, b, g4 = gates_tm.shape
    hidden = g4 // 4
    dt = gates_tm.dtype
    return pl.pallas_call(
        _lstm_fwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, g4), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, 1), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden, g4), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, hidden), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, b, hidden), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, hidden), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hidden), dt),
            jax.ShapeDtypeStruct((t, b, hidden), dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hidden), dt),
            pltpu.VMEM((b, hidden), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(gates_tm, mask_tm[..., None], w_rec, peep, h0, c0)


# ======================================================================
# LSTM forward — tiled over hidden column blocks
# ======================================================================

def _gate_blocked(x_g4, hidden):
    """[..., 4H] -> [..., NJ, 4*BLK]: per hidden block j, the i/f/g/o gate
    columns for units j*BLK..(j+1)*BLK-1, concatenated."""
    nj = hidden // _BLK
    lead = x_g4.shape[:-1]
    x = x_g4.reshape(lead + (4, nj, _BLK))
    x = jnp.moveaxis(x, -2, -3)  # [..., NJ, 4, BLK]
    return x.reshape(lead + (nj, 4 * _BLK))


def _gate_unblocked(x_blk, hidden):
    """Inverse of _gate_blocked: [..., NJ, 4*BLK] -> [..., 4H]."""
    nj = hidden // _BLK
    lead = x_blk.shape[:-2]
    x = x_blk.reshape(lead + (nj, 4, _BLK))
    x = jnp.moveaxis(x, -3, -2)  # [..., 4, NJ, BLK]
    return x.reshape(lead + (4 * hidden,))


def _lstm_fwd_tiled_kernel(gates_ref, mask_ref, w_ref, peep_ref, h0_ref,
                           c0_ref, hseq_ref, cseq_ref, hprev_scr, hnext_scr,
                           c_scr):
    t = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    dt = hseq_ref.dtype

    @pl.when((t == 0) & (j == 0))
    def _():
        hprev_scr[:] = h0_ref[:]
        c_scr[:] = _f32(c0_ref[:])

    sl = pl.ds(j * _BLK, _BLK)
    h_prev_full = hprev_scr[:]
    z = _f32(gates_ref[0, 0]) + jnp.dot(h_prev_full, w_ref[0],
                                        preferred_element_type=jnp.float32,
                                        precision=_dot_precision(h_prev_full.dtype))
    c_prev = c_scr[:, sl]
    pi = peep_ref[0, 0:1, :]
    pf = peep_ref[0, 1:2, :]
    po = peep_ref[0, 2:3, :]
    i = _sigmoid(z[:, :_BLK] + c_prev * pi)
    f = _sigmoid(z[:, _BLK:2 * _BLK] + c_prev * pf)
    g = jnp.tanh(z[:, 2 * _BLK:3 * _BLK])
    c_new = f * c_prev + i * g
    o = _sigmoid(z[:, 3 * _BLK:] + c_new * po)
    h_new = o * jnp.tanh(c_new)
    m = mask_ref[0]
    h = jnp.where(m > 0, h_new.astype(dt), hprev_scr[:, sl])
    c = jnp.where(m > 0, c_new, c_prev)
    c_scr[:, sl] = c
    hnext_scr[:, sl] = h
    hseq_ref[0] = h
    cseq_ref[0] = c.astype(dt)

    @pl.when(j == nj - 1)
    def _():
        hprev_scr[:] = hnext_scr[:]


def _peep_blocked(peep, hidden):
    """[3, H] -> [NJ, 3, BLK] so tile j loads its hidden-column slice."""
    nj = hidden // _BLK
    return jnp.moveaxis(peep.reshape(3, nj, _BLK), 1, 0)


def _lstm_fwd_tiled(gates_tm, mask_tm, w_rec, peep, h0, c0):
    t, b, g4 = gates_tm.shape
    hidden = g4 // 4
    nj = hidden // _BLK
    dt = gates_tm.dtype
    w_blocked = jnp.moveaxis(
        w_rec.reshape(hidden, 4, nj, _BLK), 2, 0).reshape(nj, hidden, 4 * _BLK)
    gates_blocked = _gate_blocked(gates_tm, hidden)  # [T, B, NJ, 4BLK]
    gates_blocked = jnp.moveaxis(gates_blocked, 2, 1)  # [T, NJ, B, 4BLK]
    return pl.pallas_call(
        _lstm_fwd_tiled_kernel,
        grid=(t, nj),
        in_specs=[
            pl.BlockSpec((1, 1, b, 4 * _BLK), lambda i, j: (i, j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hidden, 4 * _BLK), lambda i, j: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 3, _BLK), lambda i, j: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, b, _BLK), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, _BLK), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hidden), dt),
            jax.ShapeDtypeStruct((t, b, hidden), dt),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hidden), dt),
            pltpu.VMEM((b, hidden), dt),
            pltpu.VMEM((b, hidden), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(gates_blocked, mask_tm[..., None], w_blocked,
      _peep_blocked(peep, hidden), h0, c0)


def _lstm_fwd(gates_tm, mask_tm, w_rec, peep, h0, c0, mode):
    if mode == "tiled":
        return _lstm_fwd_tiled(gates_tm, mask_tm, w_rec, peep, h0, c0)
    return _lstm_fwd_resident(gates_tm, mask_tm, w_rec, peep, h0, c0)


# ======================================================================
# LSTM backward — resident
# ======================================================================

def _lstm_bwd_kernel(gates_ref, mask_ref, w_ref, peep_ref, hprev_ref,
                     cprev_ref, cseq_ref, dh_seq_ref, dhf_ref, dcf_ref,
                     dgates_ref, dh0_ref, dc0_ref, dpeep_ref,
                     dh_scr, dc_scr):
    k = pl.program_id(0)          # 0 .. T-1, processing t = T-1-k
    dt = dgates_ref.dtype

    @pl.when(k == 0)
    def _():
        dh_scr[:] = _f32(dhf_ref[:])
        dc_scr[:] = _f32(dcf_ref[:])
        dpeep_ref[:] = jnp.zeros_like(dpeep_ref)

    h_prev = hprev_ref[0]
    c_prev = _f32(cprev_ref[0])
    z = _f32(gates_ref[0]) + jnp.dot(h_prev, w_ref[:],
                                     preferred_element_type=jnp.float32,
                                     precision=_dot_precision(h_prev.dtype))
    hidden = h_prev.shape[-1]
    pi = peep_ref[0:1, :]
    pf = peep_ref[1:2, :]
    po = peep_ref[2:3, :]
    i = _sigmoid(z[:, :hidden] + c_prev * pi)
    f = _sigmoid(z[:, hidden:2 * hidden] + c_prev * pf)
    g = jnp.tanh(z[:, 2 * hidden:3 * hidden])
    c_new = f * c_prev + i * g   # unmasked c_t (== cseq at live steps)
    o = _sigmoid(z[:, 3 * hidden:] + c_new * po)
    tc = jnp.tanh(_f32(cseq_ref[0]))   # tanh(c_t)

    m = mask_ref[0]
    dh_tot = _f32(dh_seq_ref[0]) + dh_scr[:]
    dc_tot = dc_scr[:]
    dh_eff = jnp.where(m > 0, dh_tot, 0.0)
    do = dh_eff * tc
    dzo = do * o * (1.0 - o)
    # o's peephole reads c_t: its grad feeds back into dc (hl_lstm_ops
    # backward: grad.checkOg path)
    dc_eff = (jnp.where(m > 0, dc_tot, 0.0)
              + dh_eff * o * (1.0 - tc * tc) + dzo * po)
    dzi = dc_eff * g * i * (1.0 - i)
    dzf = dc_eff * c_prev * f * (1.0 - f)
    dzg = dc_eff * i * (1.0 - g * g)
    dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1)
    dgates_ref[0] = dz.astype(dt)
    dh_prev = jnp.where(m > 0, 0.0, dh_tot) + jnp.dot(
        dz.astype(w_ref.dtype), w_ref[:].T,
        preferred_element_type=jnp.float32,
        precision=_dot_precision(w_ref.dtype))
    dc_prev = (dc_eff * f + dzi * pi + dzf * pf
               + jnp.where(m > 0, 0.0, dc_tot))
    dh_scr[:] = dh_prev
    dc_scr[:] = dc_prev
    dpeep_ref[0:1, :] += jnp.sum(dzi * c_prev, axis=0, keepdims=True)
    dpeep_ref[1:2, :] += jnp.sum(dzf * c_prev, axis=0, keepdims=True)
    dpeep_ref[2:3, :] += jnp.sum(dzo * c_new, axis=0, keepdims=True)

    @pl.when(k == pl.num_programs(0) - 1)
    def _():
        dh0_ref[:] = dh_prev.astype(dh0_ref.dtype)
        dc0_ref[:] = dc_prev.astype(dc0_ref.dtype)


def _lstm_bwd_resident(gates_tm, mask_tm, w_rec, peep, hprev_tm, cprev_tm,
                       cseq_tm, dh_seq_tm, dh_f, dc_f):
    t, b, g4 = gates_tm.shape
    hidden = g4 // 4
    dt = gates_tm.dtype
    rev = lambda i: (t - 1 - i, 0, 0)  # noqa: E731
    fixed = lambda i: (0, 0)           # noqa: E731
    return pl.pallas_call(
        _lstm_bwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, g4), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, 1), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden, g4), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, hidden), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, hidden), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, hidden), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, hidden), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, hidden), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, b, g4), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, hidden), fixed, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, g4), dt),
            jax.ShapeDtypeStruct((b, hidden), dt),
            jax.ShapeDtypeStruct((b, hidden), dt),
            jax.ShapeDtypeStruct((3, hidden), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hidden), jnp.float32),
            pltpu.VMEM((b, hidden), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(gates_tm, mask_tm[..., None], w_rec, peep, hprev_tm, cprev_tm, cseq_tm,
      dh_seq_tm, dh_f, dc_f)


# ======================================================================
# LSTM backward — tiled
# ======================================================================

def _lstm_bwd_tiled_kernel(gates_ref, mask_ref, w_ref, peep_ref, hprev_ref,
                           cprev_ref, cseq_ref, dh_seq_ref, dhf_ref, dcf_ref,
                           dgates_ref, dh0_ref, dc0_ref, dpeep_ref,
                           dhc_scr, dhn_scr, dc_scr):
    k = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(0)
    nj = pl.num_programs(1)
    dt = dgates_ref.dtype
    sl = pl.ds(j * _BLK, _BLK)

    @pl.when((k == 0) & (j == 0))
    def _():
        dhc_scr[:] = _f32(dhf_ref[:])
        dc_scr[:] = _f32(dcf_ref[:])
        # dpeep is a full-width fixed-index output: its block never moves,
        # so it stays VMEM-resident across the whole grid (the dh0/dc0
        # pattern) and per-step slices accumulate into it
        dpeep_ref[:] = jnp.zeros_like(dpeep_ref)

    h_prev_full = hprev_ref[0]
    z = _f32(gates_ref[0, 0]) + jnp.dot(h_prev_full, w_ref[0],
                                        preferred_element_type=jnp.float32,
                                        precision=_dot_precision(h_prev_full.dtype))
    c_prev = _f32(cprev_ref[0])
    pi = peep_ref[0, 0:1, :]
    pf = peep_ref[0, 1:2, :]
    po = peep_ref[0, 2:3, :]
    i = _sigmoid(z[:, :_BLK] + c_prev * pi)
    f = _sigmoid(z[:, _BLK:2 * _BLK] + c_prev * pf)
    g = jnp.tanh(z[:, 2 * _BLK:3 * _BLK])
    c_new = f * c_prev + i * g
    o = _sigmoid(z[:, 3 * _BLK:] + c_new * po)
    tc = jnp.tanh(_f32(cseq_ref[0]))

    m = mask_ref[0]
    dh_tot = _f32(dh_seq_ref[0]) + dhc_scr[:, sl]
    dc_tot = dc_scr[:, sl]
    dh_eff = jnp.where(m > 0, dh_tot, 0.0)
    do = dh_eff * tc
    dzo = do * o * (1.0 - o)
    dc_eff = (jnp.where(m > 0, dc_tot, 0.0)
              + dh_eff * o * (1.0 - tc * tc) + dzo * po)
    dzi = dc_eff * g * i * (1.0 - i)
    dzf = dc_eff * c_prev * f * (1.0 - f)
    dzg = dc_eff * i * (1.0 - g * g)
    dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1)
    dgates_ref[0, 0] = dz.astype(dt)

    # full-width dh contribution from this gate block's dz (dz @ w_j^T has
    # all H columns); accumulated across j into the next-step carry buffer
    contrib = jnp.dot(dz.astype(w_ref.dtype), w_ref[0].T,
                      preferred_element_type=jnp.float32,
                      precision=_dot_precision(w_ref.dtype))

    @pl.when(j == 0)
    def _():
        dhn_scr[:] = contrib

    @pl.when(j > 0)
    def _():
        dhn_scr[:] += contrib

    # block-diagonal terms land in this block's columns only: the masked
    # passthrough of dh, and the dc carry (incl. the i/f peephole feedback)
    dhn_scr[:, sl] += jnp.where(m > 0, 0.0, dh_tot)
    dc_scr[:, sl] = (dc_eff * f + dzi * pi + dzf * pf
                     + jnp.where(m > 0, 0.0, dc_tot))
    dpeep_ref[0:1, sl] += jnp.sum(dzi * c_prev, axis=0, keepdims=True)
    dpeep_ref[1:2, sl] += jnp.sum(dzf * c_prev, axis=0, keepdims=True)
    dpeep_ref[2:3, sl] += jnp.sum(dzo * c_new, axis=0, keepdims=True)

    @pl.when(j == nj - 1)
    def _():
        dhc_scr[:] = dhn_scr[:]  # roll the dh carry to step t-1

    @pl.when((k == nt - 1) & (j == nj - 1))
    def _():
        dh0_ref[:] = dhc_scr[:].astype(dh0_ref.dtype)
        dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)


def _lstm_bwd_tiled(gates_tm, mask_tm, w_rec, peep, hprev_tm, cprev_tm,
                    cseq_tm, dh_seq_tm, dh_f, dc_f):
    t, b, g4 = gates_tm.shape
    hidden = g4 // 4
    nj = hidden // _BLK
    dt = gates_tm.dtype
    w_blocked = jnp.moveaxis(
        w_rec.reshape(hidden, 4, nj, _BLK), 2, 0).reshape(nj, hidden, 4 * _BLK)
    gates_blocked = jnp.moveaxis(_gate_blocked(gates_tm, hidden), 2, 1)
    rev4 = lambda k, j: (t - 1 - k, j, 0, 0)   # noqa: E731
    rev3 = lambda k, j: (t - 1 - k, 0, 0)      # noqa: E731
    revb = lambda k, j: (t - 1 - k, 0, j)      # noqa: E731
    fixed = lambda k, j: (0, 0)                # noqa: E731
    dgates_blocked, dh0, dc0, dpeep = pl.pallas_call(
        _lstm_bwd_tiled_kernel,
        grid=(t, nj),
        in_specs=[
            pl.BlockSpec((1, 1, b, 4 * _BLK), rev4, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, 1), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hidden, 4 * _BLK), lambda k, j: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 3, _BLK), lambda k, j: (j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, hidden), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, _BLK), revb, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, _BLK), revb, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, _BLK), revb, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, b, 4 * _BLK), rev4, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, hidden), fixed, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, nj, b, 4 * _BLK), dt),
            jax.ShapeDtypeStruct((b, hidden), dt),
            jax.ShapeDtypeStruct((b, hidden), dt),
            jax.ShapeDtypeStruct((3, hidden), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hidden), jnp.float32),
            pltpu.VMEM((b, hidden), jnp.float32),
            pltpu.VMEM((b, hidden), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(gates_blocked, mask_tm[..., None], w_blocked,
      _peep_blocked(peep, hidden), hprev_tm, cprev_tm,
      cseq_tm, dh_seq_tm, dh_f, dc_f)
    dgates = _gate_unblocked(jnp.moveaxis(dgates_blocked, 1, 2), hidden)
    return dgates, dh0, dc0, dpeep


# ======================================================================
# public LSTM VJP
# ======================================================================

@jax.custom_vjp
def lstm_fused(gates_tm, mask_tm, w_rec, h0, c0, w_peep=None):
    """Fused masked LSTM scan (standard gates: i,f = sigmoid; g = tanh;
    h = o * tanh(c)), with the reference's peephole checks (hl_lstm_ops:
    i/f see c_{t-1}, o sees c_t) when ``w_peep`` [3, H] is given — pass
    None (or zeros) for a plain LSTM; the zero rows reproduce it exactly.
    gates_tm [T, B, 4H] already holds W_in·x + b. Returns
    (h_seq_tm [T, B, H], h_f, c_f). Masked steps copy state forward into
    the sequence outputs, so h_seq[-1]/c_seq[-1] ARE the final states."""
    t, b, g4 = gates_tm.shape
    mode = lstm_mode(b, g4 // 4, gates_tm.dtype) or "resident"
    peep = _peep_or_zeros(w_peep, g4 // 4)
    h_seq, c_seq = _lstm_fwd(gates_tm, mask_tm, w_rec, peep, h0, c0, mode)
    return h_seq, h_seq[-1], c_seq[-1]


def _peep_or_zeros(w_peep, hidden):
    if w_peep is None:
        return jnp.zeros((3, hidden), jnp.float32)
    return _f32(w_peep.reshape(3, hidden))


def _vjp_fwd(gates_tm, mask_tm, w_rec, h0, c0, w_peep=None):
    t, b, g4 = gates_tm.shape
    mode = lstm_mode(b, g4 // 4, gates_tm.dtype) or "resident"
    peep = _peep_or_zeros(w_peep, g4 // 4)
    h_seq, c_seq = _lstm_fwd(gates_tm, mask_tm, w_rec, peep, h0, c0, mode)
    return ((h_seq, h_seq[-1], c_seq[-1]),
            (gates_tm, mask_tm, w_rec, h0, c0, w_peep, h_seq, c_seq))


def _vjp_bwd(res, cotangents):
    gates_tm, mask_tm, w_rec, h0, c0, w_peep, h_seq, c_seq = res
    t, b, g4 = gates_tm.shape
    hidden = g4 // 4
    mode = lstm_mode(b, hidden, gates_tm.dtype) or "resident"
    peep = _peep_or_zeros(w_peep, hidden)
    dh_seq, dh_f, dc_f = cotangents
    hprev_tm = jnp.concatenate([h0[None], h_seq[:-1]], axis=0)
    cprev_tm = jnp.concatenate([c0[None], c_seq[:-1]], axis=0)
    bwd = _lstm_bwd_tiled if mode == "tiled" else _lstm_bwd_resident
    dgates, dh0, dc0, dpeep = bwd(gates_tm, mask_tm, w_rec, peep, hprev_tm,
                                  cprev_tm, c_seq, dh_seq, dh_f, dc_f)
    # weight grad as one big MXU GEMM outside the kernel (fp32 accumulation)
    dw = jnp.einsum("tbh,tbg->hg", hprev_tm, dgates,
                    preferred_element_type=jnp.float32,
                    precision=_dot_precision(hprev_tm.dtype)).astype(w_rec.dtype)
    dw_peep = (None if w_peep is None
               else dpeep.reshape(w_peep.shape).astype(w_peep.dtype))
    return dgates, None, dw, dh0, dc0, dw_peep


lstm_fused.defvjp(_vjp_fwd, _vjp_bwd)


# ======================================================================
# GRU (resident only; reference hl_gpu_gru.cuh parity)
# ======================================================================

def gru_mode(batch, hidden, dtype):
    if _INTERPRET:  # CPU interpret tests
        return "resident"
    if hidden < 8 or hidden % 8 != 0:
        return None
    isz = _itemsize(dtype)
    resident = (3 * hidden * hidden * isz       # w_rz + w_c
                + 4 * batch * 3 * hidden * isz  # proj blocks
                + 4 * batch * hidden * 4)       # h scratch + blocks
    return "resident" if resident <= _VMEM_BUDGET else None


def _gru_fwd_kernel(proj_ref, mask_ref, wrz_ref, wc_ref, h0_ref,
                    hseq_ref, h_scr):
    t = pl.program_id(0)
    dt = hseq_ref.dtype

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]

    h_prev = h_scr[:]
    hidden = h_prev.shape[-1]
    proj = proj_ref[0]
    rz = jnp.dot(h_prev, wrz_ref[:], preferred_element_type=jnp.float32,
                 precision=_dot_precision(h_prev.dtype))
    u = _sigmoid(_f32(proj[:, :hidden]) + rz[:, :hidden])
    r = _sigmoid(_f32(proj[:, hidden:2 * hidden]) + rz[:, hidden:])
    rh = (r * _f32(h_prev)).astype(dt)
    c = jnp.tanh(_f32(proj[:, 2 * hidden:]) + jnp.dot(
        rh, wc_ref[:], preferred_element_type=jnp.float32,
        precision=_dot_precision(rh.dtype)))
    h_new = u * _f32(h_prev) + (1.0 - u) * c
    m = mask_ref[0]
    h = jnp.where(m > 0, h_new.astype(dt), h_prev)
    h_scr[:] = h
    hseq_ref[0] = h


def _gru_fwd(proj_tm, mask_tm, w_rz, w_c, h0):
    t, b, g3 = proj_tm.shape
    hidden = g3 // 3
    dt = proj_tm.dtype
    return pl.pallas_call(
        _gru_fwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, g3), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, 1), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden, 2 * hidden), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden, hidden), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, b, hidden), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((t, b, hidden), dt)],
        scratch_shapes=[pltpu.VMEM((b, hidden), dt)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(proj_tm, mask_tm[..., None], w_rz, w_c, h0)[0]


def _gru_bwd_kernel(proj_ref, mask_ref, wrz_ref, wc_ref, hprev_ref,
                    dh_seq_ref, dhf_ref, dproj_ref, dh0_ref, dh_scr):
    k = pl.program_id(0)
    nt = pl.num_programs(0)
    dt = dproj_ref.dtype

    @pl.when(k == 0)
    def _():
        dh_scr[:] = _f32(dhf_ref[:])

    h_prev = hprev_ref[0]
    hidden = h_prev.shape[-1]
    h32 = _f32(h_prev)
    proj = proj_ref[0]
    rz = jnp.dot(h_prev, wrz_ref[:], preferred_element_type=jnp.float32,
                 precision=_dot_precision(h_prev.dtype))
    u = _sigmoid(_f32(proj[:, :hidden]) + rz[:, :hidden])
    r = _sigmoid(_f32(proj[:, hidden:2 * hidden]) + rz[:, hidden:])
    rh = (r * h32).astype(dt)
    c = jnp.tanh(_f32(proj[:, 2 * hidden:]) + jnp.dot(
        rh, wc_ref[:], preferred_element_type=jnp.float32,
        precision=_dot_precision(rh.dtype)))

    m = mask_ref[0]
    dh_tot = _f32(dh_seq_ref[0]) + dh_scr[:]
    dh_eff = jnp.where(m > 0, dh_tot, 0.0)
    du = dh_eff * (h32 - c)
    dc = dh_eff * (1.0 - u)
    dzc = dc * (1.0 - c * c)
    drh = jnp.dot(dzc.astype(wc_ref.dtype), wc_ref[:].T,
                  preferred_element_type=jnp.float32,
                  precision=_dot_precision(wc_ref.dtype))
    dr = drh * h32
    dzu = du * u * (1.0 - u)
    dzr = dr * r * (1.0 - r)
    dzrz = jnp.concatenate([dzu, dzr], axis=-1)
    dh_prev = (dh_eff * u + drh * r
               + jnp.dot(dzrz.astype(wrz_ref.dtype), wrz_ref[:].T,
                         preferred_element_type=jnp.float32,
                         precision=_dot_precision(wrz_ref.dtype))
               + jnp.where(m > 0, 0.0, dh_tot))
    dproj_ref[0] = jnp.concatenate([dzu, dzr, dzc], axis=-1).astype(dt)
    dh_scr[:] = dh_prev

    @pl.when(k == nt - 1)
    def _():
        dh0_ref[:] = dh_prev.astype(dh0_ref.dtype)


def _gru_bwd(proj_tm, mask_tm, w_rz, w_c, hprev_tm, dh_seq_tm, dh_f):
    t, b, g3 = proj_tm.shape
    hidden = g3 // 3
    dt = proj_tm.dtype
    rev = lambda i: (t - 1 - i, 0, 0)  # noqa: E731
    fixed = lambda i: (0, 0)           # noqa: E731
    return pl.pallas_call(
        _gru_bwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, g3), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, 1), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden, 2 * hidden), fixed,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden, hidden), fixed, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, hidden), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, hidden), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, b, g3), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, hidden), fixed, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, g3), dt),
            jax.ShapeDtypeStruct((b, hidden), dt),
        ],
        scratch_shapes=[pltpu.VMEM((b, hidden), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(proj_tm, mask_tm[..., None], w_rz, w_c, hprev_tm, dh_seq_tm, dh_f)


@jax.custom_vjp
def gru_fused(proj_tm, mask_tm, w_rz, w_c, h0):
    """Fused masked GRU scan, reference gate order (hl_gpu_gru.cuh):
    update u, reset r, candidate c; proj_tm [T, B, 3H] holds W_in·x + b.
    Returns (h_seq_tm [T, B, H], h_f)."""
    h_seq = _gru_fwd(proj_tm, mask_tm, w_rz, w_c, h0)
    return h_seq, h_seq[-1]


def _gru_vjp_fwd(proj_tm, mask_tm, w_rz, w_c, h0):
    h_seq = _gru_fwd(proj_tm, mask_tm, w_rz, w_c, h0)
    return (h_seq, h_seq[-1]), (proj_tm, mask_tm, w_rz, w_c, h0, h_seq)


def _gru_vjp_bwd(res, cotangents):
    proj_tm, mask_tm, w_rz, w_c, h0, h_seq = res
    dh_seq, dh_f = cotangents
    hprev_tm = jnp.concatenate([h0[None], h_seq[:-1]], axis=0)
    dproj, dh0 = _gru_bwd(proj_tm, mask_tm, w_rz, w_c, hprev_tm,
                          dh_seq, dh_f)
    t, b, g3 = proj_tm.shape
    hidden = g3 // 3
    # weight grads as big MXU GEMMs outside the kernel; r*h_prev is
    # recomputed for all t in one batched pass
    dw_rz = jnp.einsum("tbh,tbg->hg", _f32(hprev_tm),
                       _f32(dproj[:, :, :2 * hidden]),
                       precision=_dot_precision(jnp.float32)).astype(w_rz.dtype)
    # only the reset-gate half of w_rz is needed to recompute r
    zr = jnp.einsum("tbh,hg->tbg", _f32(hprev_tm), _f32(w_rz[:, hidden:]),
                    precision=_dot_precision(jnp.float32))
    r = _sigmoid(_f32(proj_tm[:, :, hidden:2 * hidden]) + zr)
    rh = r * _f32(hprev_tm)
    dw_c = jnp.einsum("tbh,tbg->hg", rh,
                      _f32(dproj[:, :, 2 * hidden:]),
                      precision=_dot_precision(jnp.float32)).astype(w_c.dtype)
    return dproj, None, dw_rz, dw_c, dh0


gru_fused.defvjp(_gru_vjp_fwd, _gru_vjp_bwd)


# ======================================================================
# int8 dequant matmul (quantized serving bundles, serve/quantize.py)
# ======================================================================
#
# The serving-side counterpart of the conv kernels' lane packing: a
# quantized bundle stores matmul weights as per-output-channel int8
# (+ f32 scale sidecar), and the weight read IS the bandwidth cost of
# a serving dot. The default path below lets XLA fuse the dequant
# multiply into the dot (the int8 tensor is what streams from HBM);
# this kernel is the hand-fused alternative — the int8 column block
# and its scale slice live in VMEM, dequant runs in-register against
# the streamed activations — gated exactly like ops/pallas_conv.py:
# "auto" fires only for (K, N) shapes with a recorded on-chip A/B win.

# (k, n) weight shapes where benchmark/exp_serve.py --mode quant-ab
# recorded a device-timed win for the Pallas int8 dot over the XLA
# dequant-fused dot. M (the batch/rows axis) is excluded: the grid is
# per column block, so per-step work is M-invariant the same way the
# conv gate is batch-invariant. Ships empty until the first real-chip
# measurement lands (default-safe: the XLA path is untouched). Record
# wins with the measured ms in a comment, e.g. (784, 128): 0.08 vs
# 0.11 XLA.
_INT8_MEASURED_WINS = frozenset()

_flags.define_flag("int8_matmul", "auto",
                   "Pallas int8-dot dispatch for quantized-bundle "
                   "matmuls: auto (only (K, N) shapes with a recorded "
                   "A/B win — see ops/pallas_kernels.py "
                   "_INT8_MEASURED_WINS), on (all supported shapes), "
                   "off (trace-time flag; env PADDLE_TPU_INT8_MATMUL)")


def int8_matmul_mode(m, k, n, dtype):
    """'blocked' when the Pallas int8 dot can lower for this shape,
    else None (XLA dequant-fused fallback). The grid is one 128-wide
    output-column block per step; the full [M, K] activation block and
    the [K, 128] int8 weight block must fit VMEM together."""
    if n < _BLK or n % _BLK != 0:
        return None
    if _INTERPRET:  # CPU interpret tests: no VMEM/lane constraints
        return "blocked"
    if k < 8 or k % 8 != 0 or m < 1:
        return None
    isz = _itemsize(dtype)
    working = (m * k * isz          # activation block (fixed index)
               + 2 * k * _BLK       # int8 weight block, dbl-buffered
               + 2 * _BLK * 4       # scale slice
               + 2 * m * _BLK * isz)  # out block
    return "blocked" if working <= _VMEM_BUDGET else None


def _int8_matmul_take_kernel(m, k, n, dtype):
    if not enabled():
        return False
    mode = _flags.get_flag("int8_matmul")
    if mode == "off" or int8_matmul_mode(m, k, n, dtype) is None:
        return False
    if mode == "on":
        return True
    return (k, n) in _INT8_MEASURED_WINS


def _int8_matmul_kernel(x_ref, w_ref, s_ref, o_ref):
    dt = x_ref.dtype
    # dequant in VMEM: the HBM-resident weight is int8; one broadcast
    # multiply against the per-output-channel scale feeds the MXU dot
    w = (w_ref[:].astype(jnp.float32) * s_ref[:]).astype(dt)
    o_ref[:] = jnp.dot(x_ref[:], w,
                       preferred_element_type=jnp.float32,
                       precision=_dot_precision(dt)).astype(o_ref.dtype)


def _int8_matmul_call(x, w_q, scale):
    m, k = x.shape
    n = w_q.shape[-1]
    nj = n // _BLK
    return pl.pallas_call(
        _int8_matmul_kernel,
        grid=(nj,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, _BLK), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BLK), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((m, _BLK), lambda j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((m, n), x.dtype)],
        interpret=_interpret(),
    )(x, w_q, scale.reshape(1, n))[0]


def int8_matmul(x, w_q, scale):
    """``x @ dequant(w_q, scale)`` for a per-output-channel int8 weight
    (serve/quantize.py): the quantized-bundle matmul. ``x`` is [..., K]
    floating, ``w_q`` [K, N] int8, ``scale`` [N] f32. Default path is
    the XLA dequant-fused dot — the multiply sits inside the jit
    program, so the weight streams from HBM as int8 either way; the
    Pallas kernel takes over only for shapes behind the
    ``_INT8_MEASURED_WINS`` gate (or PADDLE_TPU_INT8_MATMUL=on)."""
    k = x.shape[-1]
    n = w_q.shape[-1]
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= int(d)
    if _int8_matmul_take_kernel(m, k, n, x.dtype):
        out = _int8_matmul_call(x.reshape((m, k)), w_q, scale)
        return out.reshape(lead + (n,))
    return jnp.matmul(x, w_q.astype(x.dtype) * scale.astype(x.dtype))
