"""Lane-packed Pallas conv kernels for the ResNet stage-1/2 hot shapes.

The round-5 floor analysis (benchmark/artifacts/resnet50_bs64_analysis.md)
attributes ~10 ms of the 27.2 ms ResNet-50 bs64 step to C=64/C=128
convolutions running at 19-50% MFU: the MXU contracts 128 lanes per pass,
and a C=64 conv leaves half of every contraction pass empty. XLA exposes
no lane-packing lever (every legal user-level rewrite was shipped or
measured-slower in rounds 4/5), so — exactly like the reference hand-fused
its recurrent hot path for the K40 (paddle/cuda/hl_cuda_lstm.cu) — the
remaining lever is a hand-written kernel. This module is the conv
counterpart of ops/pallas_kernels.py (the fused LSTM/GRU family).

**Packing scheme.** The conv is computed as an implicit-im2col GEMM whose
contraction axis is the flattened (filter-tap, channel) axis of length
kh*kw*C, chunked into full 128-lane groups:

* 3x3 C=64  — 2 taps x 64 channels per group: contraction 576 -> 5 groups
  (vs 9 half-empty 64-lane passes tap-by-tap); the "2 spatial positions
  x 64 channels" packing the floor analysis asked for.
* 3x3 C=128 — 1 tap per group: 9 full 128-lane groups (spatial taps fold
  into successive lane groups).
* 1x1 C>=128 — C/128 groups, plain full-lane GEMM with explicit tiling.
* 1x1 C=64  — no taps to pair, so 2 *image* positions fold into lanes:
  the width axis is viewed as [W/2, 2*64=128] and the weight becomes the
  [128, 2F] block-diagonal pair, computed outside the kernel as a pure
  reshape/update (gradients flow through it; the kernel only ever sees
  full lanes).

Each grid step processes one batch image: the whole (padded) feature map
streams to VMEM, every group contributes one [OH*OW, 128] x [128, F] MXU
dot into an f32 accumulator, and the packed weights stay VMEM-resident
across the batch (fixed-index block, the LSTM kernels' w_ref pattern).

Training support is a jax.custom_vjp: bwd-data REUSES the forward kernel
(for stride-1 SAME odd-k convs the data gradient is the same conv with
spatially flipped, in/out-transposed weights — the transpose stays inside
the supported family, including both directions of the 1x1 bottleneck
pair), and bwd-filter is a second kernel accumulating the packed
[G, 128, F] weight gradient across the batch grid in a fixed-index f32
output block (the LSTM bwd kernel's dpeep pattern).

Dispatch is shape-gated in ops/conv.py (conv2d): "auto" enables a shape
only once a per-shape A/B measurement on the real chip has recorded a win
in _MEASURED_WINS (benchmark/exp_pallas_conv.py emits the table), so the
XLA path is untouched by default; PADDLE_TPU_PALLAS_CONV=on/off force the
kernels everywhere supported / nowhere. CPU tier-1 tests run the same
kernels numerically via interpret mode (tests/test_pallas_conv.py).
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.utils import flags as _flags

_INTERPRET = False  # flipped by tests on CPU

_LANES = 128  # MXU contraction width
# VMEM working-set budget (bytes), matching ops/pallas_kernels.py: v5e has
# ~16MB usable — leave headroom for Pallas double buffering.
_VMEM_BUDGET = 10 * 1024 * 1024

# Shapes (kh, kw, c_in, c_out, h, w) where the on-chip A/B measurement
# (benchmark/exp_pallas_conv.py) recorded a device-timed win over the XLA
# conv. The key includes the spatial geometry — a win at 56x56 says
# nothing about the same weight shape at another feature-map size (VMEM
# working set and GEMM M-dim both change); batch is excluded because the
# grid is per-image, so per-step work is batch-invariant. "auto" dispatch
# fires only for these; ships empty until the first real-chip measurement
# lands (default-safe: the XLA path is untouched). Record wins with the
# measured ms in a comment, e.g. (3, 3, 64, 64, 56, 56): 0.41 vs 0.62 XLA.
_MEASURED_WINS = frozenset()

_flags.define_flag("pallas_conv", "auto",
                   "lane-packed Pallas conv dispatch: auto (only shapes "
                   "with a recorded A/B win — see ops/pallas_conv.py "
                   "_MEASURED_WINS), on (all supported shapes), off "
                   "(trace-time flag; env PADDLE_TPU_PALLAS_CONV)")


def available():
    """False only under the operator's kill switch
    (``PADDLE_TPU_DISABLE_PALLAS``); chip_smoke.py refuses to run with it."""
    import os

    return not os.environ.get("PADDLE_TPU_DISABLE_PALLAS")


def enabled():
    """Kernel path only where it can lower: the TPU backend, or anywhere
    under the tests' explicit interpret flag (ops/pallas_kernels.py)."""
    return available() and (jax.default_backend() == "tpu" or _INTERPRET)


def _interpret():
    return _INTERPRET or jax.default_backend() == "cpu"


def _dot_precision(dtype):
    from paddle_tpu.ops.pallas_kernels import _dot_precision as dp

    return dp(dtype)


# ======================================================================
# packing plans (static python, computed at trace time)
# ======================================================================

def _group_map(kh, kw, c):
    """Static packing plan: chunk the flattened (tap-major, channel-minor)
    contraction axis of length kh*kw*c into 128-lane groups. Returns a
    tuple of groups; each group is a tuple of (dh, dw, c0, c1) input
    slices whose concatenation fills the group's lanes (the last group may
    be short — the kernel zero-pads it)."""
    total = kh * kw * c
    groups = []
    for g in range(-(-total // _LANES)):
        lo, hi = g * _LANES, min((g + 1) * _LANES, total)
        pieces = []
        for t in range(lo // c, (hi - 1) // c + 1):
            c0 = max(lo - t * c, 0)
            c1 = min(hi - t * c, c)
            pieces.append((t // kw, t % kw, c0, c1))
        groups.append(tuple(pieces))
    return tuple(groups)


def _pack_weights(w):
    """[kh, kw, C, F] -> [G, 128, F]: flatten the (tap, channel) axis and
    chunk into the same 128-lane groups as _group_map (zero rows pad the
    last group)."""
    kh, kw, c, f = w.shape
    total = kh * kw * c
    g = -(-total // _LANES)
    flat = w.reshape(total, f)
    if g * _LANES != total:
        flat = jnp.pad(flat, ((0, g * _LANES - total), (0, 0)))
    return flat.reshape(g, _LANES, f)


def _unpack_weight_grad(dw_packed, kh, kw, c, f):
    """Inverse of _pack_weights on the gradient: [G, 128, F] -> [kh, kw, C, F]
    (padding rows drop)."""
    flat = dw_packed.reshape(-1, f)[: kh * kw * c]
    return flat.reshape(kh, kw, c, f)


def _block_diag(w2, pack):
    """[C, F] -> [pack*C, pack*F] block-diagonal: the 1x1 C<128 weight as
    seen by lane-folded image positions. Built with dynamic_update_slice
    so the weight gradient flows back through the diagonal blocks only."""
    c, f = w2.shape
    out = jnp.zeros((pack * c, pack * f), w2.dtype)
    for j in range(pack):
        out = jax.lax.dynamic_update_slice(out, w2, (j * c, j * f))
    return out


# ======================================================================
# forward kernel (shared by bwd-data via weight transpose)
# ======================================================================

def _conv_fwd_kernel(x_ref, w_ref, y_ref, *, oh, ow, groups):
    """One batch image: y[oh, ow, F] = sum_g Z_g @ W_g with Z_g the
    concatenated tap/channel slices of the padded input filling 128 lanes."""
    dt = y_ref.dtype
    f = y_ref.shape[-1]
    m = oh * ow
    acc = jnp.zeros((m, f), jnp.float32)
    prec = _dot_precision(x_ref.dtype)
    for g, pieces in enumerate(groups):
        parts = [x_ref[0, dh:dh + oh, dw:dw + ow, c0:c1].reshape(m, c1 - c0)
                 for (dh, dw, c0, c1) in pieces]
        z = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
        lanes = z.shape[-1]
        if lanes < _LANES:  # short last group: zero lanes x zero w rows
            z = jnp.concatenate(
                [z, jnp.zeros((m, _LANES - lanes), z.dtype)], axis=-1)
        acc = acc + jnp.dot(z, w_ref[g], preferred_element_type=jnp.float32,
                            precision=prec)
    y_ref[0] = acc.reshape(oh, ow, f).astype(dt)


def _fwd_impl(x, w):
    """Stride-1 SAME (odd square kernel) conv, NHWC x HWIO -> NHWC."""
    n, h, wd, c = x.shape
    kh, kw, ci, f = w.shape
    ph, pw = kh // 2, kw // 2
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    hp, wp = h + 2 * ph, wd + 2 * pw
    groups = _group_map(kh, kw, c)
    wpk = _pack_weights(w)
    kernel = partial(_conv_fwd_kernel, oh=h, ow=wd, groups=groups)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((len(groups), _LANES, f), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, h, wd, f), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, h, wd, f), x.dtype),
        interpret=_interpret(),
    )(xp, wpk)


# ======================================================================
# backward-filter kernel
# ======================================================================

def _conv_bwdw_kernel(x_ref, dy_ref, dw_ref, *, oh, ow, groups):
    """Packed weight gradient: dw[g] += Z_g^T @ dY, accumulated across the
    batch grid into the fixed-index f32 output block (the LSTM backward
    kernel's dpeep accumulation pattern)."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    f = dy_ref.shape[-1]
    m = oh * ow
    dy = dy_ref[0].reshape(m, f)
    prec = _dot_precision(dy.dtype)
    for g, pieces in enumerate(groups):
        parts = [x_ref[0, dh:dh + oh, dw:dw + ow, c0:c1].reshape(m, c1 - c0)
                 for (dh, dw, c0, c1) in pieces]
        z = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
        lanes = z.shape[-1]
        if lanes < _LANES:
            z = jnp.concatenate(
                [z, jnp.zeros((m, _LANES - lanes), z.dtype)], axis=-1)
        dw_ref[g] += jax.lax.dot_general(
            z, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)


def _bwd_filter_impl(x, dy, kh, kw):
    """dw[kh, kw, C, F] for the stride-1 SAME conv, f32 accumulation."""
    n, h, wd, c = x.shape
    f = dy.shape[-1]
    ph, pw = kh // 2, kw // 2
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    hp, wp = h + 2 * ph, wd + 2 * pw
    groups = _group_map(kh, kw, c)
    kernel = partial(_conv_bwdw_kernel, oh=h, ow=wd, groups=groups)
    dw_packed = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h, wd, f), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((len(groups), _LANES, f),
                               lambda i: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((len(groups), _LANES, f),
                                       jnp.float32),
        interpret=_interpret(),
    )(xp, dy)
    return _unpack_weight_grad(dw_packed, kh, kw, c, f)


# ======================================================================
# custom VJP
# ======================================================================

@jax.custom_vjp
def _conv_p(x, w):
    """Core differentiable stride-1 SAME conv on the packed kernel family."""
    return _fwd_impl(x, w)


def _conv_p_vjp_fwd(x, w):
    return _fwd_impl(x, w), (x, w)


def _conv_p_vjp_bwd(res, dy):
    x, w = res
    kh, kw, ci, co = w.shape
    # bwd-data IS a conv in the same family: stride-1 SAME with the
    # spatially flipped, in/out-transposed weight (dx = dy * w_rot180^T) —
    # forward-kernel reuse, like the LSTM backward reusing the gate GEMM
    w_t = jnp.flip(jnp.flip(w, 0), 1).transpose(0, 1, 3, 2)
    dx = _fwd_impl(dy, w_t)
    dw = _bwd_filter_impl(x, dy, kh, kw).astype(w.dtype)
    return dx, dw


_conv_p.defvjp(_conv_p_vjp_fwd, _conv_p_vjp_bwd)


def conv2d_lane_packed(x_nhwc, w_hwio):
    """Public entry: stride-1 SAME conv via the lane-packed kernels.
    Shapes must pass kernel_supported(); ops/conv.py gates the dispatch.

    1x1 convs with C < 128 fold ``128 // C`` adjacent image columns into
    the lane axis outside the kernel (pure reshapes + a block-diagonal
    weight view — both differentiable), so the kernel always contracts
    full 128-lane groups."""
    kh, kw, c, f = w_hwio.shape
    if kh == 1 and kw == 1 and c < _LANES:
        pack = _LANES // c
        n, h, wd, _ = x_nhwc.shape
        x2 = x_nhwc.reshape(n, h, wd // pack, pack * c)
        wbd = _block_diag(w_hwio.reshape(c, f), pack)
        y2 = _conv_p(x2, wbd.reshape(1, 1, pack * c, pack * f))
        return y2.reshape(n, h, wd, f)
    return _conv_p(x_nhwc, w_hwio)


# ======================================================================
# eligibility / dispatch gate
# ======================================================================

def _norm_padding(padding, kh, kw):
    """-> ((ph, ph), (pw, pw)) or None if not expressible."""
    if isinstance(padding, str):
        if padding.upper() == "SAME":
            return ((kh // 2, kh // 2), (kw // 2, kw // 2))
        if padding.upper() == "VALID":
            return ((0, 0), (0, 0))
        return None
    return tuple((int(lo), int(hi)) for lo, hi in padding)


def _vmem_bytes(h, wd, c, f, kh, kw, dtype):
    isz = jnp.dtype(dtype).itemsize
    hp, wp = h + 2 * (kh // 2), wd + 2 * (kw // 2)
    g = -(-(kh * kw * c) // _LANES)
    return (2 * hp * wp * c * isz      # x block, double-buffered
            + g * _LANES * f * isz     # packed weights (resident)
            + 2 * h * wd * f * isz     # y / dy block, double-buffered
            + h * wd * f * 4           # f32 accumulator
            + g * _LANES * f * 4)      # bwd-filter f32 output block


def kernel_supported(x_shape, w_shape, stride, padding, groups, dilation,
                     dtype):
    """Static predicate: can conv2d_lane_packed compute this conv exactly
    (and fit VMEM)? Stride-1 SAME odd-square-kernel convs only — the
    ResNet stage-interior family the floor analysis names."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    kh, kw, c, f = (int(d) for d in w_shape)
    n, h, wd, ci = (int(d) for d in x_shape)
    if ci != c or groups != 1 or tuple(dilation) != (1, 1):
        return False
    if tuple(stride) != (1, 1) or kh != kw or kh % 2 == 0 or kh > 3:
        return False
    pads = _norm_padding(padding, kh, kw)
    if pads != ((kh // 2, kh // 2), (kw // 2, kw // 2)):
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    if c % 8 != 0 or f % 8 != 0 or c < 8 or f < 8:
        return False
    if c < _LANES:
        if kh == 1:
            # image-position folding needs an even lane split and width
            if _LANES % c != 0 or wd % (_LANES // c) != 0:
                return False
            pack = _LANES // c
            return _vmem_bytes(h, wd // pack, pack * c, pack * f, 1, 1,
                               dtype) <= _VMEM_BUDGET
    return _vmem_bytes(h, wd, c, f, kh, kw, dtype) <= _VMEM_BUDGET


def shape_key(w_shape, x_shape):
    kh, kw, c, f = (int(d) for d in w_shape)
    return (kh, kw, c, f, int(x_shape[1]), int(x_shape[2]))


def eligible(x, w, stride, padding, groups, dilation):
    """Trace-time dispatch gate for ops/conv.py: off/on force, auto takes
    the kernel only for shapes with a recorded on-chip A/B win."""
    mode = _flags.get_flag("pallas_conv")
    if mode == "off" or not enabled():
        return False
    if w.dtype != x.dtype:  # mixed-dtype dots don't lower in-kernel
        return False
    if not kernel_supported(x.shape, w.shape, stride, padding, groups,
                            dilation, x.dtype):
        return False
    if mode == "on":
        return True
    return shape_key(w.shape, x.shape) in _MEASURED_WINS
