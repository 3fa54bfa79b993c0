"""Convolution / pooling / normalization kernels.

Replaces the reference's conv stack — GemmConvOp (im2col+GEMM,
paddle/function/GemmConvOp.cpp), DepthwiseConvOp, cuDNN bindings
(hl_cuda_cudnn.cc), pooling kernels, CrossMapNormalOp — with
lax.conv_general_dilated / lax.reduce_window, which XLA tiles directly onto
the MXU. Layout is NHWC (TPU-native); the layer wrappers translate from the
reference's flattened NCHW vector convention at the graph edge.
"""

from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.dtype import matmul_precision
from paddle_tpu.utils import flags as _flags

_flags.define_flag("lrn_bf16_band", False,
                   "use bf16 operands for the LRN banded matmul (measured "
                   "slower on v5e; trace-time flag)")
_flags.define_flag("pool_grad_mode", "",
                   "max-pool backward: '' = XLA select_and_scatter (best "
                   "measured), 'equality' = compare-VJP everywhere, "
                   "'hybrid' = compare-VJP for stride-1 pools only (both "
                   "measured SLOWER on v5e; trace-time flag)")


def conv2d(x_nhwc, w_hwio, stride=(1, 1), padding="SAME", groups=1, dilation=(1, 1)):
    # Lane-packed Pallas dispatch for the ResNet stage-1/2 hot shapes
    # (C=64/128 convs underfill the MXU's 128 contraction lanes under XLA
    # — the round-5 floor analysis' 10ms bucket). Shape-gated exactly like
    # the conv2d_stem_s2d gate below: default "auto" fires only for shapes
    # with a recorded on-chip A/B win (none yet -> XLA path untouched);
    # PADDLE_TPU_PALLAS_CONV=on/off forces. See ops/pallas_conv.py.
    from paddle_tpu.ops import pallas_conv

    if pallas_conv.eligible(x_nhwc, w_hwio, stride, padding, groups,
                            dilation):
        return pallas_conv.conv2d_lane_packed(x_nhwc, w_hwio)
    return lax.conv_general_dilated(
        x_nhwc,
        w_hwio,
        window_strides=stride,
        padding=padding,
        rhs_dilation=dilation,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        precision=matmul_precision(),
    )


def _s2d_axis_geometry(length, kernel, stride, pad, out):
    """Per-axis geometry of the space-to-depth rewrite: returns
    (front_pad, total_padded_length, taps, shift) where ``taps`` is the
    transformed kernel size over block positions and ``shift`` = d in
    w2[t, q] = w[stride*t + q - d]."""
    pf = -(-pad // stride) * stride  # pad rounded UP to a block multiple
    d = pf - pad
    taps = (kernel - 1 + d) // stride + 1
    total = stride * (out - 1 + taps)  # VALID conv over blocks -> exactly out
    return pf, total, taps, d


def conv2d_stem_s2d(x_nhwc, w_hwio, stride, padding):
    """Exact space-to-depth rewrite of a strided stem convolution.

    The canonical TPU transform for the C_in=3 input convolution (the
    MXU contracts 128 lanes; 3 channels fills 3): block the input by the
    conv stride s — [N, H, W, C] -> [N, H/s, W/s, s*s*C] — and absorb
    the stride into a rearranged kernel, so the conv becomes stride-1
    with an s*s*C contraction axis. Bit-for-bit the same math: each
    output tap o[n] = sum_k w[k] x[s*n - p + k] is regrouped by block
    position q = (s*n - p + k) mod s into w2[t, q] = w[s*t + q - d]
    (zero outside the original kernel), d = front-pad alignment. The
    kernel rearrangement is traced from the ORIGINAL [fh, fw, c, F]
    parameter, so parameter shapes, checkpoints and gradients are
    unchanged — this is a pure execution-layout dispatch, like the
    reference's ExpandConvLayer-vs-cudnn choice (ConvBaseLayer.cpp).
    """
    (sh, sw) = stride
    ((ph, _), (pw, _)) = padding
    n, h, w, c = x_nhwc.shape
    fh, fw, _, f = w_hwio.shape
    oh = (h + 2 * ph - fh) // sh + 1
    ow = (w + 2 * pw - fw) // sw + 1
    pfh, th_total, th, dh = _s2d_axis_geometry(h, fh, sh, ph, oh)
    pfw, tw_total, tw, dw = _s2d_axis_geometry(w, fw, sw, pw, ow)
    # a large front pad can make the nominal total shorter than the
    # padded input; extend to cover (extra block positions slice away)
    th_total = max(th_total, -(-(h + pfh) // sh) * sh)
    tw_total = max(tw_total, -(-(w + pfw) // sw) * sw)

    x = jnp.pad(x_nhwc, ((0, 0), (pfh, th_total - h - pfh),
                         (pfw, tw_total - w - pfw), (0, 0)))
    # blocks: [N, Mh, sh, Mw, sw, C] -> [N, Mh, Mw, sh*sw*C]
    mh, mw = th_total // sh, tw_total // sw
    x = x.reshape(n, mh, sh, mw, sw, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(n, mh, mw, sh * sw * c)

    # kernel: embed w[kh, kw] at w2[th, qh, tw, qw] = w[sh*th+qh-dh, ...]
    # via a zero-padded buffer so the gather is two static slices
    wp = jnp.zeros((sh * th, sw * tw) + w_hwio.shape[2:], w_hwio.dtype)
    wp = lax.dynamic_update_slice(
        wp, w_hwio, (dh, dw) + (0,) * (w_hwio.ndim - 2))
    wp = wp.reshape(th, sh, tw, sw, c, f).transpose(0, 2, 1, 3, 4, 5)
    wp = wp.reshape(th, tw, sh * sw * c, f)

    y = lax.conv_general_dilated(
        x, wp, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=matmul_precision(),
    )
    return y[:, :oh, :ow, :]


def stem_s2d_eligible(c, fh, fw, sh, sw, ph, pw, groups, dilation, trans):
    """Auto-dispatch predicate: small-channel strided stems only — the
    shapes where the plain conv strands most of the MXU's 128 contraction
    lanes (C*fh*fw small) and the rewrite multiplies channels by s*s."""
    mode = _flags.get_flag("conv_stem_s2d")
    if mode == "off" or trans or groups != 1 or dilation != (1, 1):
        return False
    if mode == "on":
        return sh == sw and sh >= 2
    # measured on v5e (round 4, before PR 1): the 11x11/s4 AlexNet stem gains
    # (s*s*C = 48 contraction lanes vs 3), but the 7x7/s2 ResNet/GoogleNet
    # stem REGRESSES 27.2->35.2ms — XLA's native handling of the s2 stem
    # was already fine and the s2d reshapes cost HBM traffic — so auto
    # only fires when the rewrite fills at least a quarter of the MXU's
    # 128 contraction lanes (s*s*C >= 32, i.e. stride-4 stems)
    return (c <= 4 and sh == sw and sh >= 2 and fh >= sh and fw >= sw
            and c * sh * sw >= 32)


_flags.define_flag("conv_stem_s2d", "auto",
                   "space-to-depth stem convs: auto (C_in<=4 and "
                   "stride*stride*C_in>=32, i.e. stride-4 stems), on, off "
                   "(trace-time flag)")


def conv2d_transpose(x_nhwc, w_hwio, stride=(1, 1), padding="SAME"):
    return lax.conv_transpose(
        x_nhwc,
        w_hwio,
        strides=stride,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=matmul_precision(),
    )


def out_size(in_size, filter_size, stride, padding, caffe_mode=True):
    """Spatial output size, reference semantics (config_parser.py cnn_output_size):
    caffe_mode: (in + 2*pad - filter)/stride + 1 (floor);
    else: (in + 2*pad - filter + stride - 1)/stride + 1 (ceil)."""
    if caffe_mode:
        return (in_size + 2 * padding - filter_size) // stride + 1
    return (in_size + 2 * padding - filter_size + stride - 1) // stride + 1


def explicit_pad(padding_hw):
    ph, pw = padding_hw
    return ((ph, ph), (pw, pw))


def max_pool2d(x_nhwc, window, stride, padding=(0, 0), ceil_mode=True):
    """Max pooling. The gradient defaults to XLA's native
    reduce_window/select_and_scatter path: once activations stay in NHWC
    (channels on lanes), it beats the Caffe-style equality-compare VJP by
    ~2x on large feature maps (measured on v5e: GoogleNet bwd 18 vs 32
    ms/step, AlexNet 11 vs 14). The equality VJP below is kept behind
    PADDLE_TPU_EQUALITY_POOL_GRAD for shapes where windows are large
    relative to stride (its cost scales with k*k reads of the input grid,
    select_and_scatter's with window serialization)."""
    import os

    pads = _pool_pads(x_nhwc, window, stride, padding, ceil_mode)
    mode = _flags.get_flag("pool_grad_mode")
    if os.environ.get("PADDLE_TPU_EQUALITY_POOL_GRAD") or mode == "equality" \
            or (mode == "hybrid" and tuple(stride) == (1, 1)):
        return _max_pool_padded(x_nhwc, tuple(window), tuple(stride),
                                tuple(pads))
    # XLA select_and_scatter stays the default: a one-pass Pallas
    # equality-credit backward was prototyped in round 3 and measured 3x
    # SLOWER than SAS at the AlexNet pool1 geometry (2.04 vs 0.74 ms for
    # bwd+fwd — per-batch grid with odd sublane shapes lowers poorly), so
    # it was dropped rather than shipped dead
    return _max_pool_raw(x_nhwc, tuple(window), tuple(stride), tuple(pads))


def _max_pool_raw(x, window, stride, pads):
    # -inf (not finfo.min) keeps reduce_window max well-defined under pads
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        window_dimensions=(1,) + window + (1,),
        window_strides=(1,) + stride + (1,),
        padding=((0, 0),) + pads + ((0, 0),),
    )


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _max_pool_padded(x, window, stride, pads):
    """Max pooling with a hand-written VJP: XLA's native reduce_window-max
    gradient lowers to select_and_scatter, which serializes windows on TPU
    (~1ms per pool layer on the CNN benchmarks). The backward here is the
    Caffe-style equality-compare: scatter dy to every input position that
    equals its window max — k*k shifted compare/select/adds that XLA fuses
    into one elementwise kernel. Ties credit every argmax (the reference's
    CpuMatrix::maxPoolBackward does the same compare, Matrix.cpp)."""
    return _max_pool_raw(x, window, stride, pads)


def _max_pool_vjp_fwd(x, window, stride, pads):
    out = _max_pool_raw(x, window, stride, pads)
    return out, (x, out)


def _max_pool_vjp_bwd(window, stride, pads, res, dy):
    x, out = res
    kh, kw = window
    sh, sw = stride
    (pt, _), (pl, _) = pads
    h, w = x.shape[1], x.shape[2]
    ninf = jnp.asarray(-jnp.inf, out.dtype)
    zero = jnp.zeros((), dy.dtype)
    # dilate outputs onto the padded-input grid: position (oh*sh, ow*sw)
    # (the window's top-left corner) holds out[oh, ow]
    cfg_h = (0, kh - 1, sh - 1)
    cfg_w = (0, kw - 1, sw - 1)
    dyd = lax.pad(dy, zero, ((0, 0, 0), cfg_h, cfg_w, (0, 0, 0)))
    outd = lax.pad(out, ninf, ((0, 0, 0), cfg_h, cfg_w, (0, 0, 0)))
    # generous borders so every shifted window-origin slice stays in range
    fh, fw = kh - 1, kw - 1
    bh = max(0, pt + h - dyd.shape[1] + fh)
    bw = max(0, pl + w - dyd.shape[2] + fw)
    dyd = jnp.pad(dyd, ((0, 0), (fh, bh), (fw, bw), (0, 0)))
    outd = jnp.pad(outd, ((0, 0), (fh, bh), (fw, bw), (0, 0)),
                   constant_values=ninf)
    dx = jnp.zeros(x.shape, dy.dtype)
    for di in range(kh):
        for dj in range(kw):
            hs, ws = pt - di + fh, pl - dj + fw
            o = lax.slice(outd, (0, hs, ws, 0),
                          (outd.shape[0], hs + h, ws + w, outd.shape[3]))
            d = lax.slice(dyd, (0, hs, ws, 0),
                          (dyd.shape[0], hs + h, ws + w, dyd.shape[3]))
            dx = dx + jnp.where(x == o, d, zero)
    return (dx,)


_max_pool_padded.defvjp(_max_pool_vjp_fwd, _max_pool_vjp_bwd)


def avg_pool2d(x_nhwc, window, stride, padding=(0, 0), ceil_mode=True,
               exclude_padding=True):
    pads = _pool_pads(x_nhwc, window, stride, padding, ceil_mode)
    summed = lax.reduce_window(
        x_nhwc,
        0.0,
        lax.add,
        window_dimensions=(1,) + window + (1,),
        window_strides=(1,) + stride + (1,),
        padding=((0, 0),) + pads + ((0, 0),),
    )
    if exclude_padding:
        ones = jnp.ones(x_nhwc.shape[:3] + (1,), x_nhwc.dtype)
        counts = lax.reduce_window(
            ones,
            0.0,
            lax.add,
            window_dimensions=(1,) + window + (1,),
            window_strides=(1,) + stride + (1,),
            padding=((0, 0),) + pads + ((0, 0),),
        )
        return summed / jnp.maximum(counts, 1.0)
    return summed / float(window[0] * window[1])


def _pool_pads(x, window, stride, padding, ceil_mode):
    """Reference pooling uses ceil output size (config_parser.py
    pool_output_size with ceil), which may need extra low-side padding."""
    pads = []
    for axis, (w, s, p) in enumerate(zip(window, stride, padding)):
        in_size = x.shape[1 + axis]
        if ceil_mode:
            out = -(-(in_size + 2 * p - w) // s) + 1
        else:
            out = (in_size + 2 * p - w) // s + 1
        needed = max((out - 1) * s + w - in_size - p, p)
        pads.append((p, needed))
    return tuple(pads)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def batch_norm_train(x, gamma, beta, moving_mean, moving_var, axes, momentum, eps):
    """Returns (y, new_mean, new_var). ``axes`` are reduce axes (all but the
    channel axis). Reference: BatchNormLayer / CudnnBatchNormLayer with
    moving_average_fraction (ModelConfig moving_average_fraction).

    Statistics always accumulate in float32 (a bfloat16 mean over a large
    batch*spatial reduction loses whole digits); the normalized output is
    cast back to x's dtype so mixed precision flows through.

    TPU shape: mean and E[x^2] come from ONE fused reduction pass (the
    jnp.mean+jnp.var spelling reads x twice — var needs mean first), and
    the custom VJP below is the standard 2-pass batchnorm backward
    (one fused dbeta/dgamma reduction, one dx pass) instead of the
    autodiff chain — BN passes dominate the train-mode ResNet-50 step."""
    y, _, _, new_mean, new_var = _bn_train_impl(
        x, gamma, beta, moving_mean, moving_var, axes, momentum, eps)
    return y, new_mean, new_var


def _bn_train_impl(x, gamma, beta, moving_mean, moving_var, axes, momentum,
                   eps):
    from paddle_tpu.core.dtype import upcast_f32

    xf = upcast_f32(x)
    mean = jnp.mean(xf, axis=axes)
    mean_sq = jnp.mean(xf * xf, axis=axes)  # fuses with mean: one x pass
    var = jnp.maximum(mean_sq - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    y = upcast_f32(gamma) * (xf - mean) * inv + upcast_f32(beta)
    new_mean = momentum * moving_mean + (1.0 - momentum) * mean
    new_var = momentum * moving_var + (1.0 - momentum) * var
    return y.astype(x.dtype), mean, inv, new_mean, new_var


def _bn_train_vjp_fwd(x, gamma, beta, moving_mean, moving_var, axes,
                      momentum, eps):
    y, mean, inv, new_mean, new_var = _bn_train_impl(
        x, gamma, beta, moving_mean, moving_var, axes, momentum, eps)
    return (y, new_mean, new_var), (x, gamma, mean, inv)


def _bn_train_vjp_bwd(axes, momentum, eps, res, cts):
    from paddle_tpu.core.dtype import upcast_f32

    x, gamma, mean, inv = res
    dy, d_new_mean, d_new_var = cts
    dyf = upcast_f32(dy)
    xf = upcast_f32(x)
    n = 1
    for a in axes:
        n *= x.shape[a]
    xhat = (xf - mean) * inv
    # pass 1 (fused): both parameter grads
    dbeta = jnp.sum(dyf, axis=axes)
    dgamma = jnp.sum(dyf * xhat, axis=axes)
    # pass 2: dx
    g_inv = upcast_f32(gamma) * inv
    dx = g_inv * (dyf - dbeta / n - xhat * (dgamma / n))
    # moving-stat cotangents (zero in practice: state updates are aux)
    d_moving_mean = momentum * d_new_mean
    d_moving_var = momentum * d_new_var
    dx = dx + (1.0 - momentum) * (
        d_new_mean / n
        + d_new_var * (2.0 / n) * (xf - mean))
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype), d_moving_mean, d_moving_var)


batch_norm_train.defvjp(_bn_train_vjp_fwd, _bn_train_vjp_bwd)


def batch_norm_infer(x, gamma, beta, moving_mean, moving_var, eps):
    from paddle_tpu.core.dtype import upcast_f32

    xf = upcast_f32(x)
    y = (upcast_f32(gamma) * (xf - moving_mean)
         * jax.lax.rsqrt(moving_var + eps) + upcast_f32(beta))
    return y.astype(x.dtype)


def _channel_window_sum(x, size, lo, hi):
    """Sum over a sliding window on the channel (lane) axis, with explicit
    asymmetric padding — shared by LRN forward and its transpose."""
    padded = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (lo, hi)))
    return sum(padded[..., i: i + x.shape[-1]] for i in range(size))


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def cross_map_norm(x_nhwc, size, scale, power):
    """Local response normalization across channels (reference:
    CrossMapNormalOp, paddle/function/CrossMapNormalOp.cpp):
    out = x / (1 + scale/size * sum_{window} x^2)^power.

    Custom VJP: the analytic LRN gradient
        dx = dy * base^-p  -  2*(scale/size)*p * x * W^T[dy * x * base^-p-1]
    is three window-sums, ~2x cheaper than differentiating the padded
    shifted-slice chain (the AlexNet-bench hot spot)."""
    alpha = scale / size
    base = 1.0 + alpha * _channel_window_sum(
        x_nhwc * x_nhwc, size, size // 2, size - 1 - size // 2)
    return x_nhwc * base ** (-power)


def _cmr_vjp_fwd(x, size, scale, power):
    alpha = scale / size
    base = 1.0 + alpha * _channel_window_sum(
        x * x, size, size // 2, size - 1 - size // 2)
    return x * base ** (-power), (x, base)


def _cmr_vjp_bwd(size, scale, power, res, dy):
    x, base = res
    alpha = scale / size
    half = size // 2
    t = dy * x * base ** (-power - 1.0)
    # transpose of the forward window: flipped padding
    s = _channel_window_sum(t, size, size - 1 - half, half)
    dx = dy * base ** (-power) - (2.0 * alpha * power) * x * s
    return (dx,)


cross_map_norm.defvjp(_cmr_vjp_fwd, _cmr_vjp_bwd)


@lru_cache(maxsize=None)
def _lrn_band(channels, size):
    """0/1 banded [C, C] matrix: column c sums the size-wide channel
    window around c."""
    lo, hi = size // 2, size - 1 - size // 2
    band = np.zeros((channels, channels), np.float32)
    for c in range(channels):
        band[max(0, c - lo):min(channels, c + hi + 1), c] = 1.0
    return band


def cross_map_norm_auto(x_nhwc, size, scale, power):
    """LRN with the channel window sum expressed as a banded [C,C] matmul —
    the TPU-native formulation: the 5-tap window ride the MXU (~free FLOPs)
    instead of lane-shifted elementwise passes, cutting the AlexNet LRN
    fwd+bwd from ~3.0ms to ~0.73ms on the conv1 map (measured, v5e).
    Autodiff handles the backward (matmul transpose = band^T matmul).
    Falls back to the shifted-slice path for huge channel counts where a
    [C,C] band would waste FLOPs."""
    b, h, w, c = x_nhwc.shape
    if c > 1024:
        return cross_map_norm(x_nhwc, size, scale, power)
    alpha = scale / size
    from paddle_tpu.utils import flags

    if x_nhwc.dtype == jnp.bfloat16 and flags.get_flag("lrn_bf16_band"):
        # keep the big [B*H*W, C] operands in bf16 (the f32 spelling made
        # the x^2 pass + band matmuls the largest backward dots in the
        # AlexNet profile — 148MB f32 intermediates at conv1); the dot
        # still ACCUMULATES f32, and base/power run f32 per element.
        # OFF by default: measured on v5e it REGRESSED the AlexNet step
        # 10.0 -> 13.9 ms (XLA lowers the bf16 band dot + its backward
        # with extra converts/layouts that cost more than the f32 reads
        # saved) — kept only for future re-evaluation. Flag is read at
        # TRACE time: flip it before the first jit of the model.
        x2 = x_nhwc * x_nhwc
        band = jnp.asarray(_lrn_band(c, size), jnp.bfloat16)
        s = lax.dot(x2.reshape(-1, c), band,
                    preferred_element_type=jnp.float32).reshape(x_nhwc.shape)
        base = 1.0 + alpha * s
        return x_nhwc * (base ** (-power)).astype(x_nhwc.dtype)
    # f32 accumulation minimum; f64 respected (the checkgrad harness)
    ctype = jnp.promote_types(x_nhwc.dtype, jnp.float32)
    x2 = x_nhwc.astype(ctype) ** 2
    band = jnp.asarray(_lrn_band(c, size), ctype)
    s = lax.dot(x2.reshape(-1, c), band).reshape(x_nhwc.shape)
    base = 1.0 + alpha * s
    return x_nhwc * (base ** (-power)).astype(x_nhwc.dtype)


def spatial_pyramid_pool(x_nhwc, pyramid_height, pool="max"):
    """SPP (reference: SpatialPyramidPoolLayer): concat of pooled maps at
    1x1, 2x2, ... 2^(h-1) x 2^(h-1) grids -> [B, sum(4^l) * C]."""
    b, h, w, c = x_nhwc.shape
    outs = []
    for level in range(pyramid_height):
        bins = 2 ** level
        wh, ww = -(-h // bins), -(-w // bins)
        sh, sw = h // bins if h >= bins else 1, w // bins if w >= bins else 1
        wh, ww = max(wh, 1), max(ww, 1)
        fn = max_pool2d if pool == "max" else avg_pool2d
        pooled = fn(x_nhwc, (wh, ww), (max(sh, 1), max(sw, 1)))
        pooled = pooled[:, :bins, :bins, :]
        outs.append(pooled.reshape(b, -1))
    return jnp.concatenate(outs, axis=-1)


def maxout(x_nhwc, groups):
    """Maxout over channel groups (reference: MaxOutLayer): channels C are
    split into C/groups output channels, taking max over each group."""
    b, h, w, c = x_nhwc.shape
    out_c = c // groups
    return jnp.max(x_nhwc.reshape(b, h, w, out_c, groups), axis=-1)
