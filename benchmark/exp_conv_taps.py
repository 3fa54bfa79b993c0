"""A/B experiment: XLA native conv vs shift-GEMM tap decomposition at the
profiled-slow geometries (28x28/14x14-class spatial dims, VERDICT r3 weak
#2). Run ON THE CHIP in one process (two processes never share a warm
cache or a clock, so cross-process ms comparisons are noise).

Timing: each step is data-dependent on the previous one (param/input
carry updated from the result — the harness.chain_slope_ms discipline;
independent repeated calls measure the host's enqueue rate, not the
chip).

Usage: python benchmark/exp_conv_taps.py [--fwd-only]
"""

import argparse
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
from jax import lax


def conv_native(x, w, pad):
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.DEFAULT)


def conv_taps(x, w, pad):
    """3x3/5x5 stride-1 conv as kh*kw shifted [M,Cin]x[Cin,Cout] GEMMs,
    f32 accumulation, cast back to x.dtype."""
    b, h, ww_, c = x.shape
    kh, kw, cin, cout = w.shape
    oh = h + 2 * pad - kh + 1
    ow = ww_ + 2 * pad - kw + 1
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    acc = None
    for i in range(kh):
        for j in range(kw):
            sl = lax.slice(xp, (0, i, j, 0), (b, i + oh, j + ow, c))
            t = lax.dot_general(
                sl.reshape(-1, c), w[i, j],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = t if acc is None else acc + t
    return acc.reshape(b, oh, ow, cout).astype(x.dtype)


INNER = 24  # conv steps fused into one jitted scan per profiled call


def chain_timed(step1, carry, calls=3):
    """step1: carry -> carry, one conv step. Measures DEVICE-BUSY time per
    step via the jax profiler ("XLA Modules" span aggregation — the same
    method bench.py trusts for sub-ms configs): wall-clock slopes at these
    step sizes measure host sync jitter, not the chip
    (three earlier designs of this experiment all returned negative
    slopes). INNER steps ride one jitted lax.scan so per-call dispatch
    overhead is amortized too. Returns device ms per SINGLE conv step."""
    import jax

    from paddle_tpu.observe import attribution

    @jax.jit
    def stepN(carry):
        return jax.lax.scan(lambda c, _: (step1(c), None), carry,
                            None, length=INNER)[0]

    state = {"carry": stepN(carry)}  # compile

    def run():
        for _ in range(calls):
            state["carry"] = stepN(state["carry"])

    trace = attribution.capture(run, lambda: float(state["carry"][-1]))
    if trace is None or not trace.module_us:
        return float("nan")
    return trace.module_us / (calls * INNER) / 1000.0


GEOMS = [
    # (name, B, H, Cin, Cout, K, pad)
    ("res_56x56_64", 64, 56, 64, 64, 3, 1),
    ("res_28x28_128", 64, 28, 128, 128, 3, 1),
    ("res_14x14_256", 64, 14, 256, 256, 3, 1),
    ("res_7x7_512", 64, 7, 512, 512, 3, 1),
    ("alex_27x27_c2", 128, 27, 96, 256, 5, 2),
    ("alex_13x13_c3", 128, 13, 256, 384, 3, 1),
    ("alex_13x13_c4", 128, 13, 384, 384, 3, 1),
    ("alex_13x13_c5", 128, 13, 384, 256, 3, 1),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fwd-only", action="store_true")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    dt = jnp.dtype(args.dtype)

    for name, b, hw, cin, cout, k, pad in GEOMS:
        if args.only and args.only not in name:
            continue
        rng = np.random.RandomState(0)
        x0 = jnp.asarray(rng.randn(b, hw, hw, cin) * 0.1, dt)
        w0 = jnp.asarray(rng.randn(k, k, cin, cout) / np.sqrt(k * k * cin),
                         dt)
        gf = 2.0 * b * hw * hw * k * k * cin * cout / 1e9  # fwd FLOPs

        def fwd_step(f, carry):
            x, w, _ = carry
            y = f(x, w, pad)
            # scalar data dependence: next x rescaled by a y statistic
            m = jnp.mean(y.astype(jnp.float32))
            s = (1.0 + 1e-12 * m).astype(dt)
            return (x * s, w, m)

        def fwdbwd_step(f, carry):
            x, w, _ = carry

            def loss(x, w):
                return jnp.mean(f(x, w, pad).astype(jnp.float32) ** 2)

            l, (gx, gw) = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
            return (x - (1e-9 * gx.astype(jnp.float32)).astype(dt),
                    w - (1e-9 * gw.astype(jnp.float32)).astype(dt), l)

        wrap = fwd_step if args.fwd_only else fwdbwd_step
        flops = gf if args.fwd_only else 3 * gf
        carry0 = (x0, w0, jnp.zeros((), jnp.float32))
        nat = chain_timed(partial(wrap, conv_native), carry0)
        tap = chain_timed(partial(wrap, conv_taps), carry0)
        print("%-16s native %7.3fms (%5.1f TF/s) | taps %7.3fms (%5.1f TF/s)"
              " | speedup %.2fx"
              % (name, nat, flops / nat, tap, flops / tap, nat / tap),
              flush=True)


if __name__ == "__main__":
    main()
