"""A/B experiment: XLA conv vs the lane-packed Pallas conv kernels
(paddle_tpu/ops/pallas_conv.py) at the ResNet-50 stage-1/2 hot geometries
the round-5 floor analysis names (C=64/128 convs at 19-50% MFU from MXU
lane underfill). Run ON THE CHIP in one process (cross-process ms
comparisons are noise).

Emits one JSON line per (shape, pass) with device-busy ms for both paths,
then a markdown table suitable for checking in as
benchmark/artifacts/pallas_conv_ab.md. The dispatch gate consumes the
result: shapes whose `pallas` column beats `xla` get recorded in
ops/pallas_conv.py _MEASURED_WINS (with the measured ms in a comment), at
which point the default "auto" mode starts taking the kernel for exactly
those shapes. A losing shape stays on the XLA path and the checked-in
table is the measurement artifact the VERDICT bar asks for.

Timing: device-busy per step via the profiler (paddle_tpu.observe
.attribution "XLA Modules" aggregation — the method bench.py trusts at
sub-ms steps), INNER steps
fused in one jitted scan, data-dependent carries (the chain_slope_ms
discipline; see exp_conv_taps.py for why wall slopes are unusable here).

Usage: python benchmark/exp_pallas_conv.py [--fwd-only] [--only res_]
       python benchmark/exp_pallas_conv.py --cpu-smoke   # interpret-mode
           numeric check at tiny shapes (no timing), for boxes w/o a chip
"""

import argparse
import json
import sys
from functools import partial

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
from jax import lax


def conv_xla(x, w):
    k = w.shape[0]
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1),
        padding=((k // 2, k // 2), (k // 2, k // 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.DEFAULT)


def conv_pallas(x, w):
    from paddle_tpu.ops import pallas_conv

    return pallas_conv.conv2d_lane_packed(x, w)


INNER = 24  # conv steps fused into one jitted scan per profiled call


def chain_timed(step1, carry, calls=3):
    """Device-busy ms per single step (see exp_conv_taps.chain_timed)."""
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


# the four hot shapes at their ResNet-50 bs64 geometries, both directions
# of each 1x1 bottleneck pair: (name, B, H/W, Cin, Cout, K)
GEOMS = [
    ("res1_3x3_c64", 64, 56, 64, 64, 3),
    ("res1_1x1_c64_c256", 64, 56, 64, 256, 1),
    ("res1_1x1_c256_c64", 64, 56, 256, 64, 1),
    ("res2_3x3_c128", 64, 28, 128, 128, 3),
    ("res2_1x1_c128_c512", 64, 28, 128, 512, 1),
    ("res2_1x1_c512_c128", 64, 28, 512, 128, 1),
]


def _steps(f, dt):
    def fwd_step(carry):
        x, w, _ = carry
        y = f(x, w)
        m = jnp.mean(y.astype(jnp.float32))
        return (x * (1.0 + 1e-12 * m).astype(dt), w, m)

    def fwdbwd_step(carry):
        x, w, _ = carry

        def loss(x, w):
            return jnp.mean(f(x, w).astype(jnp.float32) ** 2)

        l, (gx, gw) = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        return (x - (1e-9 * gx.astype(jnp.float32)).astype(dt),
                w - (1e-9 * gw.astype(jnp.float32)).astype(dt), l)

    return fwd_step, fwdbwd_step


def _markdown(rows, fwd_only, dtype):
    out = ["# Pallas lane-packed conv — per-shape A/B vs XLA "
           "(device-busy ms, %s, %s)" % (dtype,
                                         "fwd" if fwd_only else "fwd+bwd"),
           "",
           "| shape | GFLOP/step | xla ms | pallas ms | pallas/xla | "
           "verdict |",
           "|---|---|---|---|---|---|"]
    for r in rows:
        ratio = (r["pallas_ms"] / r["xla_ms"]
                 if r["xla_ms"] and r["xla_ms"] == r["xla_ms"] else
                 float("nan"))
        verdict = ("WIN -> record in _MEASURED_WINS" if ratio < 1.0
                   else "lose -> stay on XLA") if ratio == ratio else "n/a"
        out.append("| %s | %.2f | %.3f | %.3f | %.2fx | %s |"
                   % (r["shape"], r["gflop"], r["xla_ms"], r["pallas_ms"],
                      ratio, verdict))
    out += ["",
            "Winning shapes get their `(kh, kw, cin, cout, h, w)` key "
            "(the `key` field of the JSON rows) added to "
            "`paddle_tpu/ops/pallas_conv.py _MEASURED_WINS` (with the ms "
            "in a comment); `auto` dispatch then takes the kernel for "
            "exactly those shapes AT that feature-map geometry. See "
            "docs/pallas_conv.md."]
    return "\n".join(out)


def cpu_smoke():
    """Numeric-only interpret-mode check at tiny shapes, for boxes with no
    chip: proves the packed kernels compute the same conv (fwd + grads)
    before an on-chip timing run is attempted."""
    from paddle_tpu.ops import pallas_conv

    pallas_conv._INTERPRET = True
    ok = True
    for name, _, _, cin, cout, k in GEOMS:
        h = 6 if cin <= 128 else 4
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, h, h, cin) * 0.3, jnp.float32)
        w = jnp.asarray(rng.randn(k, k, cin, cout) / np.sqrt(k * k * cin),
                        jnp.float32)
        sel = jnp.asarray(rng.randn(2, h, h, cout), jnp.float32)

        def loss(f, x, w):
            return jnp.sum(f(x, w) * sel)

        ref = jax.grad(partial(loss, conv_xla), argnums=(0, 1))(x, w)
        got = jax.grad(partial(loss, conv_pallas), argnums=(0, 1))(x, w)
        errs = [float(jnp.max(jnp.abs(a - b))
                      / jnp.maximum(1.0, jnp.max(jnp.abs(b))))
                for a, b in zip(got, ref)]
        line = {"shape": name, "max_grad_rel_err": max(errs),
                "ok": max(errs) <= 1e-4}
        ok = ok and line["ok"]
        print(json.dumps(line), flush=True)
    print(json.dumps({"cpu_smoke": "pass" if ok else "FAIL"}), flush=True)
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fwd-only", action="store_true")
    ap.add_argument("--dtype", default="bfloat16",
                    help="bench precision (the step the headline row times "
                         "runs bf16)")
    ap.add_argument("--only", default="")
    ap.add_argument("--cpu-smoke", action="store_true")
    ap.add_argument("--write-artifact", default="",
                    help="path to write the markdown table (e.g. "
                         "benchmark/artifacts/pallas_conv_ab.md)")
    args = ap.parse_args()
    if args.cpu_smoke:
        raise SystemExit(cpu_smoke())

    # importing the kernel module defines the pallas_conv flag before the
    # set_flag below (conv_pallas itself only imports it lazily in-jit)
    from paddle_tpu.ops import pallas_conv
    from paddle_tpu.utils import flags

    dt = jnp.dtype(args.dtype)
    rows = []
    for name, b, hw, cin, cout, k in GEOMS:
        if args.only and args.only not in name:
            continue
        rng = np.random.RandomState(0)
        x0 = jnp.asarray(rng.randn(b, hw, hw, cin) * 0.1, dt)
        w0 = jnp.asarray(rng.randn(k, k, cin, cout) / np.sqrt(k * k * cin),
                         dt)
        gf = 2.0 * b * hw * hw * k * k * cin * cout / 1e9
        flops = gf if args.fwd_only else 3 * gf
        carry0 = (x0, w0, jnp.zeros((), jnp.float32))

        fwd_x, fb_x = _steps(conv_xla, dt)
        fwd_p, fb_p = _steps(conv_pallas, dt)
        # force the kernel path regardless of the recorded-wins table —
        # this experiment IS the measurement that populates it
        flags.set_flag("pallas_conv", "on")
        xla_ms = chain_timed(fwd_x if args.fwd_only else fb_x, carry0)
        pal_ms = chain_timed(fwd_p if args.fwd_only else fb_p, carry0)
        rec = {"shape": name,
               "key": pallas_conv.shape_key(w0.shape, x0.shape),
               "gflop": flops,
               "xla_ms": round(xla_ms, 4), "pallas_ms": round(pal_ms, 4),
               "xla_tfs": round(flops / xla_ms, 1) if xla_ms else None,
               "pallas_tfs": round(flops / pal_ms, 1) if pal_ms else None}
        rows.append(rec)
        print(json.dumps(rec), flush=True)

    md = _markdown(rows, args.fwd_only, args.dtype)
    print(md, flush=True)
    if args.write_artifact:
        with open(args.write_artifact, "w") as fh:
            fh.write(md + "\n")


if __name__ == "__main__":
    main()
