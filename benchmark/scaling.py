"""Data-parallel scaling-efficiency harness (north-star metric:
pserver-free DP scaling; reference comparison point: AlexNet 4×K40m
334×4/347 = 3.85× scaling via MultiGradientMachine + pserver,
BASELINE.md "CNN, 4 GPUs").

Times the SAME global-batch train step replicated on 1 device vs sharded
over all devices of a mesh, and reports scaling efficiency
t(1 dev) / t(N dev) / N. On real multi-chip hardware the efficiency
reflects ICI all-reduce overhead; under
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
it validates the harness + sharding end to end (CPU numbers are not a
hardware claim).

Usage:
  python benchmark/scaling.py --model rnn --global-batch 256
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python benchmark/scaling.py --model smallnet --n1 2 --n2 12
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

from benchmark.harness import chain_slope_ms


def build_sharded_step(model, global_batch, n_devices):
    import jax

    from paddle_tpu.parallel.mesh import build_mesh

    from benchmark.harness import build_image_step, build_rnn_step

    mesh = None
    if n_devices > 1:
        mesh = build_mesh({"data": n_devices},
                          devices=jax.devices()[:n_devices])
    if model == "rnn":
        return build_rnn_step(global_batch, hidden=256, dp_mesh=mesh)
    return build_image_step(model, global_batch, dp_mesh=mesh)


def main(argv=None):
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="rnn",
                    choices=("rnn", "smallnet", "alexnet", "googlenet",
                             "resnet50"))
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--n1", type=int, default=5)
    ap.add_argument("--n2", type=int, default=55)
    args = ap.parse_args(argv)

    n = len(jax.devices())
    if args.global_batch % max(n, 1):
        sys.exit("--global-batch %d must be divisible by the device count "
                 "%d (pick e.g. %d)" % (args.global_batch, n,
                                        (args.global_batch // n + 1) * n))
    step1, carry1, fetch1 = build_sharded_step(args.model,
                                               args.global_batch, 1)
    t1, carry1 = chain_slope_ms(step1, carry1, fetch1, args.n1, args.n2)

    if n == 1:
        print(json.dumps({
            "metric": "%s_dp_scaling" % args.model, "value": None,
            "unit": "efficiency",
            "note": "single device visible; run with a multi-device mesh",
            "t1_ms": round(t1, 3)}))
        return

    stepN, carryN, fetchN = build_sharded_step(args.model,
                                               args.global_batch, n)
    tN, carryN = chain_slope_ms(stepN, carryN, fetchN, args.n1, args.n2)
    # INTERLEAVED repeats, min-of-each: the serial t1-then-tN order let a
    # host load spike during either window skew the ratio both ways
    # (round-4 0.929 "regression" and a 1.365 outlier both reproduce
    # under deliberate background load; min of alternating windows is
    # the least-polluted pairing on a time-shared core)
    t1s, tns = [t1], [tN]
    for _ in range(2):
        m, carry1 = chain_slope_ms(step1, carry1, fetch1, args.n1, args.n2)
        t1s.append(m)
        m, carryN = chain_slope_ms(stepN, carryN, fetchN, args.n1, args.n2)
        tns.append(m)
    t1, tN = min(t1s), min(tns)
    eff = t1 / tN / n
    print(json.dumps({
        "metric": "%s_dp_scaling_%ddev" % (args.model, n),
        "value": round(eff, 4), "unit": "efficiency",
        "t1_ms": round(t1, 3), "tN_ms": round(tN, 3),
        "speedup": round(t1 / tN, 3),
        "reference_4gpu": "AlexNet 3.85x/4 = 0.96 (BASELINE.md)"}))


if __name__ == "__main__":
    main()
