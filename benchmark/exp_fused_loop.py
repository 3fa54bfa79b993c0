"""Multi-step fused training-loop A/B (trainer ``steps_per_call=K``).

The framework-level attack on the dispatch-bound profiles
(``observe/attribution.py dispatch_gap``; VERDICT r5 — NMT decode and
BiLSTM-CRF finish on-device long before Python can issue the next
step): K optimizer steps per dispatch as ONE ``lax.scan`` with donated
carries, feeds staged K-deep by the DeviceFeeder. This experiment
publishes the audited A/B on the bs32 tagging shape where scan dispatch
dominates:

* ``fused_loop_k1_tagging_bs32``  — one dispatch per step (the chunked
  loop at K=1: byte-identical math to the historical path);
* ``fused_loop_k8_tagging_bs32`` — eight steps per dispatch; the row
  carries ``speedup_vs_k1``.

**Correctness gates run before any row emits** (a speedup that changes
the math is not a speedup): the K=1 fixed-seed loss trajectory must be
IDENTICAL to the legacy per-step path, and K=4 must match K=1 to
<=1e-6 — the same gates tests/test_fused_loop.py pins in tier-1.

Every row passes ``benchmark.harness.sanitize_bench_row``, mirrors into
the telemetry steplog as ``bench_row`` when PADDLE_TPU_TELEMETRY is set,
and is checked against the repo's audited set through the
``observe/regress.py`` gate (warn-only here, like bench.py;
``PADDLE_TPU_BENCH_GATE=hard`` fails the run — and
``cli observe --regress`` gates the mirrored rows in CI).

Usage:
  python benchmark/exp_fused_loop.py                  # K=1 vs K=8
  python benchmark/exp_fused_loop.py --steps 80 --ks 1,4,8,16
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _tagging_samples(n, seed, vocab, labels, length):
    """Fixed-length tagging samples: one jit shape, so every chunk is a
    full K (the dispatch-gap measurement is not diluted by bucket-split
    partial chunks)."""
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, vocab, length).astype(np.int32).tolist(),
             rng.randint(0, labels, length).astype(np.int32).tolist())
            for _ in range(n)]


def _build_trainer(vocab, labels, hidden, emb):
    import paddle_tpu as paddle
    from paddle_tpu import data_type as dt, layer as L
    from paddle_tpu import optimizer as opt
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.parameters import Parameters

    reset_name_counters()
    word = L.data(name="word", type=dt.integer_value_sequence(vocab))
    proj = L.fc(input=L.embedding(input=word, size=emb), size=3 * hidden)
    gru = L.grumemory(input=proj, size=hidden)
    scores = L.fc(input=gru, size=labels)
    label = L.data(name="label", type=dt.integer_value_sequence(labels))
    cost = L.classification_cost(input=scores, label=label)
    params = Parameters.create(cost)
    return paddle.trainer.SGD(
        cost, params, opt.Momentum(learning_rate=1e-3, momentum=0.9))


def _run(k, samples, batch, num_passes, model_kw, collect_losses=False):
    """One fixed-seed train run; returns (losses, steady ms/step) where
    the steady number times the LAST pass (pass 1+ reuses the compiled
    programs — the same first-interval-excluded convention as the
    steplog's steady-state columns)."""
    import paddle_tpu as paddle
    from paddle_tpu import minibatch

    trainer = _build_trainer(**model_kw)
    losses, bounds = [], []

    def handler(e):
        if isinstance(e, (paddle.event.BeginPass, paddle.event.EndPass)):
            bounds.append(time.perf_counter())
        elif collect_losses and isinstance(e, paddle.event.EndIteration):
            losses.append(e.cost)

    trainer.train(minibatch.batch(lambda: iter(samples), batch),
                  num_passes=num_passes, event_handler=handler,
                  steps_per_call=k)
    steps_per_pass = len(samples) // batch
    # last pass only: [Begin, End] pairs per pass, compile in pass 0
    last_ms = (bounds[-1] - bounds[-2]) * 1e3
    return losses, last_ms / max(steps_per_pass, 1)


def check_trajectory_gates(batch, model_kw):
    """The pre-row gates: K=1 == legacy exactly; K=4 vs K=1 <= 1e-6."""
    import paddle_tpu as paddle
    from paddle_tpu import minibatch

    samples = _tagging_samples(8 * batch, seed=5, vocab=model_kw["vocab"],
                               labels=model_kw["labels"], length=12)

    def losses_of(k):
        trainer = _build_trainer(**model_kw)
        out = []
        trainer.train(minibatch.batch(lambda: iter(samples), batch),
                      num_passes=1,
                      event_handler=lambda e: out.append(e.cost)
                      if isinstance(e, paddle.event.EndIteration) else None,
                      steps_per_call=k)
        return out

    legacy = losses_of(None)
    k1 = losses_of(1)
    if legacy != k1:
        raise AssertionError(
            "steps_per_call=1 changed the fixed-seed trajectory vs the "
            "legacy path: %r vs %r" % (legacy[:3], k1[:3]))
    k4 = losses_of(4)
    worst = max(abs(a - b) for a, b in zip(k4, k1))
    if worst > 1e-6:
        raise AssertionError(
            "K=4 trajectory diverged from K=1 by %.3g (> 1e-6)" % worst)
    print("TRAJECTORY_GATE k1_identical=True k4_vs_k1_max_diff=%.3g"
          % worst)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=100,
                    help="train steps per timed pass")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ks", default="1,8",
                    help="comma-separated steps_per_call values to A/B")
    # defaults size the recurrence so per-step device time is small and
    # SCAN DISPATCH dominates — the regime the on-chip tagging_bs32
    # profile is in at full size (2.2% MFU, VERDICT r5); on CPU the
    # full-size cell is compute-bound and would hide the dispatch gap
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=8)
    args = ap.parse_args(argv)

    from benchmark.harness import sanitize_bench_row
    from paddle_tpu.utils import compile_cache
    from paddle_tpu.observe import regress as observe_regress
    from paddle_tpu.observe import steplog

    compile_cache.enable()
    model_kw = dict(vocab=1000, labels=32, hidden=args.hidden, emb=16)
    check_trajectory_gates(args.batch, model_kw)

    samples = _tagging_samples(args.steps * args.batch, seed=0,
                               vocab=model_kw["vocab"],
                               labels=model_kw["labels"],
                               length=args.seq_len)
    ks = [int(v) for v in args.ks.split(",") if v]
    shape = "tagging_bs%d" % args.batch
    rows, ms_by_k = [], {}
    for k in ks:
        _, ms = _run(k, samples, args.batch, num_passes=2,
                     model_kw=model_kw)
        ms_by_k[k] = ms
        row = {"metric": "fused_loop_k%d_%s" % (k, shape),
               "value": round(ms, 3), "unit": "ms/step",
               "steps_per_call": k, "steps": args.steps,
               "batch": args.batch, "seq_len": args.seq_len,
               "trajectory_gate": True}
        base = ms_by_k.get(ks[0])
        if k != ks[0] and base:
            row["speedup_vs_k%d" % ks[0]] = round(base / ms, 3)
        rows.append(row)

    slog = steplog.from_env(run_name="exp_fused_loop",
                            meta={"phase": "bench"})
    try:
        for row in rows:
            row = sanitize_bench_row(row)
            print("BENCH_ROW " + json.dumps(row), flush=True)
            if slog is not None:
                slog.write({"type": "bench_row", **row})
    finally:
        if slog is not None:
            slog.close()

    # audited regression gate (warn-only unless PADDLE_TPU_BENCH_GATE=hard)
    results, regressions = observe_regress.gate_rows(rows)
    for res in results:
        if res["status"] in ("regression", "ok"):
            print("GATE " + observe_regress.format_result(res))
    if regressions and observe_regress.hard_gate():
        print("BENCH GATE FAILED: %d regression(s)" % len(regressions))
        return 1
    if len(ks) > 1:
        print("SUMMARY fused_speedup_k%d_vs_k%d=%.2fx"
              % (ks[-1], ks[0], ms_by_k[ks[0]] / ms_by_k[ks[-1]]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
