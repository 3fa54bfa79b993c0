"""Training-fleet observability A/B (``observe/trainview.py`` recorder +
the ``cli observe`` straggler detector).

Two audited claims back the training-fleet view (ISSUE 19):

* **the detector names the right straggler** — a 2-worker fixed-seed
  tagging run where worker ``trainer-1`` is artificially slowed by a
  per-step sleep must come back from ``steplog.summarize_dir`` with
  ``train_fleet.straggler == trainer-1``, and the measured skew
  (worker p95 / fleet median, the ``cli observe`` number) is published
  under the lower-better ``skew`` unit:

  - ``elastic_observe_skew_tagging_bs16`` — median-over-rounds skew of
    the named straggler (a fleet drifting further from uniform step
    time is a regression);

* **the recorder is free** — ``TrainHealthHistory.record_step`` rides
  the per-step finalize path, so recorder-on vs recorder-off must stay
  within **3%** step time (the ISSUE 19 gate):

  - ``trainview_recorder_off_tagging_bs16`` — recorder disabled (floor);
  - ``trainview_recorder_on_tagging_bs16``  — recorder enabled; carries
    ``overhead_pct`` vs off.

Timing is INTERLEAVED exactly like exp_checkpoint.py: one long-lived
trainer alternates a recorder-off and a recorder-on pass per ROUND, so
shared-host drift (CPU frequency, noisy neighbors) hits both arms
together and cancels in the per-round ratio; ``overhead_pct`` is the
MEDIAN over per-round ratios while each row's ``value`` stays the
min-over-rounds steady-state ms/step. The straggler rounds likewise
re-run the full 2-worker pipeline (fresh telemetry dir, one pass per
worker, ``summarize_dir`` aggregation) per round — the bench exercises
the same path ``cli observe`` walks, not a synthetic walls list.

**Correctness gate before any row emits**: every round's aggregation
must name ``trainer-1``. A detector that fingers the wrong worker has
no publishable number (AssertionError, mirroring exp_checkpoint's
trajectory gate).

Every row passes ``benchmark.harness.sanitize_bench_row``, mirrors into
the telemetry steplog as ``bench_row`` when PADDLE_TPU_TELEMETRY is
set, and runs through the ``observe/regress.py`` audited gate
(warn-only by default; ``PADDLE_TPU_BENCH_GATE=hard`` fails the run).

Usage:
  python benchmark/exp_elastic_observe.py
  python benchmark/exp_elastic_observe.py --rounds 6 --slow-ms 30
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

from paddle_tpu.utils.error import enforce  # noqa: E402

WORKER_ENV = "PADDLE_TPU_TRAIN_WORKER"
TELEMETRY_ENV = "PADDLE_TPU_TELEMETRY"


def _tagging_samples(n, seed, vocab, labels, length):
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


class _WorkerRunner:
    """One simulated worker: a long-lived trainer whose passes run under
    this worker's ``PADDLE_TPU_TRAIN_WORKER`` identity, optionally slowed
    by a fixed per-step sleep (the artificial straggler). The worker env
    var is set for the duration of the pass only, so the bench process's
    own telemetry (the bench_row mirror) stays unattributed."""

    def __init__(self, worker_id, samples, batch, model_kw, slow_ms=0.0):
        self.worker_id = worker_id
        self.samples = samples
        self.batch = batch
        self.steps = len(samples) // batch
        self.slow_ms = float(slow_ms)
        self.trainer = _build_trainer(**model_kw)

    def run_pass(self, telemetry_dir=None):
        """One pass under this worker's identity; returns ms/step."""
        import paddle_tpu as paddle
        from paddle_tpu import minibatch

        bounds = {}
        delay_s = self.slow_ms / 1e3

        def handler(e):
            if isinstance(e, paddle.event.BeginPass):
                bounds["b"] = time.perf_counter()
            elif isinstance(e, paddle.event.EndPass):
                bounds["e"] = time.perf_counter()
            elif delay_s and isinstance(e, paddle.event.EndIteration):
                time.sleep(delay_s)

        saved = {k: os.environ.pop(k, None)
                 for k in (WORKER_ENV, TELEMETRY_ENV)}
        os.environ[WORKER_ENV] = self.worker_id
        if telemetry_dir is not None:
            os.environ[TELEMETRY_ENV] = telemetry_dir
        try:
            self.trainer.train(
                minibatch.batch(lambda: iter(self.samples), self.batch),
                num_passes=1, event_handler=handler)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return (bounds["e"] - bounds["b"]) * 1e3 / max(self.steps, 1)


def straggler_rounds(rounds, samples, batch, model_kw, slow_ms, workdir):
    """Per round: both workers train one pass into a FRESH telemetry
    dir, then ``summarize_dir`` aggregates it exactly as ``cli observe``
    would. Returns the per-round measured skew of trainer-1; raises if
    any round names a different straggler (correctness gate)."""
    from paddle_tpu.observe import steplog

    fast = _WorkerRunner("trainer-0", samples, batch, model_kw)
    slow = _WorkerRunner("trainer-1", samples, batch, model_kw,
                         slow_ms=slow_ms)
    # pass 0 carries the compiles (shared compile cache: one trace)
    fast.run_pass()
    slow.run_pass()
    skews = []
    for r in range(rounds):
        tdir = os.path.join(workdir, "fleet-%d" % r)
        fast_ms = fast.run_pass(telemetry_dir=tdir)
        slow_ms_meas = slow.run_pass(telemetry_dir=tdir)
        fleet = (steplog.summarize_dir(tdir) or {}).get("train_fleet")
        enforce(fleet and fleet.get("skew"),
                "2-worker telemetry dir produced no train_fleet summary")
        straggler = fleet.get("straggler") or {}
        if straggler.get("worker") != "trainer-1":
            raise AssertionError(
                "straggler detector named %r, expected trainer-1 "
                "(round %d: fast=%.2f slow=%.2f ms/step, skew table %r)"
                % (straggler, r, fast_ms, slow_ms_meas,
                   fleet["skew"]["workers"]))
        skews.append(float(straggler["skew"]))
        print("ROUND %d fast=%.2f slow=%.2f ms/step skew=%.3f"
              % (r, fast_ms, slow_ms_meas, skews[-1]), flush=True)
    return skews


def recorder_rounds(rounds, samples, batch, model_kw):
    """Interleaved recorder-off / recorder-on passes on ONE long-lived
    trainer (no telemetry dir: the arm under test is the in-process
    ``TrainHealthHistory``, not the steplog). Returns
    (off_ms list, on_ms list) per round."""
    from paddle_tpu.observe import trainview

    runner = _WorkerRunner("trainer-0", samples, batch, model_kw)
    runner.run_pass()  # pass 0 carries the compiles
    off_ms, on_ms = [], []
    try:
        for r in range(rounds):
            trainview.set_enabled(False)
            off_ms.append(runner.run_pass())
            trainview.set_enabled(True)
            on_ms.append(runner.run_pass())
            print("ROUND %d recorder off=%.2f on=%.2f ms/step"
                  % (r, off_ms[-1], on_ms[-1]), flush=True)
    finally:
        trainview.set_enabled(True)
    return off_ms, on_ms


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=24,
                    help="train steps per timed pass")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=32,
                    help="GRU width; small on purpose — the straggler "
                         "signal is the injected sleep, not compute")
    ap.add_argument("--slow-ms", type=float, default=25.0,
                    help="artificial per-step sleep on trainer-1 (the "
                         "injected straggler)")
    ap.add_argument("--recorder-steps", type=int, default=96,
                    help="steps per timed pass for the recorder A/B — "
                         "longer than the straggler passes so a sub-3%% "
                         "differential resolves above pass-timing noise")
    ap.add_argument("--rounds", type=int, default=6,
                    help="interleaved rounds (fresh 2-worker telemetry "
                         "dir per round; median skew over rounds)")
    args = ap.parse_args(argv)

    from benchmark.harness import sanitize_bench_row
    from paddle_tpu.utils import compile_cache
    from paddle_tpu.observe import regress as observe_regress
    from paddle_tpu.observe import steplog

    compile_cache.enable()
    model_kw = dict(vocab=200, labels=16, hidden=args.hidden, emb=16)
    samples = _tagging_samples(args.steps * args.batch, seed=0,
                               vocab=model_kw["vocab"],
                               labels=model_kw["labels"],
                               length=args.seq_len)
    shape = "tagging_bs%d" % args.batch
    rounds = max(args.rounds, 1)
    workdir = tempfile.mkdtemp(prefix="exp_elastic_observe_")
    try:
        skews = straggler_rounds(rounds, samples, args.batch, model_kw,
                                 args.slow_ms, workdir)
        recorder_samples = _tagging_samples(
            args.recorder_steps * args.batch, seed=1,
            vocab=model_kw["vocab"], labels=model_kw["labels"],
            length=args.seq_len)
        off_ms, on_ms = recorder_rounds(rounds, recorder_samples,
                                        args.batch, model_kw)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    med_skew = float(np.median(skews))
    skew_spread = ((max(skews) - min(skews)) / med_skew * 100.0
                   if med_skew else 0.0)
    # overhead: MEDIAN over per-round on/off ratios — both arms of a
    # round run back to back, so host drift cancels in the ratio
    overhead = float(np.median([(on - off) / off * 100.0
                                for on, off in zip(on_ms, off_ms)]))
    rows = [
        {"metric": "elastic_observe_skew_%s" % shape,
         "value": round(med_skew, 3), "unit": "skew",
         "straggler": "trainer-1", "slow_ms": args.slow_ms,
         "steps": args.steps, "batch": args.batch, "rounds": rounds,
         "spread_pct": round(skew_spread, 2)},
        {"metric": "trainview_recorder_off_%s" % shape,
         "value": round(min(off_ms), 3), "unit": "ms/step",
         "steps": args.recorder_steps, "batch": args.batch,
         "hidden": args.hidden, "rounds": rounds},
        {"metric": "trainview_recorder_on_%s" % shape,
         "value": round(min(on_ms), 3), "unit": "ms/step",
         "steps": args.recorder_steps, "batch": args.batch,
         "hidden": args.hidden, "rounds": rounds,
         "overhead_pct": round(overhead, 2)},
    ]

    slog = steplog.from_env(run_name="exp_elastic_observe",
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
    print("SUMMARY straggler=trainer-1 median_skew=%.3f "
          "recorder_overhead_pct=%.2f gate_le_3pct=%s"
          % (med_skew, overhead, overhead <= 3.0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
