"""Checkpoint-overhead A/B (trainer ``checkpoint_every`` +
``distributed/checkpoint.py`` async overlapped writer).

The reference pserver blocked its service loop while doCheckpoint
serialized and MD5-summed the shard; our modern equivalent must NOT
block the step thread: the overlapped path costs it one jitted
device-side buffer clone + an async device→host kick, while the named
``ckpt-writer`` thread does serialization + fsync + atomic rename.
This experiment publishes the audited contrast on a fixed-seed tagging
run:

* ``checkpoint_off_tagging_bs32``     — no checkpointing (the floor);
* ``checkpoint_overlap_tagging_bs32`` — overlapped saves every N steps;
  the row carries ``overhead_pct`` vs off — the ISSUE 12 gate is
  **< 5%**;
* ``checkpoint_sync_tagging_bs32``    — the blocking save on the step
  thread (what overlap buys its way out of).

The default shape (hidden=128) is deliberately COMPUTE-BOUND: the step
must spend its time in XLA (GIL-free) for overlap to have anything to
overlap against. On a toy shape whose step is dominated by Python feed
conversion and dispatch, the writer thread's serialization bytecode
serializes against the step thread on the GIL no matter how it is
scheduled — that measures CPython contention on a 2-core host, not the
checkpoint design (a TPU host's step thread is a thin dispatch loop
with idle host cores, the regime hidden=128 emulates). ``--hidden 64``
reproduces the adversarial GIL-bound case.

Timing is INTERLEAVED: the three configs keep long-lived trainers and
alternate one timed pass per round. Timing each config in its own
process minutes apart cannot resolve a sub-5% differential — the floor
itself drifts more than that on a shared host (CPU frequency, page
cache, fsync latency). Each round's three passes run back to back so
drift hits all three together; ``overhead_pct`` is the MEDIAN over the
per-round ratios (drift cancels in the ratio, the median sheds burst
rounds), while each row's ``value`` stays the min-over-rounds
steady-state ms/step.

**Correctness gate before any row emits**: the overlapped run's
fixed-seed loss trajectory must be IDENTICAL (<= 1e-6) to the
no-checkpointing run's — a cheap save that changed the math would not
be a save. (tests/test_preemption.py pins the same identity, plus the
kill -9 resume, in tier-1.)

Every row passes ``benchmark.harness.sanitize_bench_row``, mirrors into
the telemetry steplog as ``bench_row`` when PADDLE_TPU_TELEMETRY is
set, and runs through the ``observe/regress.py`` audited gate
(warn-only by default; ``PADDLE_TPU_BENCH_GATE=hard`` fails the run).

Usage:
  python benchmark/exp_checkpoint.py
  python benchmark/exp_checkpoint.py --steps 120 --every 10
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


def _run(samples, batch, num_passes, model_kw, ckpt_dir=None, every=0,
         sync=False, collect_losses=False):
    """One fixed-seed run; returns (losses, steady ms/step of the LAST
    pass — compile lands in pass 0, the steplog steady-state
    convention) plus the saves count."""
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
                  checkpoint_dir=ckpt_dir, checkpoint_every=every,
                  checkpoint_sync=sync)
    steps_per_pass = len(samples) // batch
    # min over the post-compile passes: the repeatable steady-state
    # number on a shared/noisy host (pass 0 carries the compiles)
    pass_ms = [(bounds[2 * i + 1] - bounds[2 * i]) * 1e3
               for i in range(1, len(bounds) // 2)]
    best_ms = min(pass_ms) if pass_ms else float("nan")
    writer_saves = None
    if ckpt_dir and os.path.isdir(ckpt_dir):
        writer_saves = len([d for d in os.listdir(ckpt_dir)
                            if d.startswith("pass-")])
    return losses, best_ms / max(steps_per_pass, 1), writer_saves


class _PassRunner:
    """One config's long-lived trainer, driven one timed pass at a
    time. A sub-5% differential cannot be resolved by timing each
    config in its own process minutes apart — the floor itself drifts
    more than that on a shared host (CPU frequency, page cache, fsync
    latency). Interleaving one pass per config per ROUND puts every
    config under the same drift, and min-over-rounds cancels it."""

    def __init__(self, samples, batch, model_kw, ckpt_dir=None, every=0,
                 sync=False):
        self.samples = samples
        self.batch = batch
        self.steps = len(samples) // batch
        self.trainer = _build_trainer(**model_kw)
        self.kw = dict(checkpoint_dir=ckpt_dir, checkpoint_every=every,
                       checkpoint_sync=sync)
        self.ckpt_dir = ckpt_dir

    def pass_ms(self):
        """Train one pass; returns ms/step (full pass wall / steps —
        checkpoint work between EndIteration events included)."""
        import paddle_tpu as paddle
        from paddle_tpu import minibatch

        bounds = {}

        def handler(e):
            if isinstance(e, paddle.event.BeginPass):
                bounds["b"] = time.perf_counter()
            elif isinstance(e, paddle.event.EndPass):
                bounds["e"] = time.perf_counter()

        self.trainer.train(
            minibatch.batch(lambda: iter(self.samples), self.batch),
            num_passes=1, event_handler=handler, **self.kw)
        return (bounds["e"] - bounds["b"]) * 1e3 / max(self.steps, 1)

    def saves(self):
        if not self.ckpt_dir or not os.path.isdir(self.ckpt_dir):
            return None
        return len([d for d in os.listdir(self.ckpt_dir)
                    if d.startswith("pass-")])


def check_trajectory_gate(batch, model_kw, every, workdir):
    """Overlapped checkpointing must not change the fixed-seed math."""
    samples = _tagging_samples(8 * batch, seed=5, vocab=model_kw["vocab"],
                               labels=model_kw["labels"], length=12)
    # the gate pass is 8 steps; clamp the cadence so saves actually fire
    # inside it (a gate that never checkpointed would test nothing)
    gate_every = max(1, min(every, 4))
    off, _, _ = _run(samples, batch, 1, model_kw, collect_losses=True)
    on, _, saves = _run(samples, batch, 1, model_kw,
                        ckpt_dir=os.path.join(workdir, "gate"),
                        every=gate_every, collect_losses=True)
    enforce(saves, "trajectory gate ran without committing a checkpoint")
    worst = max(abs(a - b) for a, b in zip(off, on))
    if worst > 1e-6:
        raise AssertionError(
            "overlapped checkpointing changed the fixed-seed trajectory "
            "by %.3g (> 1e-6)" % worst)
    print("TRAJECTORY_GATE overlap_vs_off_max_diff=%.3g" % worst)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=60,
                    help="train steps per timed pass")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--every", type=int, default=10,
                    help="checkpoint cadence in steps (still ~10-100x "
                         "more frequent than production; at --every 5 "
                         "the writer's few ms of GIL-held serialization "
                         "per save sit at the gate's edge on a 2-core "
                         "host)")
    ap.add_argument("--seq-len", type=int, default=24)
    ap.add_argument("--hidden", type=int, default=128,
                    help="GRU width; the default keeps the step "
                         "compute-bound (see module docstring)")
    ap.add_argument("--rounds", type=int, default=8,
                    help="interleaved A/B rounds (one timed pass per "
                         "config per round; min over rounds)")
    args = ap.parse_args(argv)

    from benchmark.harness import sanitize_bench_row
    from paddle_tpu.utils import compile_cache
    from paddle_tpu.observe import regress as observe_regress
    from paddle_tpu.observe import steplog

    compile_cache.enable()
    model_kw = dict(vocab=1000, labels=32, hidden=args.hidden, emb=32)
    workdir = tempfile.mkdtemp(prefix="exp_checkpoint_")
    try:
        check_trajectory_gate(args.batch, model_kw, args.every, workdir)
        samples = _tagging_samples(args.steps * args.batch, seed=0,
                                   vocab=model_kw["vocab"],
                                   labels=model_kw["labels"],
                                   length=args.seq_len)
        shape = "tagging_bs%d" % args.batch
        runners = {
            "off": _PassRunner(samples, args.batch, model_kw),
            "overlap": _PassRunner(samples, args.batch, model_kw,
                                   ckpt_dir=os.path.join(workdir, "o"),
                                   every=args.every),
            "sync": _PassRunner(samples, args.batch, model_kw,
                                ckpt_dir=os.path.join(workdir, "s"),
                                every=args.every, sync=True),
        }
        for runner in runners.values():  # pass 0 carries the compiles
            runner.pass_ms()
        samples_ms = {tag: [] for tag in runners}
        for r in range(max(args.rounds, 1)):
            for tag, runner in runners.items():
                samples_ms[tag].append(runner.pass_ms())
            print("ROUND %d off=%.2f overlap=%.2f sync=%.2f ms/step"
                  % (r, *(samples_ms[t][-1]
                          for t in ("off", "overlap", "sync"))),
                  flush=True)
        best = {tag: min(ms) for tag, ms in samples_ms.items()}
        # overhead: MEDIAN over per-round ratios — each round's three
        # passes run back to back, so host drift (CPU frequency, fsync
        # latency, noisy neighbors) hits all three configs together and
        # cancels in the ratio; the median then sheds burst rounds
        med_overhead = {
            tag: float(np.median(
                [(m - off) / off * 100.0
                 for m, off in zip(samples_ms[tag], samples_ms["off"])]))
            for tag in ("overlap", "sync")}
        rows = [{"metric": "checkpoint_off_%s" % shape,
                 "value": round(best["off"], 3), "unit": "ms/step",
                 "steps": args.steps, "batch": args.batch,
                 "hidden": args.hidden, "rounds": args.rounds}]
        for tag in ("overlap", "sync"):
            rows.append({"metric": "checkpoint_%s_%s" % (tag, shape),
                         "value": round(best[tag], 3), "unit": "ms/step",
                         "steps": args.steps, "batch": args.batch,
                         "hidden": args.hidden, "rounds": args.rounds,
                         "checkpoint_every": args.every,
                         "checkpoints_kept": runners[tag].saves(),
                         "overhead_pct": round(med_overhead[tag], 2),
                         "trajectory_gate": True})
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    slog = steplog.from_env(run_name="exp_checkpoint",
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
    overlap = next(r for r in rows if "overlap" in r["metric"])
    sync = next(r for r in rows if "sync" in r["metric"])
    print("SUMMARY overlap_overhead_pct=%.2f sync_overhead_pct=%.2f "
          "gate_lt_5pct=%s" % (overlap["overhead_pct"],
                               sync["overhead_pct"],
                               overlap["overhead_pct"] < 5.0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
