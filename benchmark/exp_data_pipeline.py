"""Input-pipeline A/B experiment (paddle_tpu.data, docs/data.md).

Two audited A/B families on the north-star sequence shapes:

* **Feed A/B** — the SAME fixed-seed training run with the synchronous
  feed vs the pipelined DeviceFeeder (`trainer.SGD.train
  feed_pipeline=`): steady-state ms/step plus the feed time charged to
  the step thread (sync: conversion; pipelined: queue stall). The loss
  trajectories are asserted IDENTICAL before any row is emitted — a
  speedup that changes the math is not a speedup.
* **Padding A/B** — padded (per-batch max, the historical behavior) vs
  length-bucketed vs packed batch assembly over the tagging and NMT
  length distributions (imikolov-style log-normal skew): padding-waste
  percent (pad tokens / total padded slots). Host-side arithmetic —
  the waste is a property of batch assembly, not the device.

Every row passes ``benchmark.harness.sanitize_bench_row`` and mirrors
into the telemetry steplog as ``bench_row`` when PADDLE_TPU_TELEMETRY
is set (the regression-gate contract shared with benchmark/run.py:
``cli observe --regress`` gates the mirrored rows; ``ms/step`` and
``pct_waste`` are lower-better units in observe/regress.py).

Usage:
  python benchmark/exp_data_pipeline.py                 # both families
  python benchmark/exp_data_pipeline.py --steps 30 --batch 32
  python benchmark/exp_data_pipeline.py --skip-feed     # padding only
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _tagging_samples(n, seed, vocab=3000, labels=67, mean=2.8, sigma=0.7,
                     max_len=120):
    """Variable-length tagging samples with realistic (log-normal)
    length skew — the conll05/imikolov shape family."""
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        ln = 2 + min(int(rng.lognormal(mean, sigma)), max_len - 2)
        out.append((rng.randint(0, vocab, ln).astype(np.int32).tolist(),
                    rng.randint(0, labels, ln).astype(np.int32).tolist()))
    return out


def _build_tagging_trainer(vocab, labels, hidden):
    import paddle_tpu as paddle
    from paddle_tpu import data_type as dt, layer as L
    from paddle_tpu import optimizer as opt
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.parameters import Parameters

    reset_name_counters()
    word = L.data(name="word", type=dt.integer_value_sequence(vocab))
    emb = L.embedding(input=word, size=32)
    proj = L.fc(input=emb, size=3 * hidden)
    gru = L.grumemory(input=proj, size=hidden)
    scores = L.fc(input=gru, size=labels)
    label = L.data(name="label", type=dt.integer_value_sequence(labels))
    cost = L.classification_cost(input=scores, label=label)
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost, params, opt.Momentum(learning_rate=1e-3, momentum=0.9))
    return trainer


def measure_feed_ab(steps, batch, vocab=3000, labels=67, hidden=64):
    """One fixed-seed train run per feed mode; rows carry steady-state
    ms/step + the per-step feed time charged to the step thread."""
    import paddle_tpu as paddle
    from paddle_tpu import minibatch

    samples = _tagging_samples(steps * batch, seed=0, vocab=vocab,
                               labels=labels)

    def run(feed_pipeline):
        trainer = _build_tagging_trainer(vocab, labels, hidden)
        losses, walls = [], []
        t_last = [None]

        def handler(e):
            if isinstance(e, paddle.event.EndIteration):
                losses.append(e.cost)
                now = time.perf_counter()
                if t_last[0] is not None:
                    walls.append((now - t_last[0]) * 1e3)
                t_last[0] = now

        trainer.train(minibatch.batch(lambda: iter(samples), batch),
                      num_passes=1, event_handler=handler,
                      feed_pipeline=feed_pipeline,
                      buckets=[16, 32, 64, 128])
        # steady state: drop the first interval (compile)
        tail = walls[1:] or walls
        return losses, sum(tail) / max(len(tail), 1)

    sync_losses, sync_ms = run(False)
    piped_losses, piped_ms = run(True)
    if not np.allclose(sync_losses, piped_losses, rtol=0, atol=0):
        raise AssertionError(
            "pipelined feed changed the fixed-seed loss trajectory: "
            "sync %r vs pipelined %r" % (sync_losses[:3], piped_losses[:3]))
    shape = "tagging_bs%d" % batch
    return [
        {"metric": "data_feed_sync_%s" % shape, "value": round(sync_ms, 3),
         "unit": "ms/step", "steps": len(sync_losses), "batch": batch,
         "feed": "sync"},
        {"metric": "data_feed_pipelined_%s" % shape,
         "value": round(piped_ms, 3), "unit": "ms/step",
         "steps": len(piped_losses), "batch": batch, "feed": "pipelined",
         "loss_trajectory_identical": True},
    ]


def measure_padding_ab(n_samples, batch, shape_name, mean, sigma, max_len,
                       pack_len):
    """Padded vs bucketed vs packed waste over one length distribution.
    Pure host arithmetic via the same assembly code paths training uses
    (minibatch.batch + bucket_length, rebucket_batches, packed_batches).
    """
    from paddle_tpu import minibatch
    from paddle_tpu.core.sequence import bucket_length
    from paddle_tpu.data import bucketing

    samples = _tagging_samples(n_samples, seed=1, mean=mean, sigma=sigma,
                               max_len=max_len)

    def waste_of(batches, padded_len_of):
        fill = pad = 0
        for b in batches:
            padded = padded_len_of(b)
            f, p = bucketing.batch_waste(b, padded)
            fill += f
            pad += p
        return 100.0 * pad / max(fill + pad, 1)

    padded = waste_of(
        list(minibatch.batch(lambda: iter(samples), batch)()),
        lambda b: bucket_length(max(len(s[0]) for s in b)))
    bucketed_batches = list(bucketing.rebucket_batches(
        minibatch.batch(lambda: iter(samples), batch), buckets=None)())
    bucketed = waste_of(bucketed_batches, lambda b: b.bucket)
    packed_rows = []
    for pb in bucketing.packed_batches(lambda: iter(samples), batch,
                                       pack_len)():
        packed_rows.extend(pb)
    pack_fill = sum(len(s[0]) for row in packed_rows for s in row)
    pack_slots = len(packed_rows) * pack_len
    packed = 100.0 * (pack_slots - pack_fill) / max(pack_slots, 1)
    rows = []
    for mode, value, extra in (
            ("padded", padded, {}),
            ("bucketed", bucketed,
             {"buckets": sorted({b.bucket for b in bucketed_batches})}),
            ("packed", packed, {"pack_len": pack_len,
                                "rows": len(packed_rows),
                                "sequences": len(samples)})):
        row = {"metric": "data_padding_waste_%s_%s" % (mode, shape_name),
               "value": round(value, 2), "unit": "pct_waste",
               "samples": n_samples, "batch": batch}
        row.update(extra)
        rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=20,
                    help="train steps per feed-A/B run")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--samples", type=int, default=4096,
                    help="samples per padding-A/B distribution")
    ap.add_argument("--skip-feed", action="store_true",
                    help="padding A/B only (no device work)")
    args = ap.parse_args(argv)

    from benchmark.harness import sanitize_bench_row
    from paddle_tpu.utils import compile_cache
    from paddle_tpu.observe import steplog

    compile_cache.enable()
    rows = []
    if not args.skip_feed:
        rows += measure_feed_ab(args.steps, args.batch)
    # tagging: conll05-ish lengths; nmt: wmt14-ish longer sentences
    rows += measure_padding_ab(args.samples, args.batch, "tagging",
                               mean=2.8, sigma=0.7, max_len=120,
                               pack_len=128)
    rows += measure_padding_ab(args.samples, args.batch, "nmt",
                               mean=3.2, sigma=0.6, max_len=220,
                               pack_len=256)

    slog = steplog.from_env(run_name="exp_data_pipeline",
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
    waste = {r["metric"]: r["value"] for r in rows
             if r["unit"] == "pct_waste"}
    bucketed_win = (waste.get("data_padding_waste_bucketed_tagging", 1e9)
                    < waste.get("data_padding_waste_padded_tagging", 0))
    print("SUMMARY bucketed_beats_padded_on_tagging=%s" % bucketed_win)
    return 0


if __name__ == "__main__":
    sys.exit(main())
