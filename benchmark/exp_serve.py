"""Serving throughput/latency experiments over the paddle_tpu.serve
tier (docs/serving.md).

Three modes, all emitting audited JSON rows through
``benchmark.harness.sanitize_bench_row`` (serving invariants: p99 < p50
or qps <= 0 REJECT the row), mirrored into telemetry as ``bench_row``
when PADDLE_TPU_TELEMETRY is set, and gated against the checked-in
audited set via ``observe/regress.py`` (warn-only by default,
``PADDLE_TPU_BENCH_GATE=hard`` fails):

* ``--mode closed`` (default) — the PR 3 closed-loop MLP measurement:
  N concurrent submitters against the dynamic-batching engine.
* ``--mode openloop-ab`` — the continuous-batching acceptance A/B: ONE
  fixed-seed open-loop arrival trace (Poisson arrivals at
  ``--arrival-qps``, heavy-tailed lognormal lengths — the skewed load
  where whole-request batching drowns in padding) replayed against
  (a) the whole-request engine padding every sequence to the exported
  seq_len, and (b) the continuous-batching scheduler streaming the
  same recurrent bundle through its slot matrix. Gates asserted BEFORE
  any row emits: sustained qps >= ``--min-speedup`` x the baseline
  (default 3.0) at equal-or-better p99.
* ``--mode priority`` — the mixed two-model shed run: a high-priority
  model at a sustainable rate plus a low-priority flood through one
  Router. Gates: the LOW model sheds (>0, counted in metrics +
  ``serve_shed`` records), the HIGH model sheds nothing, and the high
  p99 under the flood stays within ``--p99-tol-pct`` of its solo run.
* ``--mode replicas-ab`` — the replica-scaling acceptance A/B
  (serve/fleet.py): ONE fixed-seed open-loop trace replayed against
  (a) a single continuous scheduler and (b) an N-replica
  :class:`ReplicaSet` of shared-nothing schedulers across the visible
  devices (run under ``XLA_FLAGS=--xla_force_host_platform_device_
  count=N`` on a CPU host). Gates asserted BEFORE any row emits:
  replica-vs-single numeric equivalence on a probe sequence through
  EVERY replica; fleet warmup mints <= replicas x the single-replica
  compile count and the serving phase mints ZERO compiles
  (``watch_compiles``); sustained qps >= the speedup gate at
  equal-or-better p99. The gate defaults to the full 3.0x of the
  acceptance criterion, auto-derated to ``0.75 x min(replicas,
  cpu_count)`` when the host has fewer cores than replicas — the same
  75% parallel efficiency the full bar encodes, at the achievable
  width (``--replicas-min-speedup`` overrides; the row records both
  the gate used and the core count so the audit sees the derating).

* ``--mode workers-ab`` — the multi-process data-plane A/B
  (serve/workers.py, docs/serving.md "Worker processes"): the SAME
  seeded request population against an in-process :class:`ReplicaSet`
  and a multi-process :class:`WorkerSet` at matched width, plus a
  single-scheduler capacity baseline. Gates asserted BEFORE any row
  emits: 1e-6 equivalence through EVERY worker process, zero
  post-warmup compiles inside any worker (the in-worker
  ``watch_compiles`` reading over control RPC), the shm ring never
  drops a request, and sustained qps >= ``0.9 x min(workers, cores)``
  (capped at the 3.6x acceptance bar) vs the single scheduler —
  informational on hosts below 2 cores, with the derate recorded in
  the row (``--workers-min-speedup`` overrides).

* ``--mode quant-ab`` — the quantized-bundle A/B (docs/serving.md
  "Quantized bundles"): one set of mlp parameters exported fp AND
  int8, gated on accuracy (argmax agreement + bounded logit drift),
  footprint (manifest ``hbm_estimate_bytes`` shrink >= 3x and a
  bigger replicas-that-fit under a fixed budget) and zero post-warmup
  compiles; emits qps rows for both sides plus audited ``bytes`` /
  ``replicas`` capacity rows.

* ``--mode trace-overhead`` — the request-scoped tracing A/B
  (docs/observability.md "Request tracing & tail attribution"): the
  SAME closed-loop load through two identical engines, one with
  ``PADDLE_TPU_TRACE_SAMPLE=0`` and one sampling at ``--trace-sample``
  (default 0.1), measurement passes interleaved and best-of-N per side
  (min-of-N convention). Gates asserted BEFORE any row emits: zero
  post-warmup compiles on either side (tracing is host-side only), the
  traced side actually sampled traces, and tracing-on stays within
  ``--trace-tol-pct`` (default 3%) of tracing-off qps AND p99 — the
  "observability is free enough to leave on" claim, audited.

* ``--mode sessions`` — the session-tier A/B (docs/serving.md "Session
  tier & paging"): ONE fixed-seed think-time trace with sessions >>
  ``decode_slots`` (each session decodes chunks with think gaps
  between them) against (a) the hard admission cap, where a live
  session pins its slot for life and overflow 429s, and (b) the paged
  session tier spilling quiescent carries to the host store. Gates
  before any row emits: paging bitwise-correct vs the whole-sequence
  decode, zero post-warmup compiles, the paged side serves every
  session, the cap bites on the baseline, and the mean spill
  device_get stays under the mean window dispatch (the overlap claim).

* ``--mode slo-ab`` — the self-tuning acceptance A/B (docs/control.md):
  one shifting open-loop trace against a hand-tuned engine and an
  identical engine started with a deliberately WRONG batch deadline,
  the SLO controller closing the loop over its knob registry. Gates
  before any row emits: the controller moved the deadline knob, the
  converged side lands within ``--slo-tol-pct`` (default 10%) of the
  hand-tuned qps AND p99, zero post-warmup compiles (knobs are
  host-side by contract), and every move is present as an additive
  ``control_action`` steplog record.

Usage:
  python benchmark/exp_serve.py                       # closed-loop MLP
  python benchmark/exp_serve.py --mode openloop-ab
  python benchmark/exp_serve.py --mode priority
  python benchmark/exp_serve.py --mode quant-ab
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmark/exp_serve.py --mode replicas-ab --replicas 4
"""

import argparse
import gc
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _export_demo_bundle(out_dir, batch_sizes):
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.export import export_bundle

    reset_name_counters()
    out = mlp()
    params = Parameters.create(out)
    export_bundle(out, params, out_dir, batch_sizes=batch_sizes,
                  name="mnist_mlp")
    return out_dir


def _export_quant_pair(fp_dir, q_dir, batch_sizes):
    """ONE set of mlp parameters exported twice: as the fp bundle and
    as its int8-quantized twin — the A/B pair of --mode quant-ab."""
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.export import export_bundle

    reset_name_counters()
    out = mlp()
    params = Parameters.create(out)
    export_bundle(out, params, fp_dir, batch_sizes=batch_sizes,
                  name="mnist_mlp")
    export_bundle(out, params, q_dir, batch_sizes=batch_sizes,
                  name="mnist_mlp_int8", quantize="int8")
    return fp_dir, q_dir


def _export_tagger_bundle(out_dir, batch_sizes, seq_len, slots, window,
                          hidden, name="tagger"):
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.text import sequence_tagging_gru
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.export import export_bundle

    reset_name_counters()
    out = sequence_tagging_gru(dict_size=1000, label_size=32,
                               emb_size=32, hidden=hidden)
    params = Parameters.create(out)
    export_bundle(out, params, out_dir, batch_sizes=batch_sizes,
                  seq_len=seq_len, name=name, decode_slots=(slots,),
                  decode_window=window)
    return out_dir


def run_closed_loop(engine, bundle, clients, requests, rows_per_request,
                    rng):
    """The shared closed-loop client driver: ``clients`` threads each
    running ``requests // clients`` inferences over 8 pre-built random
    payloads. Returns ``(latencies_ms ndarray, wall_s)`` — the default
    mode and quant-ab both drive their engines through this one loop,
    so the timing convention cannot silently diverge between modes."""
    spec = bundle.inputs[0]
    shape = (rows_per_request,) + tuple(
        bundle.feed_shape(spec, rows_per_request)[1:])
    payloads = [
        {spec["name"]: rng.randn(*shape).astype(spec["dtype"])}
        for _ in range(8)]
    per_client = requests // clients
    latencies, lat_lock = [], threading.Lock()

    def client(cid):
        mine = []
        for i in range(per_client):
            t0 = time.perf_counter()
            engine.infer(payloads[(cid + i) % len(payloads)], timeout=120.0)
            mine.append((time.perf_counter() - t0) * 1e3)
        with lat_lock:
            latencies.extend(mine)

    threads = [threading.Thread(target=client, args=(c,),
                                name="serve-bench-client-%d" % c)
               for c in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t_start
    return np.asarray(latencies), wall_s


def measure(bundle_dir, clients, requests, rows_per_request,
            max_latency_ms):
    from paddle_tpu.serve import InferenceEngine, load_bundle

    bundle = load_bundle(bundle_dir)
    engine = InferenceEngine(bundle, max_latency_ms=max_latency_ms)
    lat, wall_s = run_closed_loop(engine, bundle, clients, requests,
                                  rows_per_request,
                                  np.random.RandomState(0))
    stats = engine.stats()
    engine.stop()
    return {
        "metric": "serve_mlp_qps_c%d" % clients,
        "value": round(len(lat) / wall_s, 2),
        "unit": "qps",
        "p50_ms": round(float(np.percentile(lat, 50)), 3),
        "p99_ms": round(float(np.percentile(lat, 99)), 3),
        "requests": int(len(lat)),
        "batches": int(stats.get("batches", 0)),
        "rows_per_request": rows_per_request,
        "clients": clients,
        "max_batch": stats["max_batch_size"],
        "max_latency_ms": stats["max_latency_ms"],
        "wall_s": round(wall_s, 3),
    }


# -- open-loop machinery -----------------------------------------------------

def arrival_trace(requests, qps, seed, mean_len, seq_len, vocab=1000):
    """ONE reproducible open-loop load: Poisson arrival offsets (s) and
    heavy-tailed (lognormal sigma=0.8) sequence lengths in
    [1, seq_len]. The same (seed, requests, qps, mean_len) always
    replays the same trace — A and B see identical work."""
    rng = np.random.RandomState(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / float(qps),
                                         size=requests))
    lengths = np.clip(
        np.rint(rng.lognormal(np.log(mean_len), 0.8, size=requests)),
        1, seq_len).astype(np.int64)
    seqs = [rng.randint(0, vocab, size=(int(k),)).astype(np.int32)
            for k in lengths]
    return arrivals, seqs


def sustained_qps(completions, lo=0.1, hi=0.9):
    """Throughput over the CENTRAL completion window (default: 10th to
    90th percentile completion times). ``N / wall`` is hostage to the
    drain tail — one long sequence admitted last decodes alone for its
    full remaining length, stretching the wall with near-zero
    completions — while the central slope measures the system at
    sustained load; both A/B sides of an experiment get the identical
    treatment."""
    cs = sorted(completions)
    if not cs:
        raise ValueError(
            "no completions to measure — every request shed or failed")
    i_lo, i_hi = int(len(cs) * lo), min(int(len(cs) * hi),
                                        len(cs) - 1)
    if i_hi <= i_lo or cs[i_hi] <= cs[i_lo]:
        return len(cs) / max(cs[-1], 1e-9)
    return (i_hi - i_lo) / (cs[i_hi] - cs[i_lo])


def drive_open_loop(submit_fn, arrivals):
    """Replay an open-loop schedule: request i is dispatched at
    ``arrivals[i]`` seconds after start REGARDLESS of completions (the
    no-coordinated-omission convention: latency counts from the
    SCHEDULED arrival, so queueing delay is charged to the system, not
    hidden by a slow client). Returns (latencies_ms, wall_s, shed,
    completion_times_s)."""
    from paddle_tpu.serve import Overloaded

    t0 = time.perf_counter()
    lock = threading.Lock()
    latencies, completions = [], []
    futures = []
    shed = 0
    i = 0
    n = len(arrivals)
    while i < n:
        now = time.perf_counter() - t0
        # submit EVERY due request, then sleep one coarse tick: per-
        # request sleeps would wake 1000+/s against the serving
        # worker's GIL and throttle the offered rate below schedule
        while i < n and arrivals[i] <= now:
            t_arr = arrivals[i]
            try:
                fut = submit_fn(i)
            except Overloaded:
                shed += 1
                i += 1
                continue

            def _done(f, t_sched=float(t_arr)):
                t_c = time.perf_counter() - t0
                with lock:
                    completions.append(t_c)
                    latencies.append((t_c - t_sched) * 1e3)

            fut.add_done_callback(_done)
            futures.append(fut)
            i += 1
        if i < n:
            time.sleep(min(max(arrivals[i] - (time.perf_counter() - t0),
                               0.0), 0.005))
    for fut in futures:
        fut.result(timeout=600.0)
    with lock:
        wall_s = max(completions) if completions else 0.0
        lat = list(latencies)
        done = list(completions)
    return lat, wall_s, shed, done


def _percentiles(lat):
    lat = np.asarray(lat)
    return (round(float(np.percentile(lat, 50)), 3),
            round(float(np.percentile(lat, 99)), 3))


def measure_openloop_ab(args):
    """The continuous-batching acceptance A/B on one recurrent bundle
    under one skewed open-loop trace."""
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import (ContinuousScheduler, InferenceEngine,
                                  load_bundle)

    bundle_dir = args.bundle or _export_tagger_bundle(
        tempfile.mkdtemp(prefix="serve_tagger_"),
        tuple(int(b) for b in args.batch_sizes.split(",")),
        args.seq_len, args.decode_slots, args.decode_window, args.hidden)
    bundle = load_bundle(bundle_dir)
    seq_len = bundle.seq_len
    arrivals, seqs = arrival_trace(args.requests, args.arrival_qps,
                                   args.seed, args.mean_len, seq_len)

    # A: whole-request batching — every sequence pads to seq_len
    engine = InferenceEngine(bundle, max_latency_ms=args.max_latency_ms,
                             metrics_registry=MetricsRegistry(),
                             model="tagger_batch")
    padded = []
    for s in seqs:
        ids = np.zeros((1, seq_len), np.int32)
        ids[0, :len(s)] = s
        padded.append({"word": ids,
                       "word:lens": np.array([len(s)], np.int32)})
    lat_a, wall_a, _, _ = drive_open_loop(
        lambda i: engine.submit(padded[i]), arrivals)
    engine.stop()

    # B: continuous batching — the same trace through the slot matrix
    sched = ContinuousScheduler(bundle, metrics_registry=MetricsRegistry(),
                                model="tagger_cont", max_queue=None)
    lat_b, wall_b, _, _ = drive_open_loop(
        lambda i: sched.submit({"word": seqs[i]}), arrivals)
    cont_stats = sched.stats()
    sched.stop()

    qps_a, qps_b = len(lat_a) / wall_a, len(lat_b) / wall_b
    p50_a, p99_a = _percentiles(lat_a)
    p50_b, p99_b = _percentiles(lat_b)
    speedup = qps_b / qps_a

    # the acceptance gates run BEFORE any row emits: a failed gate
    # publishes nothing
    if args.min_speedup > 0:
        assert speedup >= args.min_speedup, (
            "continuous batching gate FAILED: %.2fx sustained qps "
            "(%.1f vs %.1f), need >= %.1fx"
            % (speedup, qps_b, qps_a, args.min_speedup))
        assert p99_b <= p99_a, (
            "continuous batching gate FAILED: p99 %.1fms worse than "
            "whole-request %.1fms" % (p99_b, p99_a))

    base = {
        "unit": "qps", "requests": args.requests,
        "offered_qps": args.arrival_qps, "seed": args.seed,
        "mean_len": args.mean_len, "seq_len": seq_len,
        "arrivals": "poisson", "lengths": "lognormal_s0.8",
    }
    row_a = dict(base, metric="serve_batch_tagger_qps",
                 value=round(qps_a, 2), p50_ms=p50_a, p99_ms=p99_a,
                 wall_s=round(wall_a, 3), mode="whole_request")
    row_b = dict(base, metric="serve_cont_tagger_qps",
                 value=round(qps_b, 2), p50_ms=p50_b, p99_ms=p99_b,
                 wall_s=round(wall_b, 3), mode="continuous",
                 slots=cont_stats["slots"], window=cont_stats["window"],
                 iterations=cont_stats["iterations"],
                 slot_steps=cont_stats["slot_steps"],
                 speedup_vs_batch=round(speedup, 2))
    return [row_a, row_b]


def measure_replicas_ab(args):
    """The replica-scaling acceptance A/B: one skewed open-loop trace
    against a single continuous scheduler vs an N-replica fleet of
    shared-nothing schedulers over the same bundle."""
    from paddle_tpu.observe import steplog as observe_steplog
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import (ContinuousScheduler, ReplicaSet,
                                  load_bundle)

    bundle_dir = args.bundle or _export_tagger_bundle(
        tempfile.mkdtemp(prefix="serve_tagger_"),
        tuple(int(b) for b in args.batch_sizes.split(",")),
        args.seq_len, args.decode_slots, args.decode_window, args.hidden)
    bundle = load_bundle(bundle_dir)
    out_name = bundle.outputs[0]["name"]
    n = args.replicas
    # the FIXED request population: lengths/contents from the seeded
    # trace machinery (arrival offsets are derived per phase below)
    _, seqs = arrival_trace(args.requests, args.arrival_qps, args.seed,
                            args.mean_len, bundle.seq_len)
    burst = np.zeros(len(seqs))  # all due at t=0: capacity phase

    # A: ONE scheduler (the PR 8 shape), warmup compile count recorded
    # as the per-replica budget for the fleet's warmup gate below
    with observe_steplog.watch_compiles() as w_single:
        single = ContinuousScheduler(bundle,
                                     metrics_registry=MetricsRegistry(),
                                     model="tagger", max_queue=None)
    single_compiles = max(w_single.compiles, 1)
    probe = seqs[0]
    want = single.infer({"word": probe}, timeout=600.0)[out_name]
    # capacity phase: every request submitted up front, sustained qps =
    # central completion slope, best of N passes (the min-of-N timing
    # convention: noise on a shared host only ever SLOWS a pass). On a
    # shared bench host an open-loop driver competes with the servers
    # for cores/GIL mid-measurement (in production the clients are
    # other machines); the burst pays the submit cost BEFORE the
    # measurement window.
    def capacity(submit_fn):
        best = 0.0
        for _ in range(args.capacity_passes):
            _, _, _, done = drive_open_loop(submit_fn, burst)
            best = max(best, sustained_qps(done))
        return best

    qps_a = capacity(lambda i: single.submit({"word": seqs[i]}))
    # latency phase: one seeded open-loop Poisson replay at a rate the
    # single replica can sustain (0.6x its measured capacity) — the
    # SAME offered rate both sides, per the p99 acceptance clause
    offered = 0.6 * qps_a
    lat_rng = np.random.RandomState(args.seed + 1)
    lat_arrivals = np.cumsum(lat_rng.exponential(1.0 / offered,
                                                 size=len(seqs)))
    lat_a, _, _, _ = drive_open_loop(
        lambda i: single.submit({"word": seqs[i]}), lat_arrivals)
    single.stop()

    # B: the N-replica fleet over the SAME bundle
    with observe_steplog.watch_compiles() as w_fleet:
        fleet = ReplicaSet(bundle, replicas=n, continuous=True,
                           metrics_registry=MetricsRegistry(),
                           model="tagger",
                           engine_kwargs={"max_queue": None},
                           warmup=True)
    # gate 1 (before ANY row): replica-vs-single numeric equivalence —
    # the probe sequence through EVERY replica's own engine must match
    # the single scheduler's output
    for member in fleet.replicas():
        got = member.engine.infer({"word": probe},
                                  timeout=600.0)[out_name]
        np.testing.assert_allclose(
            got, want, atol=1e-6,
            err_msg="replica %d diverges from the single scheduler"
                    % member.index)
    # gate 2: replica count mints compiles only at warmup, and at most
    # N x the single-replica count
    assert w_fleet.compiles <= n * single_compiles, (
        "fleet warmup compiled %d programs > %d replicas x %d single"
        % (w_fleet.compiles, n, single_compiles))
    with observe_steplog.watch_compiles() as w_serve:
        qps_b = capacity(lambda i: fleet.submit({"word": seqs[i]}))
        lat_b, _, _, _ = drive_open_loop(
            lambda i: fleet.submit({"word": seqs[i]}), lat_arrivals)
    fleet_stats = fleet.stats()
    fleet.stop()
    # gate 3: zero compiles after warmup, across all replica churn
    assert w_serve.compiles == 0, (
        "replica dispatch minted %d post-warmup compiles: %s"
        % (w_serve.compiles, w_serve.events))

    p50_a, p99_a = _percentiles(lat_a)
    p50_b, p99_b = _percentiles(lat_b)
    speedup = qps_b / qps_a

    # gate 4: sustained-capacity multiplier, plus p99 no worse at the
    # matched offered rate. The acceptance bar is 3.0x at 4 replicas —
    # 75% parallel efficiency; a CPU host with fewer cores than
    # replicas cannot honestly multiply past its core count, so the
    # auto gate demands the SAME 75% efficiency at the achievable
    # width: 0.75 x min(replicas, cores), capped at 3.0 (recorded in
    # the row; --replicas-min-speedup pins an explicit bar, 0
    # disables).
    cores = os.cpu_count() or 1
    min_speedup = args.replicas_min_speedup
    if min_speedup < 0:
        min_speedup = min(3.0, 0.75 * min(n, cores))
    # p99 clause: no worse than single-replica at the matched offered
    # rate. On independent devices more capacity can only shorten the
    # queue, so the full clause applies whenever the host keeps a spare
    # core beyond the replica count. When forced CPU "devices" SHARE
    # cores with each other and the driver (cores <= replicas), each
    # concurrent dispatch inflates every other's service time — an
    # emulation artifact real chips don't have — so the clause relaxes
    # to 2x and the row records the relaxation (p99_tol).
    p99_tol = 1.0 if cores > n else 2.0
    if min_speedup > 0:
        assert speedup >= min_speedup, (
            "replica scaling gate FAILED: %.2fx sustained qps "
            "(%.1f vs %.1f at %d replicas), need >= %.2fx"
            % (speedup, qps_b, qps_a, n, min_speedup))
        assert p99_b <= p99_a * p99_tol, (
            "replica scaling gate FAILED: fleet p99 %.1fms vs "
            "single-replica %.1fms at the same offered rate "
            "(tolerance %.1fx)" % (p99_b, p99_a, p99_tol))

    base = {
        "unit": "qps", "requests": args.requests,
        "offered_qps": round(offered, 1), "seed": args.seed,
        "mean_len": args.mean_len, "seq_len": bundle.seq_len,
        "arrivals": "burst_capacity+poisson_latency",
        "lengths": "lognormal_s0.8",
        "cpu_count": cores, "hidden": args.hidden,
        "slots": args.decode_slots, "window": args.decode_window,
    }
    row_a = dict(base, metric="serve_single_tagger_qps",
                 value=round(qps_a, 2), p50_ms=p50_a, p99_ms=p99_a,
                 mode="single_replica",
                 warmup_compiles=single_compiles)
    row_b = dict(base, metric="serve_fleet_tagger_qps",
                 value=round(qps_b, 2), p50_ms=p50_b, p99_ms=p99_b,
                 mode="replica_fleet",
                 replicas=n, devices=len(set(fleet_stats["devices"])),
                 speedup_vs_single=round(speedup, 2),
                 gate_speedup=round(min_speedup, 2),
                 p99_tol=round(p99_tol, 1),
                 warmup_compiles=w_fleet.compiles,
                 serve_compiles=w_serve.compiles)
    return [row_a, row_b]


def measure_workers_ab(args):
    """The multi-process data-plane A/B (docs/serving.md "Worker
    processes"): the same seeded request population against an
    in-process :class:`ReplicaSet` and a multi-process
    :class:`WorkerSet` at MATCHED replica count over the same tagger
    bundle, with a single-scheduler capacity baseline for the scaling
    gate. Gates asserted BEFORE any row emits:

    1. equivalence — the probe sequence through EVERY worker process
       matches the single scheduler to 1e-6;
    2. zero post-warmup compiles in any worker (the in-worker
       ``watch_compiles`` reading over control RPC, diffed across the
       measured phase);
    3. the ring never drops — every dispatched request completes and
       nothing sheds during the measured burst;
    4. scaling — sustained qps >= 0.9x ideal (``0.9 x min(workers,
       cores)``, capped at the 4-worker acceptance bar 3.6x) vs the
       single scheduler. A host without at least 2 cores cannot
       honestly demonstrate multi-process scaling, so the gate derates
       to informational there; the derate is recorded in the row
       (``gate_speedup``/``cpu_count``). ``--workers-min-speedup``
       pins an explicit bar, 0 disables.
    """
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import (ContinuousScheduler, ReplicaSet,
                                  load_bundle)
    from paddle_tpu.serve.workers import WorkerSet

    bundle_dir = args.bundle or _export_tagger_bundle(
        tempfile.mkdtemp(prefix="serve_tagger_"),
        tuple(int(b) for b in args.batch_sizes.split(",")),
        args.seq_len, args.decode_slots, args.decode_window, args.hidden)
    bundle = load_bundle(bundle_dir)
    out_name = bundle.outputs[0]["name"]
    n = args.workers
    _, seqs = arrival_trace(args.requests, args.arrival_qps, args.seed,
                            args.mean_len, bundle.seq_len)
    burst = np.zeros(len(seqs))

    def capacity(submit_fn):
        best = 0.0
        for _ in range(args.capacity_passes):
            _, _, drops, done = drive_open_loop(submit_fn, burst)
            assert drops == 0, "capacity burst shed %d requests" % drops
            best = max(best, sustained_qps(done))
        return best

    # baseline: ONE in-process scheduler — the denominator of the
    # scaling gate and the numeric reference for the equivalence gate
    single = ContinuousScheduler(bundle,
                                 metrics_registry=MetricsRegistry(),
                                 model="tagger", max_queue=None)
    probe = seqs[0]
    want = single.infer({"word": probe}, timeout=600.0)[out_name]
    qps_single = capacity(lambda i: single.submit({"word": seqs[i]}))
    offered = 0.6 * qps_single
    lat_rng = np.random.RandomState(args.seed + 1)
    lat_arrivals = np.cumsum(lat_rng.exponential(1.0 / offered,
                                                 size=len(seqs)))
    single.stop()

    # A: the in-process replica fleet at width n (the PR 12 shape —
    # N engines, ONE interpreter, so router + engines share the GIL)
    fleet = ReplicaSet(bundle, replicas=n, continuous=True,
                       metrics_registry=MetricsRegistry(),
                       model="tagger",
                       engine_kwargs={"max_queue": None}, warmup=True)
    qps_replicas = capacity(lambda i: fleet.submit({"word": seqs[i]}))
    lat_a, _, _, _ = drive_open_loop(
        lambda i: fleet.submit({"word": seqs[i]}), lat_arrivals)
    fleet.stop()

    # B: the multi-process worker fleet at the SAME width
    workers = WorkerSet(bundle, workers=n, continuous=True,
                        engine_kwargs={"max_queue": None},
                        metrics_registry=MetricsRegistry(),
                        model="tagger")
    try:
        workers.wait_ready(timeout=600.0)
        # gate 1: probe through EVERY worker process, 1e-6 vs single
        for index in range(n):
            got = workers.submit_to(index, {"word": probe}).result(
                timeout=600.0)[out_name]
            np.testing.assert_allclose(
                got, want, atol=1e-6,
                err_msg="worker %d diverges from the single scheduler"
                        % index)
        compiles_before = workers.compile_counts()
        qps_workers = capacity(lambda i: workers.submit(
            {"word": seqs[i]}))
        lat_b, _, _, _ = drive_open_loop(
            lambda i: workers.submit({"word": seqs[i]}), lat_arrivals)
        compiles_after = workers.compile_counts()
        wstats = workers.stats()
    finally:
        workers.stop()
    # gate 2: the measured phase minted zero compiles in any worker
    assert compiles_after == compiles_before, (
        "worker dispatch minted post-warmup compiles: %r -> %r"
        % (compiles_before, compiles_after))
    # gate 3: the ring never drops — every dispatch completed, no sheds
    router = wstats["router"]
    assert router["completed"] == router["dispatched"], (
        "ring dropped requests: %d dispatched vs %d completed"
        % (router["dispatched"], router["completed"]))
    assert wstats.get("shed", 0) == 0, (
        "worker engines shed %d requests during the measured burst"
        % wstats.get("shed", 0))

    # gate 4: scaling vs the single scheduler, derated to the host
    cores = os.cpu_count() or 1
    ideal = min(n, cores)
    min_speedup = args.workers_min_speedup
    if min_speedup < 0:
        min_speedup = min(3.6, 0.9 * ideal) if ideal >= 2 else 0.0
    speedup = qps_workers / qps_single
    if min_speedup > 0:
        assert speedup >= min_speedup, (
            "worker scaling gate FAILED: %.2fx sustained qps "
            "(%.1f vs %.1f at %d workers), need >= %.2fx"
            % (speedup, qps_workers, qps_single, n, min_speedup))

    p50_a, p99_a = _percentiles(lat_a)
    p50_b, p99_b = _percentiles(lat_b)
    base = {
        "unit": "qps", "requests": args.requests,
        "offered_qps": round(offered, 1), "seed": args.seed,
        "mean_len": args.mean_len, "seq_len": bundle.seq_len,
        "arrivals": "burst_capacity+poisson_latency",
        "lengths": "lognormal_s0.8",
        "cpu_count": cores, "hidden": args.hidden,
        "slots": args.decode_slots, "window": args.decode_window,
        "single_qps": round(qps_single, 2),
    }
    row_a = dict(base, metric="serve_replicaset_tagger_qps",
                 value=round(qps_replicas, 2),
                 p50_ms=p50_a, p99_ms=p99_a,
                 mode="inprocess_replicas", replicas=n,
                 speedup_vs_single=round(qps_replicas / qps_single, 2))
    row_b = dict(base, metric="serve_workerset_tagger_qps",
                 value=round(qps_workers, 2),
                 p50_ms=p50_b, p99_ms=p99_b,
                 mode="worker_processes", workers=n,
                 transport="shm_ring",
                 speedup_vs_single=round(speedup, 2),
                 speedup_vs_replicas=round(
                     qps_workers / max(qps_replicas, 1e-9), 2),
                 gate_speedup=round(min_speedup, 2),
                 serve_compiles=0)
    return [row_a, row_b]


def measure_quant_ab(args):
    """The quantized-bundle serving A/B (docs/serving.md "Quantized
    bundles"): ONE set of mlp parameters exported fp and int8, both
    served through identical closed-loop engines. Gates asserted BEFORE
    any row emits: (1) accuracy — argmax agreement >= --quant-min-agree
    and max logit drift <= --quant-max-drift on a seeded probe batch;
    (2) footprint — the int8 manifest ``hbm_estimate_bytes`` shrinks
    >= --quant-min-shrink x vs fp, and under the reference
    --hbm-budget the int8 bundle fits MORE replicas (serve/fleet
    .replicas_that_fit); (3) zero post-warmup compiles on either side
    (``watch_compiles``). The qps delta is recorded, not gated: on a
    CPU host the dequant multiply costs FLOPs it saves in HBM reads —
    the bandwidth win is the on-chip rerun's to prove (ROADMAP
    Queue 1 #12)."""
    from paddle_tpu.analyze.topology_check import hbm_budget_bytes
    from paddle_tpu.observe import steplog as observe_steplog
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import InferenceEngine, load_bundle
    from paddle_tpu.serve.fleet import replicas_that_fit

    # buckets (1, 8), not the closed-loop default (1, 8, 32): the
    # manifest estimate includes the largest bucket's per-dispatch
    # feed+activation workspace, which is IDENTICAL on both sides —
    # a 32-row bucket dilutes the params shrink the capacity chain
    # (replicas-that-fit) actually banks on
    # --bundle is ignored here on purpose: the A/B pair must share ONE
    # set of parameters, so both sides export fresh from the same init
    fp_dir, q_dir = _export_quant_pair(
        tempfile.mkdtemp(prefix="serve_quant_fp_"),
        tempfile.mkdtemp(prefix="serve_quant_int8_"), (1, 8))
    fp_bundle, q_bundle = load_bundle(fp_dir), load_bundle(q_dir)

    # gate 1: the accuracy gate — fp and int8 must agree on the probe
    rng = np.random.RandomState(args.seed)
    rows_max = fp_bundle.max_batch()
    probe = rng.randn(rows_max, 784).astype(np.float32)
    out_fp = fp_bundle.infer({"pixel": probe})["mlp_out"]
    out_q = q_bundle.infer({"pixel": probe})["mlp_out"]
    agree = float(np.mean(out_fp.argmax(1) == out_q.argmax(1)))
    drift = float(np.abs(out_fp - out_q).max())
    assert agree >= args.quant_min_agree, (
        "quantization accuracy gate FAILED: argmax agreement %.3f < "
        "%.3f" % (agree, args.quant_min_agree))
    assert drift <= args.quant_max_drift, (
        "quantization accuracy gate FAILED: max logit drift %.4f > "
        "%.4f" % (drift, args.quant_max_drift))

    # gate 2: the capacity chain — smaller manifest estimate, more
    # replicas under the same budget
    est_fp = int(fp_bundle.manifest["hbm_estimate_bytes"])
    est_q = int(q_bundle.manifest["hbm_estimate_bytes"])
    shrink = est_fp / est_q
    assert shrink >= args.quant_min_shrink, (
        "quantization footprint gate FAILED: hbm_estimate_bytes "
        "shrank %.2fx (%d -> %d), need >= %.1fx"
        % (shrink, est_fp, est_q, args.quant_min_shrink))
    budget = hbm_budget_bytes(env=args.hbm_budget)
    if budget is None:
        raise SystemExit(
            "--hbm-budget %r did not parse (want PADDLE_TPU_HBM_BUDGET "
            "syntax, e.g. 4M / 16G / plain bytes)" % args.hbm_budget)
    fit_fp = replicas_that_fit(fp_bundle, budget)
    fit_q = replicas_that_fit(q_bundle, budget)
    assert fit_q > fit_fp, (
        "quantization capacity gate FAILED: int8 fits %d replicas vs "
        "fp %d under budget %s" % (fit_q, fit_fp, args.hbm_budget))

    def closed_loop(bundle):
        """Closed-loop qps/latency on one side through the shared
        driver, with the post-warmup compile gate (the replicas-ab
        convention)."""
        engine = InferenceEngine(bundle,
                                 max_latency_ms=args.max_latency_ms,
                                 metrics_registry=MetricsRegistry(),
                                 warmup=True)
        with observe_steplog.watch_compiles() as watch:
            lat, wall_s = run_closed_loop(engine, bundle, args.clients,
                                          args.requests,
                                          args.rows_per_request, rng)
        engine.stop()
        # gate 3: a warm quantized engine must serve exactly like a
        # warm fp engine — zero compiles in the measured phase
        assert watch.compiles == 0, (
            "quant-ab %s side minted %d post-warmup compiles: %s"
            % (bundle.name, watch.compiles, watch.events))
        p50, p99 = _percentiles(lat)
        return len(lat) / wall_s, p50, p99, wall_s

    qps_fp, p50_fp, p99_fp, wall_fp = closed_loop(fp_bundle)
    qps_q, p50_q, p99_q, wall_q = closed_loop(q_bundle)

    base = {
        "unit": "qps", "requests": args.requests,
        "clients": args.clients,
        "rows_per_request": args.rows_per_request, "seed": args.seed,
    }
    row_fp = dict(base, metric="serve_quant_fp_qps",
                  value=round(qps_fp, 2), p50_ms=p50_fp, p99_ms=p99_fp,
                  wall_s=round(wall_fp, 3), mode="fp32")
    row_q = dict(base, metric="serve_quant_int8_qps",
                 value=round(qps_q, 2), p50_ms=p50_q, p99_ms=p99_q,
                 wall_s=round(wall_q, 3), mode="int8",
                 speedup_vs_fp=round(qps_q / qps_fp, 2),
                 argmax_agreement=round(agree, 4),
                 max_logit_drift=round(drift, 5),
                 serve_compiles=0)
    row_hbm = {"metric": "serve_quant_hbm_int8_bytes", "value": est_q,
               "unit": "bytes", "fp_bytes": est_fp,
               "shrink_vs_fp": round(shrink, 2),
               "scheme": q_bundle.quantization["scheme"]}
    row_fit = {"metric": "serve_quant_replicas_fit", "value": fit_q,
               "unit": "replicas", "fp_fit": fit_fp,
               "budget": args.hbm_budget,
               "delta_vs_fp": fit_q - fit_fp}
    return [row_fp, row_q, row_hbm, row_fit]


# -- session-tier machinery (--mode sessions) --------------------------------

def session_trace(sessions, chunks_per, mean_len, think_ms, ramp_s, seed,
                  vocab=1000):
    """ONE reproducible multi-session conversation load: ``sessions``
    users, each decoding ``chunks_per`` request chunks of lognormal
    lengths with exponential think-time gaps between them (the gap
    counts from the PREVIOUS chunk's completion — a user reads the
    reply, thinks, types). Session starts stagger uniformly over
    ``ramp_s`` seconds. The same seed always replays the same trace, so
    the hard-cap baseline and the paged session tier see identical
    work."""
    rng = np.random.RandomState(seed)
    starts = np.sort(rng.uniform(0.0, ramp_s, size=sessions))
    chunks, thinks = [], []
    for _ in range(sessions):
        lens = np.clip(np.rint(rng.lognormal(np.log(mean_len), 0.6,
                                             size=chunks_per)),
                       1, 4 * int(mean_len)).astype(np.int64)
        chunks.append([rng.randint(0, vocab, size=(int(k),))
                       .astype(np.int32) for k in lens])
        thinks.append(rng.exponential(think_ms / 1e3,
                                      size=chunks_per - 1))
    return starts, chunks, thinks


def drive_session_trace(submit_fn, starts, chunks, thinks,
                        close_fn=None):
    """Replay a session trace: chunk 0 of session i is due at
    ``starts[i]``; chunk c+1 is due at chunk c's completion plus the
    session's think gap (latency counts from the DUE time, the
    no-coordinated-omission convention). A shed or gone chunk fails the
    whole session (its user got an error mid-conversation), skips its
    remaining chunks and ABORTS the session through ``close_fn`` —
    exactly what a real front end does, and what keeps a hard-cap
    baseline from leaking zombie slots to failed sessions. Returns
    (latencies_ms, completion_times_s, outputs {session: [chunk
    arrays]}, failed session count)."""
    import heapq

    from paddle_tpu.serve import Overloaded, SessionGone

    n = len(chunks)
    total = sum(len(c) for c in chunks)
    lock = threading.Lock()
    heap = [(float(starts[i]), i, 0) for i in range(n)]
    heapq.heapify(heap)
    latencies, completions = [], []
    outputs = {i: [] for i in range(n)}
    failed = set()
    remaining = [total]
    done_evt = threading.Event()
    t0 = time.perf_counter()

    def account(k=1):
        remaining[0] -= k
        if remaining[0] <= 0:
            done_evt.set()

    while True:
        with lock:
            if not heap:
                if remaining[0] <= 0:
                    break
                next_due = None
            else:
                next_due = heap[0][0]
        now = time.perf_counter() - t0
        if next_due is None or next_due > now:
            if done_evt.wait(timeout=0.002):
                with lock:
                    if not heap:
                        break
            continue
        with lock:
            due, i, c = heapq.heappop(heap)
        is_last = c == len(chunks[i]) - 1
        try:
            fut = submit_fn(i, chunks[i][c], is_last)
        except (Overloaded, SessionGone):
            with lock:
                failed.add(i)
                account(len(chunks[i]) - c)
            if close_fn is not None:
                close_fn(i)
            continue

        def _done(f, i=i, c=c, due=due, is_last=is_last):
            t_c = time.perf_counter() - t0
            try:
                out = f.result()
            except Exception:  # noqa: BLE001 — the gate reads `failed`
                with lock:
                    failed.add(i)
                    account(len(chunks[i]) - c)
                if close_fn is not None:
                    close_fn(i)
                return
            with lock:
                completions.append(t_c)
                latencies.append((t_c - due) * 1e3)
                outputs[i].append(next(iter(out.values())))
                if not is_last:
                    gap = float(thinks[i][c])
                    heapq.heappush(heap, (t_c + gap, i, c + 1))
                account()

        fut.add_done_callback(_done)
    return latencies, completions, outputs, len(failed)


def measure_sessions(args):
    """The session-tier acceptance A/B (docs/serving.md "Session tier &
    paging"): ONE fixed-seed think-time trace with sessions >>
    decode_slots replayed against (a) the **hard admission cap** — the
    pre-session scheduler semantic where a live session pins its slot
    for life (``paging=False``) and everyone past the slots+queue bound
    is 429'd — and (b) the **paged session tier**, where quiescent
    sessions spill to the host store and restore on their next chunk.

    Gates asserted BEFORE any row emits:

    1. paging correctness — probe sessions' concatenated chunk outputs
       match the whole-sequence decode bitwise-level (atol 0);
    2. zero post-warmup compiles through all paging churn
       (``watch_compiles``);
    3. the paged side serves EVERY session (no sheds, no failures);
    4. the hard cap bites on the same trace (>=1 session shed) —
       ``--require-cap-bite 0`` relaxes for tiny smoke runs;
    5. swap overhead: the mean spill device_get (overlapped on the
       writer thread) is cheaper than the mean window dispatch, so
       paging rides inside the dispatch the scheduler was already
       paying."""
    from paddle_tpu.observe import steplog as observe_steplog
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import ContinuousScheduler, load_bundle

    bundle_dir = args.bundle or _export_tagger_bundle(
        tempfile.mkdtemp(prefix="serve_tagger_"),
        tuple(int(b) for b in args.batch_sizes.split(",")),
        args.seq_len, args.decode_slots, args.decode_window, args.hidden)
    bundle = load_bundle(bundle_dir)
    out_name = bundle.outputs[0]["name"]
    in_name = bundle.inputs[0]["name"]
    slots = args.decode_slots
    assert args.sessions > slots, (
        "--mode sessions wants sessions >> decode_slots (got %d vs %d)"
        % (args.sessions, slots))
    starts, chunks, thinks = session_trace(
        args.sessions, args.chunks_per_session, args.mean_len,
        args.think_ms, args.session_ramp_s, args.seed)

    # A: the hard admission cap (the slot matrix IS the session table)
    hard = ContinuousScheduler(
        bundle, metrics_registry=MetricsRegistry(), model="tagger_hard",
        paging=False, max_queue=args.hardcap_queue)
    lat_a, done_a, _, failed_a = drive_session_trace(
        lambda i, chunk, last: hard.submit(
            {in_name: chunk}, session_id="s%d" % i, end_session=last),
        starts, chunks, thinks,
        close_fn=lambda i: hard.close_session("s%d" % i))
    hard_stats = hard.stats()
    hard.stop()

    # B: the paged session tier over the same trace (unbounded queue:
    # paging, not shedding, is the admission policy under test)
    paged = ContinuousScheduler(
        bundle, metrics_registry=MetricsRegistry(), model="tagger_paged",
        paging=True, max_queue=None,
        session_capacity=args.session_store,
        idle_spill_ms=args.idle_spill_ms)
    with observe_steplog.watch_compiles() as watch:
        lat_b, done_b, outs_b, failed_b = drive_session_trace(
            lambda i, chunk, last: paged.submit(
                {in_name: chunk}, session_id="s%d" % i, end_session=last),
            starts, chunks, thinks,
            close_fn=lambda i: paged.close_session("s%d" % i))
    paged_stats = paged.stats()
    paged.stop()

    # gate 1: paging correctness — probe sessions bitwise vs the
    # whole-sequence decode through a fresh sessionless scheduler
    probe_ids = sorted({0, len(chunks) // 2, len(chunks) - 1})
    check = ContinuousScheduler(bundle,
                                metrics_registry=MetricsRegistry(),
                                model="tagger_check", max_queue=None)
    for i in probe_ids:
        whole = check.infer({in_name: np.concatenate(chunks[i])},
                            timeout=600.0)[out_name]
        got = np.concatenate(outs_b[i], axis=0)
        assert got.shape == whole.shape and np.array_equal(got, whole), (
            "session tier gate FAILED: probe session %d diverges from "
            "its whole-sequence decode after paging" % i)
    check.stop()
    # gate 2: paging churn minted zero post-warmup compiles
    assert watch.compiles == 0, (
        "session tier gate FAILED: paging minted %d post-warmup "
        "compiles: %s" % (watch.compiles, watch.events))
    # gate 3: the paged side served EVERY session
    assert failed_b == 0 and paged_stats["shed"] == 0, (
        "session tier gate FAILED: paged side failed %d sessions, "
        "shed %d requests" % (failed_b, paged_stats["shed"]))
    assert paged_stats["spills"] > 0 and paged_stats["restores"] > 0, (
        "session tier gate FAILED: trace never exercised paging "
        "(%d spills / %d restores) — raise --sessions or shrink "
        "--decode-slots" % (paged_stats["spills"],
                            paged_stats["restores"]))
    # gate 4: the hard cap actually bit on this trace
    if args.require_cap_bite:
        assert failed_a > 0 or hard_stats["shed"] > 0, (
            "session tier gate FAILED: the hard-cap baseline shed "
            "nothing — the trace does not exceed the cap; raise "
            "--sessions or --think-ms")
    # gate 5: swap overhead < window dispatch time (the overlap claim)
    spill_ms = (paged_stats.get("spill_get_ms_sum", 0.0)
                / max(paged_stats["spills"], 1))
    iter_ms = (paged_stats.get("iter_ms_sum", 0.0)
               / max(paged_stats["iterations"], 1))
    assert spill_ms < iter_ms, (
        "session tier gate FAILED: mean spill device_get %.3fms >= "
        "mean window dispatch %.3fms — the copy no longer hides "
        "inside the dispatch" % (spill_ms, iter_ms))

    # the hard cap always serves its slot-resident sessions, so both
    # sides have completions; an empty side is a broken measurement and
    # sustained_qps raises on it
    p50_a, p99_a = _percentiles(lat_a)
    p50_b, p99_b = _percentiles(lat_b)
    total_requests = sum(len(c) for c in chunks)
    base = {
        "unit": "qps", "sessions": args.sessions, "slots": slots,
        "window": args.decode_window, "seq_len": args.seq_len,
        "chunks_per_session": args.chunks_per_session,
        "think_ms": args.think_ms, "mean_len": args.mean_len,
        "seed": args.seed, "requests": total_requests,
        "hidden": args.hidden,
    }
    row_a = dict(base, metric="serve_sessions_hardcap_qps",
                 value=round(sustained_qps(done_a), 2),
                 p50_ms=p50_a, p99_ms=p99_a,
                 mode="hard_cap", completed=len(done_a),
                 sessions_failed=failed_a,
                 shed=int(hard_stats["shed"]),
                 max_queue=args.hardcap_queue)
    row_b = dict(base, metric="serve_sessions_paged_qps",
                 value=round(sustained_qps(done_b), 2),
                 p50_ms=p50_b, p99_ms=p99_b,
                 mode="paged", completed=len(done_b),
                 sessions_failed=failed_b,
                 spills=int(paged_stats["spills"]),
                 restores=int(paged_stats["restores"]),
                 evictions=int(paged_stats["evictions"]),
                 spill_get_ms_mean=round(spill_ms, 3),
                 iter_ms_mean=round(iter_ms, 3),
                 store_capacity=args.session_store,
                 serve_compiles=watch.compiles)
    return [row_a, row_b]


def measure_hosts_ab(args):
    """The multi-host serving acceptance drill (docs/serving.md
    "Multi-host serving"): a 2-host MULTI-PROCESS fleet — ``cli serve
    --join`` OS processes behind the coordinator, all paging against
    ONE shared remote-store process — driven through the fleet-of-
    fleets front with a fixed-seed think-time session trace, then one
    host SIGKILLed mid-conversation (between committed chunks: every
    acked chunk was spilled to the shared store before its reply, so
    the kill lands in think-time where the only session state is the
    committed one). Gates asserted BEFORE any row emits:

    1. zero committed sessions lost — EVERY conversation's
       concatenated pre+post-kill outputs equal the in-process
       whole-sequence decode BITWISE (float32 survives the JSON hop
       exactly), and no session errors in any phase;
    2. chaos p99 < ``--hosts-p99-factor`` x the steady-state p99 — the
       rehome penalty is a bounded blip, not a stall;
    3. zero post-warmup compiles on the survivors across the chaos
       window (``GET /debug/compiles`` diff) — re-homed sessions
       restore into already-compiled programs.
    """
    import concurrent.futures

    from paddle_tpu.distributed.client import (
        CoordinatorClient, spawn_coordinator_on_free_port)
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import ContinuousScheduler, load_bundle
    from paddle_tpu.serve.cluster import ClusterFront

    bundle_dir = args.bundle or _export_tagger_bundle(
        tempfile.mkdtemp(prefix="serve_tagger_"),
        tuple(int(b) for b in args.batch_sizes.split(",")),
        args.seq_len, args.decode_slots, args.decode_window, args.hidden)
    bundle = load_bundle(bundle_dir)
    in_name = bundle.inputs[0]["name"]
    out_name = bundle.outputs[0]["name"]
    sessions = args.hosts_sessions
    n_hosts = args.serve_hosts
    assert n_hosts >= 2, "--mode hosts-ab needs >= 2 hosts to kill one"
    assert args.chunks_per_session >= 2, (
        "--mode hosts-ab kills MID-conversation: need >= 2 chunks")
    starts, chunks, thinks = session_trace(
        sessions, args.chunks_per_session, args.mean_len,
        args.think_ms, args.session_ramp_s, args.seed)

    # the bitwise reference: each conversation decoded whole, in one
    # process — what the cluster must reproduce across the kill
    ref = ContinuousScheduler(bundle, metrics_registry=MetricsRegistry(),
                              model="tagger_ref", max_queue=None)
    whole = {i: ref.infer({in_name: np.concatenate(chunks[i])},
                          timeout=600.0)[out_name]
             for i in range(sessions)}
    ref.stop()

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONUNBUFFERED="1",
               PYTHONPATH=(repo_root + os.pathsep
                           + os.environ.get("PYTHONPATH", "")))
    env.pop("PADDLE_TPU_TELEMETRY", None)  # hosts log to their own runs
    # this process exported the bundle and ran the reference, so on a
    # TPU host it holds the chips the serving hosts would need
    from paddle_tpu.core.place import enforce_children_can_open_devices

    enforce_children_can_open_devices(
        n_hosts, "exp_serve --mode hosts-ab", env=env)
    port, coord = spawn_coordinator_on_free_port()
    endpoint = "127.0.0.1:%d" % port
    store = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serve.remote_store",
         "--port", "0", "--capacity", str(args.session_store)],
        stdout=subprocess.PIPE, text=True, env=env)
    procs, front, pool = {}, None, None
    try:
        line = store.stdout.readline().strip()
        assert line.startswith("listening "), (
            "remote store failed to start: %r" % line)
        store_addr = line.split()[-1]
        for i in range(n_hosts):
            hid = "h%d" % i
            procs[hid] = subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.cli", "serve",
                 bundle_dir, "--continuous", "--port", "0",
                 "--join", endpoint, "--host-id", hid,
                 "--lease-ttl", "5",
                 "--session-store-addr", store_addr],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=env)
        client = CoordinatorClient(endpoint, worker_id="hosts_ab",
                                   retry_timeout=5.0)
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            if len(client.serve_hosts()["hosts"]) == n_hosts:
                break
            for hid, p in procs.items():
                assert p.poll() is None, "host %s died at startup" % hid
            time.sleep(0.5)
        else:
            raise AssertionError("hosts never joined the coordinator")
        client.close()
        front = ClusterFront(endpoint=endpoint, poll_interval=0.2,
                             metrics_registry=MetricsRegistry(),
                             host_timeout=10.0, request_timeout=60.0)
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline and not front.ready():
            time.sleep(0.5)
        assert front.ready(), "hosts never warmed"

        pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(sessions, 4),
            thread_name_prefix="hosts-ab-client")

        def submit(i, chunk, end):
            return pool.submit(front.infer, {in_name: chunk},
                               timeout=120.0, session_id="s%d" % i,
                               end_session=end)

        # steady phase: the FIRST half of every conversation, fleet
        # intact — its latencies are the p99 baseline, and every acked
        # chunk is committed to the shared store before its reply
        mid = max(1, args.chunks_per_session // 2)
        pre = [c[:mid] for c in chunks]
        post = [c[mid:] for c in chunks]
        pre_thinks = [t[:mid - 1] for t in thinks]
        post_thinks = [t[mid:] for t in thinks]
        lat_steady, _, outs_pre, failed_pre = drive_session_trace(
            lambda i, c, last: submit(i, c, False),
            starts, pre, pre_thinks)
        assert failed_pre == 0, (
            "steady phase failed %d sessions" % failed_pre)

        # kill the host holding the most conversations, in think-time
        # (no chunk in flight: the steady trace drained) — the drill's
        # whole point is that committed carries outlive their host
        homes = {i: front._session_last.get("s%d" % i)
                 for i in range(sessions)}
        by_host = {}
        for i, h in homes.items():
            by_host.setdefault(h, []).append(i)
        victim = max(sorted(by_host), key=lambda h: len(by_host[h]))
        hosts_map, _ = front._snapshot()
        compiles_before = {
            hid: e.host.compiles() for hid, e in hosts_map.items()
            if hid != victim and e.live}
        os.kill(procs[victim].pid, signal.SIGKILL)
        procs[victim].wait(timeout=60)

        # chaos phase: the SECOND half of every conversation — the
        # victim's sessions re-home onto survivors from the store
        lat_chaos, _, outs_post, failed_chaos = drive_session_trace(
            lambda i, c, last: submit(i, c, last),
            starts, post, post_thinks)
        assert failed_chaos == 0, (
            "chaos phase failed %d sessions — committed sessions were "
            "lost with the host" % failed_chaos)
        compiles_after = {
            hid: e.host.compiles()
            for hid, e in front._snapshot()[0].items()
            if hid in compiles_before and e.live}
        stats = front.stats()
    finally:
        if pool is not None:
            pool.shutdown(wait=False)
        if front is not None:
            front.stop()
        for p in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs.values():
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
        store.terminate()
        store.wait(timeout=10)
        coord.terminate()
        coord.wait(timeout=10)

    # gate 1: zero committed sessions lost, bitwise
    for i in range(sessions):
        got = np.concatenate(outs_pre[i] + outs_post[i], axis=0)
        assert got.shape == whole[i].shape and np.array_equal(
            got, whole[i]), (
            "session s%d diverges after the kill: the cluster lost "
            "committed state" % i)
    assert stats["session_rehomes"] >= 1, (
        "the kill re-homed nothing — the drill did not exercise "
        "failover (victim %r held %d sessions)"
        % (victim, len(by_host.get(victim, ()))))
    # gate 2: the rehome penalty is bounded
    p50_s, p99_s = _percentiles(lat_steady)
    p50_c, p99_c = _percentiles(lat_chaos)
    factor = p99_c / max(p99_s, 1e-9)
    assert factor < args.hosts_p99_factor, (
        "chaos p99 %.1f ms is %.2fx steady p99 %.1f ms (gate %.1fx): "
        "failover stalls the fleet" % (p99_c, factor, p99_s,
                                       args.hosts_p99_factor))
    # gate 3: survivors minted zero compiles across the chaos window
    assert compiles_after == compiles_before, (
        "chaos window minted compiles on survivors: %r -> %r"
        % (compiles_before, compiles_after))

    base = {
        "unit": "ms", "sessions": sessions,
        "chunks_per_session": args.chunks_per_session,
        "think_ms": args.think_ms, "mean_len": args.mean_len,
        "seq_len": bundle.seq_len, "seed": args.seed,
        "hidden": args.hidden, "slots": args.decode_slots,
        "window": args.decode_window, "transport": "http_json",
        "store": "remote_process",
    }
    row_steady = dict(base, metric="serve_cluster_steady_p99_ms",
                      value=p99_s, p50_ms=p50_s, p99_ms=p99_s,
                      mode="hosts_steady", hosts=n_hosts)
    row_chaos = dict(base, metric="serve_cluster_chaos_p99_ms",
                     value=p99_c, p50_ms=p50_c, p99_ms=p99_c,
                     mode="hosts_chaos", hosts=n_hosts - 1,
                     session_rehomes=stats["session_rehomes"],
                     p99_vs_steady=round(factor, 2),
                     gate_p99_factor=args.hosts_p99_factor,
                     committed_sessions_lost=0, serve_compiles=0)
    return [row_steady, row_chaos]


def measure_trace_overhead(args):
    """The tracing-overhead A/B: identical engines over one bundle,
    tracing off vs sampling at ``--trace-sample``, driven by the shared
    closed-loop client loop. Passes are INTERLEAVED (off, on, off, on,
    ...) so host drift hits both sides equally, and each side keeps its
    best pass whole — highest sustained qps with THAT pass's p50/p99
    (min-of-N: shared-host noise only ever slows a pass; folding the
    metrics independently would publish a pair no pass achieved).
    Both engines write real steplogs (flush_every=32, the serving
    default) to a scratch dir, so the traced side pays the full
    production cost — context mint, phase spans, the sampled
    ``serve_trace`` records and the always-on exemplar offers."""
    from paddle_tpu.observe import steplog as observe_steplog
    from paddle_tpu.observe import tracing as observe_tracing
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import InferenceEngine, load_bundle

    bundle_dir = args.bundle or _export_demo_bundle(
        tempfile.mkdtemp(prefix="serve_trace_"),
        tuple(int(b) for b in args.batch_sizes.split(",")))
    bundle = load_bundle(bundle_dir)
    slog_dir = tempfile.mkdtemp(prefix="serve_trace_slog_")

    def build(tag):
        return InferenceEngine(
            bundle, max_latency_ms=args.max_latency_ms,
            metrics_registry=MetricsRegistry(), warmup=True,
            steplog=observe_steplog.StepLog(slog_dir, run_name=tag,
                                            flush_every=32))

    engine_off, engine_on = build("trace_off"), build("trace_on")
    prev = os.environ.get("PADDLE_TPU_TRACE_SAMPLE")

    def one_pass(engine, rate, rng):
        if rate > 0:
            os.environ["PADDLE_TPU_TRACE_SAMPLE"] = repr(rate)
        else:
            os.environ.pop("PADDLE_TPU_TRACE_SAMPLE", None)
        # a full collection of the interpreter's garbage takes tens of
        # ms over jax's heap: made here it does not land on one side's
        # slowest request, which a short pass's p99 is
        gc.collect()
        lat, wall_s = run_closed_loop(engine, bundle, args.clients,
                                      args.requests,
                                      args.rows_per_request, rng)
        p50, p99 = _percentiles(lat)
        return len(lat) / wall_s, p50, p99

    # each side keeps its best pass WHOLE (highest sustained qps, that
    # pass's own p50/p99 riding along) — folding qps and p99 minima
    # independently would publish a (qps, p99) pair no real pass
    # achieved
    best = {"off": (0.0, float("inf"), float("inf")),
            "on": (0.0, float("inf"), float("inf"))}
    sampled_before = observe_tracing.sampled_count()
    try:
        with observe_steplog.watch_compiles() as watch:
            for p in range(args.trace_passes):
                # same seeded payload stream per (side, pass) pair
                for side, engine, rate in (
                        ("off", engine_off, 0.0),
                        ("on", engine_on, args.trace_sample)):
                    rng = np.random.RandomState(args.seed + p)
                    result = one_pass(engine, rate, rng)
                    if result[0] > best[side][0]:
                        best[side] = result
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_TRACE_SAMPLE", None)
        else:
            os.environ["PADDLE_TPU_TRACE_SAMPLE"] = prev
        engine_off.stop()
        engine_on.stop()
    traced = observe_tracing.sampled_count() - sampled_before

    # gates BEFORE any row emits
    assert watch.compiles == 0, (
        "trace-overhead gate FAILED: the measured phase minted %d "
        "compiles (tracing must be host-side only): %s"
        % (watch.compiles, watch.events))
    assert traced > 0, (
        "trace-overhead gate FAILED: the traced side sampled nothing "
        "at rate %.3f over %d requests x %d passes"
        % (args.trace_sample, args.requests, args.trace_passes))
    qps_off, p50_off, p99_off = best["off"]
    qps_on, p50_on, p99_on = best["on"]
    tol = args.trace_tol_pct / 100.0
    assert qps_on >= qps_off * (1.0 - tol), (
        "trace-overhead gate FAILED: tracing-on qps %.1f more than "
        "%.1f%% under tracing-off %.1f"
        % (qps_on, args.trace_tol_pct, qps_off))
    assert p99_on <= p99_off * (1.0 + tol), (
        "trace-overhead gate FAILED: tracing-on p99 %.2fms more than "
        "%.1f%% over tracing-off %.2fms"
        % (p99_on, args.trace_tol_pct, p99_off))

    base = {
        "unit": "qps", "requests": args.requests,
        "clients": args.clients,
        "rows_per_request": args.rows_per_request, "seed": args.seed,
        "passes": args.trace_passes,
    }
    row_off = dict(base, metric="serve_trace_off_qps",
                   value=round(qps_off, 2), p50_ms=p50_off,
                   p99_ms=p99_off, mode="tracing_off")
    row_on = dict(base, metric="serve_trace_on_qps",
                  value=round(qps_on, 2), p50_ms=p50_on, p99_ms=p99_on,
                  mode="tracing_on", sample_rate=args.trace_sample,
                  traced=int(traced),
                  overhead_qps_pct=round(
                      100.0 * (qps_off - qps_on) / qps_off, 2),
                  overhead_p99_pct=round(
                      100.0 * (p99_on - p99_off) / p99_off, 2),
                  gate_tol_pct=args.trace_tol_pct,
                  serve_compiles=watch.compiles)
    return [row_off, row_on]


def measure_health_overhead(args):
    """The health-plane overhead A/B: identical engines over one
    bundle, windowed health history + burn-rate SLO monitor ON vs the
    recorder disabled, driven by the shared closed-loop client loop.
    Same discipline as the trace-overhead mode: passes are INTERLEAVED
    so host drift hits both sides equally, each side keeps its best
    pass whole, and zero post-warmup compiles is a hard gate (the
    recorder is host-side only by contract — observe/health.py is
    lint-hot). The on side pays the full production cost: per-request
    window updates on every submit/retire AND the monitor's periodic
    fleet evaluation thread running throughout the pass."""
    from paddle_tpu.observe import health as observe_health
    from paddle_tpu.observe import steplog as observe_steplog
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import InferenceEngine, load_bundle

    bundle_dir = args.bundle or _export_demo_bundle(
        tempfile.mkdtemp(prefix="serve_health_"),
        tuple(int(b) for b in args.batch_sizes.split(",")))
    bundle = load_bundle(bundle_dir)
    slog_dir = tempfile.mkdtemp(prefix="serve_health_slog_")

    def build(tag):
        return InferenceEngine(
            bundle, max_latency_ms=args.max_latency_ms,
            metrics_registry=MetricsRegistry(), warmup=True,
            steplog=observe_steplog.StepLog(slog_dir, run_name=tag,
                                            flush_every=32))

    engine_off, engine_on = build("health_off"), build("health_on")
    history = observe_health.get_history()
    monitor = observe_health.SloMonitor(
        [engine_on], p99_ms=args.health_slo_p99_ms, interval_s=0.2)

    best = {"off": (0.0, float("inf"), float("inf")),
            "on": (0.0, float("inf"), float("inf"))}
    requests_before = history.snapshot()["totals"]["requests"]
    try:
        with observe_steplog.watch_compiles() as watch:
            for p in range(args.health_passes):
                # same seeded payload stream per (side, pass) pair; the
                # monitor thread runs ONLY during on passes — leaving
                # it up would slow the off side and flatter the A/B
                for side, engine, enabled in (
                        ("off", engine_off, False),
                        ("on", engine_on, True)):
                    rng = np.random.RandomState(args.seed + p)
                    history.set_enabled(enabled)
                    if enabled:
                        monitor.start()
                    lat, wall_s = run_closed_loop(
                        engine, bundle, args.clients, args.requests,
                        args.rows_per_request, rng)
                    if enabled:
                        monitor.stop()
                    p50, p99 = _percentiles(lat)
                    result = (len(lat) / wall_s, p50, p99)
                    if result[0] > best[side][0]:
                        best[side] = result
        history.set_enabled(True)
        verdict = monitor.evaluate()
    finally:
        monitor.stop()
        history.set_enabled(True)
        engine_off.stop()
        engine_on.stop()
    recorded = (history.snapshot()["totals"]["requests"]
                - requests_before)

    # gates BEFORE any row emits
    assert watch.compiles == 0, (
        "health-overhead gate FAILED: the measured phase minted %d "
        "compiles (the health recorder must be host-side only): %s"
        % (watch.compiles, watch.events))
    assert recorded > 0, (
        "health-overhead gate FAILED: the on side recorded nothing "
        "into the health history over %d requests x %d passes"
        % (args.requests, args.health_passes))
    assert monitor.evaluations > 0, (
        "health-overhead gate FAILED: the SLO monitor never evaluated "
        "during the on passes (interval 0.2s)")
    qps_off, p50_off, p99_off = best["off"]
    qps_on, p50_on, p99_on = best["on"]
    tol = args.health_tol_pct / 100.0
    assert qps_on >= qps_off * (1.0 - tol), (
        "health-overhead gate FAILED: health-on qps %.1f more than "
        "%.1f%% under health-off %.1f"
        % (qps_on, args.health_tol_pct, qps_off))
    assert p99_on <= p99_off * (1.0 + tol), (
        "health-overhead gate FAILED: health-on p99 %.2fms more than "
        "%.1f%% over health-off %.2fms"
        % (p99_on, args.health_tol_pct, p99_off))

    base = {
        "unit": "qps", "requests": args.requests,
        "clients": args.clients,
        "rows_per_request": args.rows_per_request, "seed": args.seed,
        "passes": args.health_passes,
    }
    row_off = dict(base, metric="serve_health_off_qps",
                   value=round(qps_off, 2), p50_ms=p50_off,
                   p99_ms=p99_off, mode="health_off")
    row_on = dict(base, metric="serve_health_on_qps",
                  value=round(qps_on, 2), p50_ms=p50_on, p99_ms=p99_on,
                  mode="health_on",
                  slo_p99_ms=args.health_slo_p99_ms,
                  recorded=int(recorded),
                  evaluations=int(monitor.evaluations),
                  overhead_qps_pct=round(
                      100.0 * (qps_off - qps_on) / qps_off, 2),
                  overhead_p99_pct=round(
                      100.0 * (p99_on - p99_off) / p99_off, 2),
                  gate_tol_pct=args.health_tol_pct,
                  serve_compiles=watch.compiles)
    # the SLO verdict itself as a gateable row: burn_rate is a
    # lower-better unit (observe/regress.py), so a future change that
    # burns the error budget faster under the same load gates like a
    # latency regression
    row_burn = dict(base, unit="burn_rate",
                    metric="serve_health_fast_burn",
                    value=verdict["burn_rates"]["fast"],
                    slo_state=verdict["state"],
                    slo_p99_ms=args.health_slo_p99_ms,
                    budget_remaining=verdict["budget_remaining"])
    return [row_off, row_on, row_burn]


def measure_slo_ab(args):
    """The self-tuning acceptance A/B (docs/control.md): ONE shifting
    open-loop trace (three Poisson segments at 1.0x/1.6x/0.7x the base
    rate — the load the controller must keep up with) against (a) a
    hand-tuned engine and (b) an identical engine started with a
    deliberately WRONG batch deadline, with the SLO controller closing
    the loop over its knob registry. The wrong deadline holds every
    request open far past the objective, the tail attribution lands on
    ``queue_ms`` (the whole-request engine bills its deadline hold
    there), and the controller's queue family walks down to its only
    registered lever: ``engine.batch_deadline_ms``.

    Gates asserted BEFORE any row emits: the controller actually moved
    the knob (>= 3 moves, ending below the wrong start), the converged
    side lands within ``--slo-tol-pct`` of hand-tuned sustained qps AND
    p99, the whole run (convergence included) mints ZERO post-warmup
    compiles (every knob is host-side by contract — jit shapes are not
    knobs), and every move the controller counted is present as an
    additive ``control_action`` steplog record (the audit trail
    ``cli observe`` prints as the knob-move timeline)."""
    from paddle_tpu.control import Controller, KnobRegistry
    from paddle_tpu.observe import health as observe_health
    from paddle_tpu.observe import steplog as observe_steplog
    from paddle_tpu.observe import tracing as observe_tracing
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import InferenceEngine, load_bundle

    bundle_dir = args.bundle or _export_demo_bundle(
        tempfile.mkdtemp(prefix="serve_slo_"),
        tuple(int(b) for b in args.batch_sizes.split(",")))
    bundle = load_bundle(bundle_dir)
    spec = bundle.inputs[0]
    shape = (1,) + tuple(bundle.feed_shape(spec, 1)[1:])
    rng = np.random.RandomState(args.seed)
    payloads = [{spec["name"]: rng.randn(*shape).astype(spec["dtype"])}
                for _ in range(8)]
    # the shifting schedule: both sides replay the IDENTICAL offsets
    seg_n = max(args.requests // 3, 1)
    segments, t0 = [], 0.0
    for mult in (1.0, 1.6, 0.7):
        offs = t0 + np.cumsum(rng.exponential(
            1.0 / (args.slo_qps * mult), size=seg_n))
        segments.append(offs)
        t0 = float(offs[-1])
    arrivals = np.concatenate(segments)

    slog_dir = tempfile.mkdtemp(prefix="serve_slo_slog_")
    reg_tuned = MetricsRegistry()

    def build(tag, deadline_ms, reg):
        return InferenceEngine(
            bundle, max_latency_ms=deadline_ms, metrics_registry=reg,
            warmup=True,
            steplog=observe_steplog.StepLog(slog_dir, run_name=tag,
                                            flush_every=32))

    engine_hand = build("slo_hand", args.slo_hand_latency_ms,
                        MetricsRegistry())
    engine_tuned = build("slo_tuned", args.slo_wrong_latency_ms,
                         reg_tuned)
    history = observe_health.get_history()
    exemplars = observe_tracing.get_exemplars()

    def replay(engine):
        lat, _, _, done = drive_open_loop(
            lambda i: engine.submit(payloads[i % len(payloads)]),
            arrivals)
        return lat, done

    controller = None
    ctl_slog = None
    history.set_enabled(True)
    try:
        with observe_steplog.watch_compiles() as watch:
            # hand-tuned baseline first: its measured p99 IS the
            # objective the controller must reach (auto mode)
            history.reset()
            exemplars.reset()
            lat_hand, done_hand = replay(engine_hand)
            p50_hand, p99_hand = _percentiles(lat_hand)
            qps_hand = sustained_qps(done_hand)
            objective = args.slo_ab_p99_ms or round(0.8 * p99_hand, 3)

            knobs = KnobRegistry()
            engine_tuned.register_knobs(knobs)
            monitor = observe_health.SloMonitor(
                [engine_tuned], p99_ms=objective, fast_s=2.0,
                slow_s=30.0, interval_s=0.2)
            ctl_slog = observe_steplog.StepLog(
                slog_dir, run_name="slo_control", flush_every=1)
            controller = Controller(
                monitor, knobs, interval_s=0.15,
                cooldown_s=args.slo_cooldown_s, hysteresis=2,
                slog=ctl_slog, registry=reg_tuned, model="slo_tuned")

            # convergence: replay the shifting trace with the control
            # loop live until the monitor reads ok (or rounds run out —
            # the measured A/B below is the acceptance, not the state)
            history.reset()
            exemplars.reset()
            controller.start()
            rounds, verdict = 0, None
            for rounds in range(1, args.slo_rounds + 1):
                replay(engine_tuned)
                verdict = monitor.evaluate()
                if verdict["state"] == "ok":
                    break
            controller.stop()
            convergence_steps = controller.moves
            deadline_knob = knobs.get("engine.batch_deadline_ms")
            converged_ms = deadline_knob.value

            # measurement: knobs frozen at the converged values, same
            # trace again — the side-by-side the gates compare
            history.reset()
            lat_tuned, done_tuned = replay(engine_tuned)
            final_verdict = monitor.evaluate()
    finally:
        if controller is not None:
            controller.stop()
        if ctl_slog is not None:
            ctl_slog.close()
        engine_hand.stop()
        engine_tuned.stop()
    p50_tuned, p99_tuned = _percentiles(lat_tuned)
    qps_tuned = sustained_qps(done_tuned)
    actions = [r for r in observe_steplog.read_jsonl(ctl_slog.path)
               if r.get("type") == "control_action"]

    # gates BEFORE any row emits
    assert watch.compiles == 0, (
        "slo-ab gate FAILED: the control loop minted %d compiles "
        "(knobs must be host-side only — jit shapes are not knobs): %s"
        % (watch.compiles, watch.events))
    assert controller.moves >= 3 and converged_ms < \
        args.slo_wrong_latency_ms, (
        "slo-ab gate FAILED: controller made %d move(s) and left the "
        "deadline at %.2fms (started wrong at %.2fms) — the loop "
        "never closed" % (controller.moves, converged_ms,
                          args.slo_wrong_latency_ms))
    assert len(actions) == controller.moves + controller.rollbacks, (
        "slo-ab gate FAILED: %d control_action records for %d moves + "
        "%d rollbacks — the audit trail lost moves"
        % (len(actions), controller.moves, controller.rollbacks))
    tol = args.slo_tol_pct / 100.0
    assert qps_tuned >= qps_hand * (1.0 - tol), (
        "slo-ab gate FAILED: converged qps %.1f more than %.0f%% "
        "under hand-tuned %.1f" % (qps_tuned, args.slo_tol_pct,
                                   qps_hand))
    assert p99_tuned <= p99_hand * (1.0 + tol), (
        "slo-ab gate FAILED: converged p99 %.2fms more than %.0f%% "
        "over hand-tuned %.2fms" % (p99_tuned, args.slo_tol_pct,
                                    p99_hand))

    base = {
        "unit": "qps", "requests": len(arrivals),
        "offered_qps": args.slo_qps, "seed": args.seed,
        "arrivals": "poisson_shifting_1.0_1.6_0.7",
        "slo_p99_ms": objective,
    }
    row_hand = dict(base, metric="serve_slo_hand_qps",
                    value=round(qps_hand, 2), p50_ms=p50_hand,
                    p99_ms=p99_hand, mode="hand_tuned",
                    max_latency_ms=args.slo_hand_latency_ms)
    row_tuned = dict(base, metric="serve_slo_tuned_qps",
                     value=round(qps_tuned, 2), p50_ms=p50_tuned,
                     p99_ms=p99_tuned, mode="autotuned",
                     start_latency_ms=args.slo_wrong_latency_ms,
                     converged_latency_ms=round(converged_ms, 3),
                     moves=int(controller.moves),
                     rollbacks=int(controller.rollbacks),
                     rounds=int(rounds),
                     slo_state=final_verdict["state"],
                     gate_tol_pct=args.slo_tol_pct,
                     serve_compiles=watch.compiles)
    # convergence cost as an audited lower-better row: a controller
    # change that needs more moves to reach the same objective gates
    # like a latency regression (observe/regress.py)
    row_conv = dict(base, unit="convergence_steps",
                    metric="serve_slo_convergence_steps",
                    value=int(convergence_steps),
                    rounds=int(rounds),
                    converged_latency_ms=round(converged_ms, 3))
    return [row_hand, row_tuned, row_conv]


def measure_priority(args):
    """The mixed two-model shed run: high-priority MLP at a sustainable
    rate, low-priority MLP flooded, one Router. Only low may shed; the
    high p99 must hold vs its solo run."""
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.serve import InferenceEngine, Router, load_bundle

    high_dir = _export_demo_bundle(
        tempfile.mkdtemp(prefix="serve_high_"), (1, 8))
    low_dir = _export_demo_bundle(
        tempfile.mkdtemp(prefix="serve_low_"), (1, 8))
    high_bundle, low_bundle = load_bundle(high_dir), load_bundle(low_dir)
    rng = np.random.RandomState(args.seed)
    payload = {"pixel": rng.randn(1, 784).astype(np.float32)}
    n_high = args.requests
    high_arrivals = np.cumsum(rng.exponential(
        1.0 / args.high_qps, size=n_high))

    def run_high(router):
        return drive_open_loop(
            lambda i: router.submit("high", dict(payload)),
            high_arrivals)

    def build_router(reg, with_low):
        router = Router(metrics_registry=reg,
                        shed_capacity={"high": None, "low": 64})
        router.add_model(
            "high", high_bundle,
            InferenceEngine(high_bundle, max_latency_ms=2.0,
                            metrics_registry=reg, model="high"),
            priority="high")
        if with_low:
            router.add_model(
                "low", low_bundle,
                InferenceEngine(low_bundle, max_latency_ms=2.0,
                                metrics_registry=reg, model="low",
                                max_queue_rows=32),
                priority="low")
        return router

    # solo baseline: high alone on the same schedule
    with build_router(MetricsRegistry(), with_low=False) as router:
        lat_solo, _, _, _ = run_high(router)
    p50_solo, p99_solo = _percentiles(lat_solo)

    # mixed: the low-priority flood runs concurrently
    reg = MetricsRegistry()
    with build_router(reg, with_low=True) as router:
        n_low = args.requests * 4
        low_arrivals = np.cumsum(np.random.RandomState(args.seed + 1)
                                 .exponential(1.0 / args.low_qps,
                                              size=n_low))
        low_result = {}

        def flood_low():
            low_result["res"] = drive_open_loop(
                lambda i: router.submit("low", dict(payload)),
                low_arrivals)

        flooder = threading.Thread(target=flood_low,
                                   name="serve-bench-low-flood")
        flooder.start()
        lat_mixed, _, high_shed, _ = run_high(router)
        flooder.join()
    _, _, low_shed, _ = low_result["res"]
    p50_mixed, p99_mixed = _percentiles(lat_mixed)
    snap = reg.snapshot()["counters"]
    low_shed_counted = sum(v for k, v in snap.items()
                           if k.startswith("paddle_tpu_serve_shed_total")
                           and 'model="low"' in k)

    # gates BEFORE any row emits
    assert low_shed > 0 and low_shed_counted >= low_shed, (
        "priority gate FAILED: the low-priority flood shed nothing "
        "(%d submitted)" % n_low)
    assert high_shed == 0, (
        "priority gate FAILED: %d high-priority sheds" % high_shed)
    tol = 1.0 + args.p99_tol_pct / 100.0
    assert p99_mixed <= p99_solo * tol, (
        "priority gate FAILED: high p99 %.1fms under flood vs %.1fms "
        "solo (tolerance %.0f%%)" % (p99_mixed, p99_solo,
                                     args.p99_tol_pct))

    return [{
        "metric": "serve_priority_high_qps",
        "value": round(len(lat_mixed)
                       / (high_arrivals[-1] + 1e-9), 2),
        "unit": "qps",
        "p50_ms": p50_mixed, "p99_ms": p99_mixed,
        "solo_p50_ms": p50_solo, "solo_p99_ms": p99_solo,
        "requests": n_high, "offered_qps": args.high_qps,
        "low_offered_qps": args.low_qps,
        "low_requests": n_low, "low_shed": int(low_shed),
        "low_shed_pct": round(100.0 * low_shed / n_low, 2),
        "high_shed": int(high_shed), "seed": args.seed,
    }]


def _emit(rows, slog_name):
    """sanitize -> print -> regress-gate -> telemetry-mirror, the
    audited-row contract every bench shares."""
    from benchmark.harness import sanitize_bench_row
    from paddle_tpu.observe import regress as observe_regress
    from paddle_tpu.observe import steplog as observe_steplog

    rows = [sanitize_bench_row(row) for row in rows]
    for row in rows:
        print(json.dumps(row))
    results, regressions = observe_regress.gate_rows(rows)
    for res in results:
        if res["status"] in ("regression", "ok"):
            print(json.dumps({"regress_note":
                              observe_regress.format_result(res)}))
    slog = observe_steplog.from_env(run_name=slog_name,
                                    meta={"phase": "bench"})
    if slog is not None:
        for row in rows:
            slog.write(dict(row, type="bench_row"))
        slog.close()
    if regressions and observe_regress.hard_gate():
        print("bench regression gate: FAILED (%d gated)"
              % len(regressions), file=sys.stderr)
        return 3
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", default="closed",
                    choices=("closed", "openloop-ab", "priority",
                             "replicas-ab", "workers-ab", "quant-ab",
                             "sessions", "trace-overhead",
                             "health-overhead", "slo-ab", "hosts-ab"))
    ap.add_argument("--bundle", default="",
                    help="pre-exported bundle dir (default: export the "
                         "mode's demo bundle to a tmp dir)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--rows-per-request", type=int, default=1)
    ap.add_argument("--max-latency-ms", type=float, default=5.0)
    ap.add_argument("--batch-sizes", default="1,8,32")
    # open-loop / priority knobs
    ap.add_argument("--arrival-qps", type=float, default=2400.0,
                    help="open-loop offered rate (Poisson; the default "
                         "saturates both systems so sustained qps is "
                         "the capacity, not the offered rate)")
    ap.add_argument("--high-qps", type=float, default=300.0,
                    help="priority mode: high-priority offered rate "
                         "(sustainable — its p99 is the thing under "
                         "test)")
    ap.add_argument("--low-qps", type=float, default=6000.0,
                    help="priority mode: low-priority flood rate (well "
                         "past the low model's capacity, so its bounded "
                         "queue must shed)")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival-trace seed (reproducible rows)")
    ap.add_argument("--mean-len", type=float, default=8.0,
                    help="lognormal median sequence length (the heavy "
                         "tail runs to ~p999 of the distribution; "
                         "seq_len must cover it)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--decode-slots", type=int, default=48)
    ap.add_argument("--decode-window", type=int, default=6)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--min-speedup", type=float, default=3.0,
                    help="openloop-ab gate: continuous must sustain "
                         ">= this x the whole-request qps (0 disables)")
    ap.add_argument("--replicas-min-speedup", type=float, default=-1.0,
                    help="replicas-ab gate: fleet must sustain >= this "
                         "x the single-replica qps (0 disables; -1 = "
                         "auto: the 3.0x acceptance bar, derated to "
                         "0.75 x min(replicas, cpu cores) on hosts "
                         "with fewer cores than replicas)")
    ap.add_argument("--replicas", type=int, default=4,
                    help="replicas-ab: fleet width (one shared-nothing "
                         "scheduler per device; force devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    ap.add_argument("--workers", type=int, default=4,
                    help="workers-ab: worker-process fleet width (one "
                         "OS process per replica)")
    ap.add_argument("--workers-min-speedup", type=float, default=-1.0,
                    help="workers-ab gate: the worker fleet must "
                         "sustain >= this x the single-scheduler qps "
                         "(0 disables; -1 = auto: the 3.6x acceptance "
                         "bar, derated to 0.9 x min(workers, cpu "
                         "cores), informational below 2 cores)")
    ap.add_argument("--capacity-passes", type=int, default=2,
                    help="replicas-ab: burst passes per side, best "
                         "kept (min-of-N convention — shared-host "
                         "noise only ever slows a pass)")
    ap.add_argument("--p99-tol-pct", type=float, default=50.0,
                    help="priority gate: high p99 under flood vs solo")
    ap.add_argument("--quant-min-agree", type=float, default=0.98,
                    help="quant-ab accuracy gate: minimum argmax "
                         "agreement between the fp and int8 bundles "
                         "on the seeded probe batch")
    ap.add_argument("--quant-max-drift", type=float, default=0.05,
                    help="quant-ab accuracy gate: maximum absolute "
                         "output drift (softmax scale) fp vs int8")
    ap.add_argument("--quant-min-shrink", type=float, default=3.0,
                    help="quant-ab footprint gate: the int8 manifest "
                         "hbm_estimate_bytes must shrink >= this x "
                         "vs the fp bundle")
    ap.add_argument("--hbm-budget", default="4M",
                    help="quant-ab: the reference device-memory budget "
                         "for the replicas-that-fit delta row "
                         "(PADDLE_TPU_HBM_BUDGET syntax)")
    # session-tier knobs (--mode sessions)
    ap.add_argument("--sessions", type=int, default=64,
                    help="sessions mode: concurrent conversations "
                         "(must exceed --decode-slots — the paging "
                         "pressure IS the experiment)")
    ap.add_argument("--chunks-per-session", type=int, default=3,
                    help="sessions mode: request chunks per "
                         "conversation")
    ap.add_argument("--think-ms", type=float, default=200.0,
                    help="sessions mode: mean think time between a "
                         "chunk's reply and the next chunk (the "
                         "quiescence the session tier pages out)")
    ap.add_argument("--session-ramp-s", type=float, default=0.5,
                    help="sessions mode: session starts stagger "
                         "uniformly over this window")
    ap.add_argument("--hardcap-queue", type=int, default=None,
                    help="sessions mode: the hard-cap baseline's queue "
                         "bound (default 2 x decode_slots); past it, "
                         "429")
    ap.add_argument("--session-store", type=int, default=4096,
                    help="sessions mode: paged side's host-store "
                         "capacity")
    ap.add_argument("--idle-spill-ms", type=float, default=None,
                    help="sessions mode: idle-spill threshold (default "
                         "None = spill under slot pressure only)")
    ap.add_argument("--require-cap-bite", type=int, default=1,
                    help="sessions mode gate: the hard-cap side must "
                         "shed >= 1 session on the trace (0 relaxes "
                         "for tiny smoke runs)")
    # trace-overhead knobs (--mode trace-overhead)
    ap.add_argument("--trace-sample", type=float, default=0.1,
                    help="trace-overhead mode: the tracing-on side's "
                         "PADDLE_TPU_TRACE_SAMPLE rate")
    ap.add_argument("--trace-passes", type=int, default=3,
                    help="trace-overhead mode: interleaved measurement "
                         "passes per side, best kept (min-of-N)")
    ap.add_argument("--trace-tol-pct", type=float, default=3.0,
                    help="trace-overhead gate: tracing-on must stay "
                         "within this % of tracing-off qps AND p99")
    # health-overhead knobs
    ap.add_argument("--health-passes", type=int, default=3,
                    help="health-overhead mode: interleaved "
                         "measurement passes per side, best kept")
    ap.add_argument("--health-tol-pct", type=float, default=3.0,
                    help="health-overhead gate: history+SLO on must "
                         "stay within this % of off qps AND p99")
    ap.add_argument("--health-slo-p99-ms", type=float, default=50.0,
                    help="health-overhead mode: the on side's declared "
                         "p99 objective (the monitor evaluates it on a "
                         "0.2s cadence during measurement)")
    # slo-ab knobs (--mode slo-ab)
    ap.add_argument("--slo-ab-p99-ms", type=float, default=0.0,
                    help="slo-ab mode: the declared p99 objective the "
                         "controller converges toward (0 = auto: 0.8 x "
                         "the hand-tuned side's measured p99, so the "
                         "controller must at least match the hand "
                         "tuning)")
    ap.add_argument("--slo-hand-latency-ms", type=float, default=2.0,
                    help="slo-ab mode: the hand-tuned side's batch "
                         "deadline (the baseline the converged side "
                         "must match)")
    ap.add_argument("--slo-wrong-latency-ms", type=float, default=60.0,
                    help="slo-ab mode: the autotuned side's deliberately "
                         "WRONG starting batch deadline (holds every "
                         "request far past the objective)")
    ap.add_argument("--slo-qps", type=float, default=300.0,
                    help="slo-ab mode: base offered rate of the "
                         "shifting trace (segments run at 1.0x/1.6x/"
                         "0.7x this rate)")
    ap.add_argument("--slo-rounds", type=int, default=12,
                    help="slo-ab mode: max convergence replays of the "
                         "trace before measurement (the loop breaks "
                         "early once the monitor reads ok)")
    ap.add_argument("--slo-cooldown-s", type=float, default=0.5,
                    help="slo-ab mode: controller per-knob cooldown "
                         "(short — the bench's fast window is 2s)")
    ap.add_argument("--slo-tol-pct", type=float, default=10.0,
                    help="slo-ab gate: converged side must land within "
                         "this %% of hand-tuned sustained qps AND p99")
    ap.add_argument("--serve-hosts", type=int, default=2,
                    help="hosts-ab: subprocess serving hosts to join "
                         "the fleet (one gets SIGKILLed mid-trace)")
    ap.add_argument("--hosts-sessions", type=int, default=8,
                    help="hosts-ab: concurrent conversations in the "
                         "chaos trace (kept small: every chunk commits "
                         "to the remote store over HTTP)")
    ap.add_argument("--hosts-p99-factor", type=float, default=2.0,
                    help="hosts-ab gate: chaos-phase p99 must stay "
                         "under this multiple of the steady-state p99")
    args = ap.parse_args(argv)
    if args.hardcap_queue is None:
        args.hardcap_queue = 2 * args.decode_slots

    from paddle_tpu.utils import compile_cache

    compile_cache.enable()
    if args.mode == "openloop-ab":
        return _emit(measure_openloop_ab(args), "exp_serve_openloop")
    if args.mode == "priority":
        return _emit(measure_priority(args), "exp_serve_priority")
    if args.mode == "replicas-ab":
        return _emit(measure_replicas_ab(args), "exp_serve_replicas")
    if args.mode == "workers-ab":
        return _emit(measure_workers_ab(args), "exp_serve_workers")
    if args.mode == "quant-ab":
        return _emit(measure_quant_ab(args), "exp_serve_quant")
    if args.mode == "sessions":
        return _emit(measure_sessions(args), "exp_serve_sessions")
    if args.mode == "trace-overhead":
        return _emit(measure_trace_overhead(args), "exp_serve_trace")
    if args.mode == "health-overhead":
        return _emit(measure_health_overhead(args), "exp_serve_health")
    if args.mode == "slo-ab":
        return _emit(measure_slo_ab(args), "exp_serve_slo")
    if args.mode == "hosts-ab":
        return _emit(measure_hosts_ab(args), "exp_serve_hosts")
    bundle_dir = args.bundle
    if not bundle_dir:
        bundle_dir = _export_demo_bundle(
            tempfile.mkdtemp(prefix="serve_bundle_"),
            tuple(int(b) for b in args.batch_sizes.split(",")))
        print(json.dumps({"note": "exported demo bundle",
                          "bundle": bundle_dir}))
    row = measure(bundle_dir, args.clients, args.requests,
                  args.rows_per_request, args.max_latency_ms)
    return _emit([row], "exp_serve")


if __name__ == "__main__":
    sys.exit(main())
