"""Multi-step fused training loop tests (trainer ``steps_per_call=K``).

The acceptance slice of ISSUE 6: fixed-seed trajectory identity (K=1 is
byte-identical to the legacy path; K=4 matches K=1 to <=1e-6 on a dense
MNIST-shaped mlp AND a recurrent tagging topology, partial final chunk
included), event-stream compatibility at K>1 (the reference ordering and
the per-step EndIteration payloads are K-invariant), DeviceFeeder chunk
assembly (queue auto-deepening, shape-boundary splits), sentinel checks
at chunk granularity (the anomaly names the real offending global step),
the additive ``train_chunk`` telemetry record, the off-path stream
golden, and the regression-gate wiring for ``exp_fused_loop`` rows."""

import functools
import json
import os

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import data_type as dt, layer as L, minibatch
from paddle_tpu import optimizer as opt
from paddle_tpu import evaluator
from paddle_tpu.data.feeder import DeviceFeeder
from paddle_tpu.graph import reset_name_counters
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import steplog
from paddle_tpu.parameters import Parameters
from paddle_tpu.topology import Topology

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "golden")
SCHEMA = os.path.join(GOLDEN_DIR, "steplog_schema.json")
OFF_STREAM = os.path.join(GOLDEN_DIR, "steplog_off_stream.json")


# ---- topologies ------------------------------------------------------------

def _dense_model(dim=6):
    reset_name_counters()
    x = L.data(name="x", type=dt.dense_vector(dim))
    y = L.data(name="y", type=dt.dense_vector(1))
    out = L.fc(input=L.fc(input=x, size=6), size=1)
    return L.square_error_cost(input=out, label=y)


def _dense_batches(n_batches, batch=4, dim=6, seed=0):
    rng = np.random.RandomState(seed)
    return [[(rng.randn(dim).astype(np.float32),
              np.array([rng.randn()], np.float32)) for _ in range(batch)]
            for _ in range(n_batches)]


def _mnist_mlp():
    """The dense MNIST mlp shape: 784 -> 64 -> 10 classification."""
    reset_name_counters()
    img = L.data(name="img", type=dt.dense_vector(784))
    lab = L.data(name="lab", type=dt.integer_value(10))
    h = L.fc(input=img, size=64)
    out = L.fc(input=h, size=10)
    return L.classification_cost(input=out, label=lab)


def _mnist_batches(n_batches, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    return [[(rng.rand(784).astype(np.float32), int(rng.randint(10)))
             for _ in range(batch)] for _ in range(n_batches)]


def _tagging_model(vocab=30, labels=5, hidden=8):
    reset_name_counters()
    word = L.data(name="word", type=dt.integer_value_sequence(vocab))
    emb = L.embedding(input=word, size=6)
    proj = L.fc(input=emb, size=3 * hidden)
    gru = L.grumemory(input=proj, size=hidden)
    scores = L.fc(input=gru, size=labels)
    label = L.data(name="label", type=dt.integer_value_sequence(labels))
    return L.classification_cost(input=scores, label=label)


def _seq_samples(n, seed=0, length=6, vocab=30, labels=5):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, vocab, length).astype(np.int32).tolist(),
             rng.randint(0, labels, length).astype(np.int32).tolist())
            for _ in range(n)]


def _train_losses(model_fn, reader, k, num_passes=1, optimizer=None,
                  extra_layers=None, **train_kw):
    cost = model_fn()
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost, params,
        optimizer or opt.Momentum(learning_rate=1e-2, momentum=0.9),
        extra_layers=extra_layers)
    losses = []
    trainer.train(reader, num_passes=num_passes,
                  event_handler=lambda e: losses.append(e.cost)
                  if isinstance(e, paddle.event.EndIteration) else None,
                  steps_per_call=k, **train_kw)
    return losses


# ---- trajectory ------------------------------------------------------------

def test_k1_identical_to_legacy_path():
    """steps_per_call=1 runs the byte-identical per-step program through
    the chunked loop: the fixed-seed loss trajectory is EXACTLY the
    legacy path's, not just close."""
    batches = _dense_batches(8, seed=7)
    legacy = _train_losses(_dense_model, lambda: iter(batches), None,
                           num_passes=2)
    fused = _train_losses(_dense_model, lambda: iter(batches), 1,
                          num_passes=2)
    assert len(legacy) == 16
    assert legacy == fused


def test_k4_matches_k1_dense_mnist_mlp():
    batches = _mnist_batches(8, seed=1)
    k1 = _train_losses(_mnist_mlp, lambda: iter(batches), 1, num_passes=2)
    k4 = _train_losses(_mnist_mlp, lambda: iter(batches), 4, num_passes=2)
    assert len(k1) == 16
    np.testing.assert_allclose(k4, k1, rtol=0, atol=1e-6)


def test_k4_matches_k1_recurrent_tagging():
    samples = _seq_samples(32, seed=3)
    reader = minibatch.batch(lambda: iter(samples), 4)
    k1 = _train_losses(_tagging_model, reader, 1,
                       optimizer=opt.Adam(learning_rate=1e-2))
    k4 = _train_losses(_tagging_model, reader, 4,
                       optimizer=opt.Adam(learning_rate=1e-2))
    assert len(k1) == 8
    np.testing.assert_allclose(k4, k1, rtol=0, atol=1e-6)


def test_partial_final_chunk_7_steps_k4(tmp_path, monkeypatch):
    """K does not divide the pass: 7 steps at K=4 run as a 4-chunk and a
    3-chunk, trajectory unchanged, and the telemetry says so."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    batches = _dense_batches(7, seed=5)
    k4 = _train_losses(_dense_model, lambda: iter(batches), 4)
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY")
    k1 = _train_losses(_dense_model, lambda: iter(batches), 1)
    assert len(k4) == 7
    np.testing.assert_allclose(k4, k1, rtol=0, atol=1e-6)
    path = next(str(p) for p in tmp_path.iterdir()
                if p.name.endswith(".steps.jsonl"))
    chunks = [r for r in steplog.read_jsonl(path)
              if r["type"] == "train_chunk"]
    assert [c["steps"] for c in chunks] == [4, 3]
    assert [c["step"] for c in chunks] == [1, 5]
    steps = [r for r in steplog.read_jsonl(path) if r["type"] == "step"]
    assert [s["step"] for s in steps] == list(range(1, 8))
    # per-step wall time is unmeasurable inside a fused region — the
    # chunk record carries the wall interval, the step records none
    assert all("wall_ms" not in s for s in steps)
    assert all("wall_ms" in c for c in chunks)


def test_fused_composes_with_dataparallel_mesh():
    """The fused scan and the DataParallel pjit plan compose: same
    trajectory as the fused single-device run (distributed/worker.py's
    --steps-per-call path)."""
    from paddle_tpu.parallel.mesh import DataParallel, build_mesh

    def run(k, parallelism):
        cost = _dense_model()
        params = Parameters.create(cost)
        trainer = paddle.trainer.SGD(
            cost, params, opt.Momentum(learning_rate=1e-2, momentum=0.9),
            parallelism=parallelism)
        batches = _dense_batches(8, batch=8, seed=11)
        losses = []
        trainer.train(lambda: iter(batches), num_passes=1,
                      event_handler=lambda e: losses.append(e.cost)
                      if isinstance(e, paddle.event.EndIteration) else None,
                      steps_per_call=k)
        return losses

    mesh = build_mesh({"data": jax.device_count()})
    dp_k4 = run(4, DataParallel(mesh))
    dp_k1 = run(1, DataParallel(build_mesh({"data": jax.device_count()})))
    single_k4 = run(4, None)
    assert len(dp_k4) == 8
    np.testing.assert_allclose(dp_k4, dp_k1, rtol=0, atol=1e-6)
    np.testing.assert_allclose(dp_k4, single_k4, rtol=0, atol=1e-5)


# ---- event stream ----------------------------------------------------------

def test_event_stream_ordering_at_k4():
    """THE event-compat satellite: at K=4 the reference per-batch
    ordering (BeginPass -> BeginIteration(b) -> EndForwardBackward(b) ->
    EndIteration(b) -> EndPass) holds for every real step, EndIteration
    fires once per real step with the exact per-step cost + evaluator
    metrics, and the EndIteration payload stream equals the legacy
    run's."""

    def run(k):
        reset_name_counters()
        x = L.data(name="x", type=dt.dense_vector(4))
        lab = L.data(name="y", type=dt.integer_value(2))
        out = L.fc(input=L.fc(input=x, size=8), size=2)
        cost = L.classification_cost(input=out, label=lab)
        err = evaluator.classification_error(input=out, label=lab)
        params = Parameters.create(cost)
        trainer = paddle.trainer.SGD(
            cost, params, opt.Momentum(learning_rate=0.1),
            extra_layers=[err])
        rng = np.random.RandomState(0)
        batches = [[(rng.randn(4).astype(np.float32), int(rng.randint(2)))
                    for _ in range(4)] for _ in range(6)]
        events = []
        trainer.train(lambda: iter(batches), num_passes=2,
                      event_handler=events.append, steps_per_call=k)
        return events, err

    events, err = run(4)

    def idx(cls, pass_id, batch_id=None):
        for i, e in enumerate(events):
            if (isinstance(e, cls) and e.pass_id == pass_id
                    and (batch_id is None or e.batch_id == batch_id)):
                return i
        raise AssertionError("missing %s p%s b%s" % (cls, pass_id,
                                                     batch_id))

    for p in range(2):
        begin = idx(paddle.event.BeginPass, p)
        end = idx(paddle.event.EndPass, p)
        assert begin < end
        for b in range(6):
            bi = idx(paddle.event.BeginIteration, p, b)
            fb = idx(paddle.event.EndForwardBackward, p, b)
            ei = idx(paddle.event.EndIteration, p, b)
            assert begin < bi < fb < ei < end
    ends = [e for e in events if isinstance(e, paddle.event.EndIteration)]
    assert len(ends) == 12
    for e in ends:
        assert isinstance(e.cost, float)
        assert isinstance(e.metrics, dict) and err.name in e.metrics

    # the EndIteration payload stream is K-invariant
    legacy_events, _ = run(None)
    legacy_ends = [e for e in legacy_events
                   if isinstance(e, paddle.event.EndIteration)]
    assert [(e.pass_id, e.batch_id, e.cost, e.metrics) for e in ends] == \
        [(e.pass_id, e.batch_id, e.cost, e.metrics) for e in legacy_ends]


# ---- DeviceFeeder chunks ---------------------------------------------------

def test_chunk_never_starves_a_shallow_queue():
    """THE depth/K satellite: a K=8 chunk over a depth-4 feeder must not
    silently serialize — the queue deepens to 8 (loudly) and full
    8-batch chunks arrive."""
    cost = _dense_model()
    topo = Topology(cost)
    batches = _dense_batches(16, seed=2)
    feeder = DeviceFeeder(lambda: iter(batches), topo, depth=4,
                          metrics_registry=observe_metrics.MetricsRegistry())
    chunks = list(feeder.chunks(8))
    assert feeder.depth == 8
    assert [c.steps for c in chunks] == [8, 8]
    assert all(c.stacked for c in chunks)
    assert chunks[0].examples == 8 * 4
    # the chunk feed is the length-K tuple of member device trees (the
    # fused program stacks them inside the jit — no host dispatches)
    assert isinstance(chunks[0].feed, tuple) and len(chunks[0].feed) == 8
    for fb, member in zip(chunks[0].batches, chunks[0].feed):
        assert member is fb.feed


def test_chunks_split_at_shape_boundaries():
    """A bucket change mid-stream closes the open chunk: chunks never
    mix jit programs (each lowers to one already-compiled scan shape)."""
    cost = _tagging_model()
    topo = Topology(cost)
    short = _seq_samples(8, seed=1, length=3)
    long = _seq_samples(8, seed=2, length=12)
    from paddle_tpu.data import bucketing

    base = minibatch.batch(lambda: iter(short + long), 4)
    bucketed = bucketing.rebucket_batches(base, buckets=[4, 16])
    feeder = DeviceFeeder(bucketed, topo,
                          metrics_registry=observe_metrics.MetricsRegistry())
    chunks = list(feeder.chunks(4))
    for c in chunks:
        buckets = {fb.bucket for fb in c.batches}
        assert len(buckets) == 1  # one bucket per chunk
    assert sum(c.steps for c in chunks) == 4
    assert {c.batches[0].bucket for c in chunks} == {4, 16}


def test_summarize_dir_amortizes_chunk_walls(tmp_path, monkeypatch):
    """cli observe keeps its step-time view for fused runs: with no
    per-step wall_ms, the percentiles amortize the train_chunk
    intervals (first chunk = compile = one entry, like the per-step
    first record)."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    batches = _dense_batches(8, seed=5)
    _train_losses(_dense_model, lambda: iter(batches), 4)
    summary = steplog.summarize_dir(str(tmp_path))
    run = summary["runs"][0]
    assert run["steps"] == 8
    assert run["fused_chunks"] == 2
    assert run["steps_per_call"] == 4
    assert run["wall_ms_p50"] > 0 and run["wall_ms_steady_mean"] > 0
    assert "examples_per_sec_best" in run


def test_explicit_feed_depth_survives_fused_mode(tmp_path, monkeypatch):
    """feed_pipeline as an int is a queue depth, not a bool: depth 5
    with K=2 keeps the 5-deep queue (and depth 1 would deepen to K, not
    silently read as True)."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    batches = _dense_batches(6, seed=5)
    _train_losses(_dense_model, lambda: iter(batches), 2, feed_pipeline=5)
    path = next(str(p) for p in tmp_path.iterdir()
                if p.name.endswith(".steps.jsonl"))
    feeds = [r for r in steplog.read_jsonl(path) if r["type"] == "feed"]
    assert feeds and all(r["depth"] == 5 for r in feeds)


def test_chunks_warn_when_shape_churn_defeats_fusing():
    """Unbucketed variable-length batches close every chunk at size 1 —
    that silent fall-back to per-step dispatch must be loud."""
    import logging

    from paddle_tpu.utils.logger import logger as plogger

    cost = _tagging_model()
    topo = Topology(cost)
    # 9 batches alternating pad buckets (16 vs 32) -> every consecutive
    # pair compiles to a different jit shape
    samples = []
    for n in range(9):
        samples.extend(_seq_samples(4, seed=n, length=10 if n % 2 else 20))
    base = minibatch.batch(lambda: iter(samples), 4)
    feeder = DeviceFeeder(base, topo,
                          metrics_registry=observe_metrics.MetricsRegistry())
    messages = []

    class Capture(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    handler = Capture(level=logging.WARNING)
    plogger.addHandler(handler)
    try:
        chunks = list(feeder.chunks(4))
    finally:
        plogger.removeHandler(handler)
    assert all(c.steps == 1 for c in chunks)
    assert any("splitting on shape boundaries" in m for m in messages)


def test_chunks_rejects_bad_size():
    cost = _dense_model()
    topo = Topology(cost)
    feeder = DeviceFeeder(lambda: iter([]), topo,
                          metrics_registry=observe_metrics.MetricsRegistry())
    with pytest.raises(ValueError, match=">= 1"):
        list(feeder.chunks(0))


# ---- sentinel at chunk granularity -----------------------------------------

def test_sentinel_names_offending_step_inside_chunk(tmp_path, monkeypatch):
    """THE sentinel satellite: NaN injected into step 2 of a K=4 chunk —
    the anomaly AND the crash report name global step 2 (chunk_index 1),
    not the chunk boundary; the ring holds the chunk record."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_SENTINEL", "warn")
    cost = _dense_model(dim=4)
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(cost, params,
                                 opt.Momentum(learning_rate=1e-2))
    batches = _dense_batches(4, batch=4, dim=4, seed=0)
    batches[1][0] = (np.full(4, np.nan, np.float32), batches[1][0][1])
    trainer.train(lambda: iter(batches), num_passes=1, steps_per_call=4)

    path = next(str(p) for p in tmp_path.iterdir()
                if p.name.endswith(".steps.jsonl"))
    records = steplog.read_jsonl(path)
    anomalies = [r for r in records if r["type"] == "anomaly"]
    assert len(anomalies) == 1
    assert anomalies[0]["step"] == 2
    assert anomalies[0]["chunk_index"] == 1
    assert anomalies[0]["kind"] == "nan_inf_loss"
    crash = [r for r in records if r["type"] == "crash_report"]
    assert crash and crash[0]["anomaly"]["step"] == 2
    # the flight-recorder ring records per CHUNK in fused mode
    ring_last = crash[0]["steps"][-1]
    assert ring_last["chunk_steps"] == 4
    assert ring_last["chunk_first_step"] == 1
    assert ring_last["step"] == 4
    # the standalone artifact agrees
    artifact = crash[0]["artifact"]
    with open(artifact) as fh:
        body = json.load(fh)
    assert body["anomaly"]["step"] == 2

    # every record in the fused run is schema-valid (the golden gained
    # the additive train_chunk type)
    golden = json.load(open(SCHEMA))
    for rec in records:
        spec = golden["record_types"][rec["type"]]
        assert set(spec["required"]) <= set(rec), rec["type"]
        # meta extras (StepLog(meta=...)) and bench_row mirrors are
        # outside the golden contract; crash_report bodies carry the
        # free-form ring
        if rec["type"] not in ("meta", "bench_row", "crash_report"):
            unknown = (set(rec) - set(spec["required"])
                       - set(spec["optional"]))
            assert not unknown, (rec["type"], unknown)
    assert any(r["type"] == "train_chunk" for r in records)


def test_record_chunk_tolerates_none_costs():
    """record_chunk normalizes None entries — a trailing None must not
    crash the finalize path."""
    from paddle_tpu.observe.sentinel import Sentinel

    s = Sentinel(mode="warn")
    s.record_chunk(1, [1.0, None])
    s.record_chunk(3, [None, 2.0])
    recs = s.recorder.records()
    assert recs[0]["cost_first"] == 1.0 and "cost_last" not in recs[0]
    assert recs[1]["cost_last"] == 2.0 and "cost_first" not in recs[1]


def test_sentinel_halt_raises_with_chunk_step(monkeypatch):
    from paddle_tpu.observe.sentinel import TrainingAnomaly

    monkeypatch.setenv("PADDLE_TPU_SENTINEL", "halt")
    cost = _dense_model(dim=4)
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(cost, params,
                                 opt.Momentum(learning_rate=1e-2))
    batches = _dense_batches(4, batch=4, dim=4, seed=0)
    batches[2][0] = (np.full(4, np.nan, np.float32), batches[2][0][1])
    events = []
    with pytest.raises(TrainingAnomaly) as exc_info:
        trainer.train(lambda: iter(batches), num_passes=1,
                      steps_per_call=4, event_handler=events.append)
    assert exc_info.value.anomaly["step"] == 3
    assert exc_info.value.anomaly["chunk_index"] == 2
    # the chunk's pre-anomaly steps finalized fully before the halt
    # (same semantics as the per-step path): their EndIteration fired,
    # the anomalous step's did not
    ended = [e.batch_id for e in events
             if isinstance(e, paddle.event.EndIteration)]
    assert ended == [0, 1]


# ---- off-path golden guard -------------------------------------------------

def _structural_stream(records):
    """The off-path stream reduced to its structure: record types in
    order with their exact field sets, plus the deterministic integer
    payload of step records. ``event`` records (jax.monitoring compile
    events) are machine-dependent and excluded."""
    out = []
    for rec in records:
        if rec["type"] == "event":
            continue
        item = {"type": rec["type"], "keys": sorted(rec)}
        if rec["type"] == "step":
            item.update(step=rec["step"], pass_=rec["pass"],
                        batch=rec["batch"], examples=rec["examples"])
        out.append(item)
    return out


def test_feature_off_stream_matches_pr5_golden(tmp_path, monkeypatch):
    """THE byte-compat acceptance guard: with steps_per_call off, the
    trainer's emitted steplog stream is structurally IDENTICAL to the
    checked-in PR 5 golden — same record sequence, same field sets, no
    train_chunk records, no new fields leaking into the legacy path."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    monkeypatch.delenv("PADDLE_TPU_SENTINEL", raising=False)
    batches = _dense_batches(3, seed=7)
    _train_losses(_dense_model, lambda: iter(batches), None, num_passes=2)
    path = next(str(p) for p in tmp_path.iterdir()
                if p.name.endswith(".steps.jsonl"))
    got = _structural_stream(steplog.read_jsonl(path))
    want = json.load(open(OFF_STREAM))["stream"]
    assert got == want
    assert all(item["type"] != "train_chunk" for item in got)


# ---- regression-gate wiring ------------------------------------------------

def test_regress_gate_flags_slower_k8_row(tmp_path):
    """exp_fused_loop rows ride the audited regression gate: a K=8 row
    slower than the audited best by more than the widened tolerance is
    flagged."""
    from paddle_tpu.observe import regress

    baseline = {"tail": json.dumps(
        {"metric": "fused_loop_k8_tagging_bs32", "value": 10.0,
         "unit": "ms/step", "spread_pct": 5.0})}
    path = tmp_path / "BENCH_fused.json"
    path.write_text(json.dumps(baseline))
    slow = {"metric": "fused_loop_k8_tagging_bs32", "value": 13.0,
            "unit": "ms/step", "spread_pct": 5.0}
    results, regressions = regress.gate_rows([slow],
                                             baseline_paths=[str(path)])
    assert len(regressions) == 1
    assert regressions[0]["status"] == "regression"
    ok = {"metric": "fused_loop_k8_tagging_bs32", "value": 10.5,
          "unit": "ms/step", "spread_pct": 5.0}
    results, regressions = regress.gate_rows([ok],
                                             baseline_paths=[str(path)])
    assert not regressions and results[0]["status"] == "ok"


def test_steps_per_call_rejects_plan_without_chunk_wrapper():
    """A parallelism without shard_train_chunk fails loudly at train()
    time instead of silently falling back to per-step dispatch."""

    class NoChunkPlan:
        def shard_train_step(self, train_step, trainer):
            import jax as _jax

            return _jax.jit(train_step, donate_argnums=(0, 1, 3, 4))

        def shard_eval_step(self, eval_step, trainer):
            import jax as _jax

            return _jax.jit(eval_step)

    cost = _dense_model()
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(cost, params,
                                 opt.Momentum(learning_rate=1e-2),
                                 parallelism=NoChunkPlan())
    with pytest.raises(Exception, match="shard_train_chunk"):
        trainer.train(lambda: iter(_dense_batches(2)), num_passes=1,
                      steps_per_call=2)


# ---- one loop, four ways to feed it ----------------------------------------
# SGD.train has one loop over dispatch units; what differs between these
# is where a batch comes from and how many steps one dispatch holds. What
# the user sees must not: trajectory, parameters, events, step count, and
# a checkpoint cursor any of them can continue from.

MODES = {"default": {}, "pipelined": {"feed_pipeline": True},
         "k1": {"steps_per_call": 1}, "k4": {"steps_per_call": 4}}
# a stacked unit's scan reassociates nothing, but XLA fuses it differently
EXACT = ("default", "pipelined", "k1")
N_BATCHES, N_PASSES, TEST_PERIOD = 7, 2, 3


class _Abort(Exception):
    pass


def _mode_model(model):
    """(cost, evaluators, optimizer, reader, test_reader): the dense mlp
    with an error evaluator, or the recurrent tagger."""
    if model == "mlp":
        reset_name_counters()
        img = L.data(name="img", type=dt.dense_vector(784))
        lab = L.data(name="lab", type=dt.integer_value(10))
        out = L.fc(input=L.fc(input=img, size=64), size=10)
        cost = L.classification_cost(input=out, label=lab)
        err = evaluator.classification_error(input=out, label=lab)
        batches = _mnist_batches(N_BATCHES, seed=1)
        held_out = _mnist_batches(2, seed=2)
        return (cost, [err], opt.Momentum(learning_rate=1e-2, momentum=0.9),
                lambda: iter(batches), lambda: iter(held_out))
    cost = _tagging_model()
    samples = _seq_samples(4 * N_BATCHES, seed=3)
    held_out = _seq_samples(8, seed=4)
    return (cost, [], opt.Adam(learning_rate=1e-2),
            minibatch.batch(lambda: iter(samples), 4),
            minibatch.batch(lambda: iter(held_out), 4))


def _mode_run(model, mode, abort_after=None, reader=None, **train_kw):
    """One fixed-seed run under ``mode`` with a periodic test every
    TEST_PERIOD steps. Returns the events, the trainer and what escaped."""
    from paddle_tpu.utils import flags as fl

    cost, evaluators, optimizer, train_reader, test_reader = \
        _mode_model(model)
    trainer = paddle.trainer.SGD(cost, Parameters.create(cost), optimizer,
                                 extra_layers=evaluators)
    events, raised = [], None

    def handler(e):
        events.append(e)
        ended = sum(isinstance(x, paddle.event.EndIteration) for x in events)
        if abort_after is not None and ended >= abort_after:
            raise _Abort()

    fl.set_flag("test_period", TEST_PERIOD)
    try:
        trainer.train(reader or train_reader, num_passes=N_PASSES,
                      event_handler=handler, test_reader=test_reader,
                      **MODES[mode], **train_kw)
    except (_Abort, ZeroDivisionError) as exc:
        raised = exc
    finally:
        fl.set_flag("test_period", 0)
    return events, trainer, raised


@functools.lru_cache(maxsize=None)
def _reference(model):
    """The default mode's uninterrupted run, made once a model."""
    events, trainer, _ = _mode_run(model, "default")
    return (events, trainer._step_count,
            {n: np.array(trainer.parameters.get(n))
             for n in trainer.parameters.names()})


def _shape(events, keep_begin=True):
    """The stream's structure: (event type, pass, batch) in order."""
    return [(type(e).__name__, e.pass_id, getattr(e, "batch_id", None))
            for e in events
            if keep_begin or not isinstance(e, paddle.event.BeginIteration)]


def _losses(events):
    return {(e.pass_id, e.batch_id): e.cost for e in events
            if isinstance(e, paddle.event.EndIteration)}


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("model", ["mlp", "tagger"])
def test_every_mode_is_the_same_run(model, mode):
    """Loss trajectory, final parameters, the ordered event stream with
    its periodic tests, and the step count do not depend on where the
    batches come from or on how many steps a dispatch holds."""
    want_events, want_steps, want_params = _reference(model)
    events, trainer, raised = _mode_run(model, mode)
    assert raised is None
    assert trainer._step_count == want_steps == N_BATCHES * N_PASSES
    tol = 0 if mode in EXACT else 1e-6

    # trajectory and parameters
    want, got = _losses(want_events), _losses(events)
    assert list(got) == list(want)
    np.testing.assert_allclose(list(got.values()), list(want.values()),
                               rtol=0, atol=tol)
    for name, value in want_params.items():
        np.testing.assert_allclose(np.array(trainer.parameters.get(name)),
                                   value, rtol=0, atol=10 * tol, err_msg=name)

    # events: everything that fires at finalize is one stream in every
    # mode, with the periodic test between the step's EndForwardBackward
    # and its EndIteration, at the same steps
    assert _shape(events, keep_begin=False) == \
        _shape(want_events, keep_begin=False)
    shape = _shape(events)
    tests = [i for i, item in enumerate(shape) if item[0] == "TestResult"]
    assert len(tests) == N_BATCHES * N_PASSES // TEST_PERIOD
    for i in tests:
        assert shape[i - 1][0] == "EndForwardBackward"
        assert shape[i + 1] == ("EndIteration",) + shape[i - 1][1:]
    # a batch announces itself before its own EndForwardBackward, in
    # order; one-batch units announce batch b+1 before batch b finalizes
    # (the one-deep pipeline), whatever thread converted it
    begins = [item for item in shape if item[0] == "BeginIteration"]
    assert begins == [("BeginIteration", p, b) for p in range(N_PASSES)
                      for b in range(N_BATCHES)]
    for p in range(N_PASSES):
        for b in range(N_BATCHES):
            assert shape.index(("BeginIteration", p, b)) < \
                shape.index(("EndForwardBackward", p, b))
    if mode in EXACT:
        assert shape == _shape(want_events)
        # evaluator metrics and the periodic tests' own results, exactly
        for mine, theirs in zip(events, want_events):
            if isinstance(mine, (paddle.event.EndIteration,
                                 paddle.event.TestResult)):
                assert (mine.cost, mine.metrics) == \
                    (theirs.cost, theirs.metrics)


@pytest.mark.parametrize("resumed", list(MODES))
@pytest.mark.parametrize("written", list(MODES))
def test_any_mode_resumes_from_any_modes_cursor(written, resumed, tmp_path):
    """A checkpoint's cursor counts batches, so a run under any mode
    continues the trajectory from a cursor any other mode wrote."""
    want = _losses(_reference("mlp")[0])
    d = str(tmp_path)
    part, _, raised = _mode_run("mlp", written, abort_after=5,
                                checkpoint_dir=d, checkpoint_every=2,
                                checkpoint_sync=True)
    assert isinstance(raised, _Abort)
    rest, trainer, raised = _mode_run("mlp", resumed, checkpoint_dir=d,
                                      checkpoint_every=2, resume=True,
                                      checkpoint_sync=True)
    assert raised is None
    assert trainer._step_count == N_BATCHES * N_PASSES
    part, rest = _losses(part), _losses(rest)
    first = min(rest)
    assert min(want) < first  # from the cursor, not from scratch
    for key, cost in {**part, **rest}.items():
        assert abs(cost - want[key]) <= 1e-6, (key, cost, want[key])
    # the resumed stream runs from its cursor to the end without a hole,
    # and what neither run reported was dispatched and checkpointed by the
    # first one, behind its one-deep pipeline
    assert list(rest) == [key for key in want if key >= first]
    assert all(max(part) < key < first
               for key in set(want) - set(part) - set(rest))


@pytest.mark.parametrize("mode,finalized", [
    ("default", [0, 1]), ("pipelined", [0, 1]), ("k1", [0, 1]),
    # the reader fails before the first unit of four is whole
    ("k4", [])])
def test_a_failing_reader_surfaces_the_same_way(mode, finalized):
    """The reader raises when asked for batch 3: its exception reaches the
    caller as itself from every source, the units finalized before it are
    whole, and the one in flight is dropped, not half reported."""
    batches = _mnist_batches(3, seed=1)

    def reader():
        yield from batches
        1 / 0

    events, trainer, raised = _mode_run("mlp", mode, reader=reader)
    assert isinstance(raised, ZeroDivisionError)
    shape = _shape(events)
    assert [b for kind, _, b in shape if kind == "EndIteration"] == finalized
    assert [b for kind, _, b in shape
            if kind == "EndForwardBackward"] == finalized
    assert not any(kind == "EndPass" for kind, _, _ in shape)
    # every batch the source handed over was dispatched and counted
    assert trainer._step_count == (0 if mode == "k4" else 3)
