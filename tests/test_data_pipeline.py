"""paddle_tpu.data tests: bucket-choice agreement with serving, length
bucketing, sequence packing (gradient-match vs the unpacked baseline),
the DeviceFeeder pipeline (parity, cancellation, error propagation) and
the trainer wiring (fixed-seed loss-trajectory equivalence, feed
telemetry records)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import data_type as dt, layer as L, minibatch
from paddle_tpu import optimizer as opt
from paddle_tpu.core.sequence import PackedSequenceBatch, SequenceBatch
from paddle_tpu.data import bucketing
from paddle_tpu.data.feeder import DeviceFeeder
from paddle_tpu.graph import reset_name_counters
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import steplog
from paddle_tpu.parameters import Parameters
from paddle_tpu.topology import Topology, convert_feed

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "steplog_schema.json")


# ---- bucket choice ---------------------------------------------------------

def test_bucket_index_semantics():
    sizes = [4, 8, 32]
    assert bucketing.bucket_for(1, sizes) == 4
    assert bucketing.bucket_for(4, sizes) == 4
    assert bucketing.bucket_for(5, sizes) == 8
    assert bucketing.bucket_for(32, sizes) == 32
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        bucketing.bucket_index(33, sizes)


def test_serve_bundle_bucket_choice_agrees_with_training():
    """THE dedup satellite: the serving bundle's bucket_for and the
    training-side bucket choice are ONE function — pin agreement over
    every reachable row count so serving and training can never drift."""
    from paddle_tpu.serve.bundle import Bundle

    bundle = Bundle.__new__(Bundle)
    bundle.buckets = [{"batch": 1}, {"batch": 8}, {"batch": 32}]
    sizes = bundle.batch_sizes()
    for rows in range(1, 33):
        assert bundle.bucket_for(rows)["batch"] == \
            bucketing.bucket_for(rows, sizes)
    with pytest.raises(ValueError, match="largest exported bucket"):
        bundle.bucket_for(33)


def test_derive_buckets_bounded_and_covering():
    rng = np.random.RandomState(0)
    lengths = np.clip(rng.lognormal(2.5, 0.8, size=500).astype(int), 1, None)
    bounds = bucketing.derive_buckets(lengths, max_buckets=6)
    assert 1 <= len(bounds) <= 6
    assert bounds == sorted(bounds)
    assert all(b % 8 == 0 for b in bounds)
    assert bounds[-1] >= lengths.max()  # every observed length fits


# ---- length bucketing ------------------------------------------------------

def _seq_samples(n, seed=0, vocab=20, labels=4,
                 lengths=(2, 3, 4, 9, 10, 18)):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        ln = int(rng.choice(lengths))
        out.append((rng.randint(0, vocab, ln).astype(np.int32).tolist(),
                    rng.randint(0, labels, ln).astype(np.int32).tolist()))
    return out


def test_rebucket_batches_groups_without_loss():
    samples = _seq_samples(48)
    base = minibatch.batch(lambda: iter(samples), 8)
    bounds = [4, 10, 20]
    batches = list(bucketing.rebucket_batches(base, buckets=bounds)())
    got = [tuple(map(tuple, s)) for b in batches for s in b]
    want = [tuple(map(tuple, s)) for s in samples]
    assert sorted(got) == sorted(want)  # nothing lost or duplicated
    for b in batches:
        assert isinstance(b, bucketing.BucketBatch)
        assert b.bucket in bounds
        for s in b:
            n = len(s[0])
            # every sample in its smallest covering bucket
            assert bucketing.bucket_for(n, bounds) == b.bucket


def test_rebucket_drop_remainder():
    samples = _seq_samples(50)
    base = minibatch.batch(lambda: iter(samples), 8)
    batches = list(bucketing.rebucket_batches(
        base, buckets=[4, 10, 20], drop_remainder=True)())
    assert batches and all(len(b) == 8 for b in batches)  # only full


def test_rebucket_batches_auto_derives():
    samples = _seq_samples(60, seed=3)
    base = minibatch.batch(lambda: iter(samples), 8)
    batches = list(bucketing.rebucket_batches(
        base, buckets=None, sample_window=16)())
    assert sum(len(b) for b in batches) == 56  # 60 rounded to batches of 8
    buckets = {b.bucket for b in batches}
    assert len(buckets) > 1  # skewed lengths actually split


def test_bucketed_convert_pads_to_exact_bucket():
    """One jit cache entry per bucket: conversion pads sequence slots to
    exactly the batch's bucket boundary, not the batch max."""
    reset_name_counters()
    word = L.data(name="word", type=dt.integer_value_sequence(20))
    label = L.data(name="label", type=dt.integer_value_sequence(4))
    cost = L.classification_cost(
        input=L.fc(input=L.embedding(input=word, size=4), size=4),
        label=label)
    topo = Topology(cost)
    batch = bucketing.BucketBatch(_seq_samples(4, lengths=(2, 3)), 10)
    feed = convert_feed(topo, batch, max_len=batch.bucket)
    assert feed["word"].max_len == 10
    assert feed["label"].max_len == 10
    # default (no max_len) keeps the historical behavior: batch max
    # rounded up the global bucket_length table (here 3 -> 16)
    feed_plain = convert_feed(topo, list(batch))
    assert feed_plain["word"].max_len == 16


def test_topology_length_of_ignores_dense_columns():
    """Mixed schema (dense feature vector + sequence): the bucket key
    must come from the SEQUENCE slots, not the fixed feature width —
    the trainer's buckets= wiring uses topology_length_of for this."""
    reset_name_counters()
    feats = L.data(name="feats", type=dt.dense_vector(128))
    word = L.data(name="word", type=dt.integer_value_sequence(20))
    merged = L.fc(input=[L.embedding(input=word, size=4),
                         L.expand(input=L.fc(input=feats, size=4),
                                  expand_as=word)], size=4)
    label = L.data(name="label", type=dt.integer_value_sequence(4))
    cost = L.classification_cost(input=merged, label=label)
    topo = Topology(cost)
    length_of = bucketing.topology_length_of(topo)
    sample = (np.zeros(128, np.float32), [1, 2, 3], [0, 1, 2])
    assert length_of(sample) == 3  # not 128
    assert bucketing.default_length_of(sample) == 128  # the caveat


def test_batch_waste_accounting():
    samples = [([1, 2], [0, 1]), ([1, 2, 3, 4], [0, 1, 2, 3])]
    fill, pad = bucketing.batch_waste(samples, padded_len=8)
    assert fill == 6 and pad == 2 * 8 - 6


# ---- packing ---------------------------------------------------------------

def test_pack_samples_respects_budget():
    samples = _seq_samples(30, seed=1)
    rows = bucketing.pack_samples(samples, max_len=20)
    flat = [tuple(map(tuple, s)) for r in rows for s in r]
    assert sorted(flat) == sorted(tuple(map(tuple, s)) for s in samples)
    for row in rows:
        assert sum(len(s[0]) for s in row) <= 20
    # packing actually packs: fewer rows than samples
    assert len(rows) < len(samples)


def test_packed_batches_reader():
    samples = _seq_samples(40, seed=2)
    reader = bucketing.packed_batches(
        lambda: iter(samples), batch_size=4, max_len=20)
    batches = list(reader())
    flat = [tuple(map(tuple, s)) for b in batches for row in b for s in row]
    assert sorted(flat) == sorted(tuple(map(tuple, s)) for s in samples)
    assert all(len(b) <= 4 for b in batches)


def test_packed_batches_streams_with_bounded_open_set():
    """The first-fit open set is capped: a long stream whose rows never
    fill exactly must still yield batches WHILE streaming (not buffer
    everything to end-of-stream) and lose no samples."""
    samples = [([1] * 5, [0] * 5) for _ in range(400)]  # 5 never sums to 64
    reader = bucketing.packed_batches(lambda: iter(samples), batch_size=4,
                                      max_len=64, max_open_rows=8)
    it = reader()
    first = next(it)  # arrives mid-stream thanks to the cap
    rest = list(it)
    total = sum(len(s[0]) for b in [first] + rest for row in b for s in row)
    assert total == 400 * 5
    for b in [first] + rest:
        for row in b:
            assert sum(len(s[0]) for s in row) <= 64


def _tagging_model(vocab=30, labels=5, hidden=8, bidirectional=False):
    reset_name_counters()
    word = L.data(name="word", type=dt.integer_value_sequence(vocab))
    emb = L.embedding(input=word, size=6)
    proj = L.fc(input=emb, size=3 * hidden)
    fwd = L.grumemory(input=proj, size=hidden)
    feat = fwd
    if bidirectional:
        bwd = L.grumemory(input=proj, size=hidden, reverse=True)
        feat = L.concat(input=[fwd, bwd])
    scores = L.fc(input=feat, size=labels)
    label = L.data(name="label", type=dt.integer_value_sequence(labels))
    cost = L.classification_cost(input=scores, label=label)
    return cost


def test_pack_feed_segment_layout():
    cost = _tagging_model()
    topo = Topology(cost)
    samples = [([1, 2, 3], [0, 1, 2]), ([4, 5], [3, 4]), ([6], [0])]
    rows = bucketing.pack_samples(samples, max_len=8)
    feed = bucketing.pack_feed(topo, rows, max_len=8)
    word = feed["word"]
    assert isinstance(word, PackedSequenceBatch)
    data = np.asarray(word.data)
    seg = np.asarray(word.segments)
    lens = np.asarray(word.lengths)
    # one row: [1,2,3 | 4,5 | 6] with segments [0,0,0,1,1,2]
    assert lens[0] == 6
    np.testing.assert_array_equal(data[0, :6], [1, 2, 3, 4, 5, 6])
    np.testing.assert_array_equal(seg[0, :6], [0, 0, 0, 1, 1, 2])
    np.testing.assert_array_equal(seg[0, 6:], [-1, -1])
    # reset mask fires exactly at segment starts
    reset = np.asarray(word.reset_mask())
    np.testing.assert_array_equal(
        reset[0], [True, False, False, True, False, True, False, False])


@pytest.mark.parametrize("bidirectional", [False, True])
def test_packing_gradient_match(bidirectional):
    """THE packing acceptance test: packed-with-segment-mask cost and
    gradients equal the unpacked baseline (atol <= 1e-5) on a small GRU
    tagging config — forward-only AND bi-directional (per-segment
    reverse)."""
    cost = _tagging_model(bidirectional=bidirectional)
    topo = Topology(cost)
    params_obj = Parameters.create(cost)
    params = {n: jnp.asarray(params_obj.get(n))
              for n in params_obj.names()}
    rng = np.random.RandomState(0)
    samples = []
    for n in (3, 5, 2, 7, 4, 6, 1, 4):
        samples.append((rng.randint(0, 30, n).astype(np.int32).tolist(),
                        rng.randint(0, 5, n).astype(np.int32).tolist()))

    def cost_sum(p, feed):
        values, _ = topo.apply(p, feed, mode="test")
        return jnp.sum(values[cost.name])

    feed_u = convert_feed(topo, samples)
    cu, gu = jax.value_and_grad(cost_sum)(params, feed_u)
    rows = bucketing.pack_samples(samples, max_len=16)
    assert len(rows) < len(samples)
    feed_p = bucketing.pack_feed(topo, rows, max_len=16)
    cp, gp = jax.value_and_grad(cost_sum)(params, feed_p)
    np.testing.assert_allclose(float(cu), float(cp), atol=1e-5)
    for name in gu:
        np.testing.assert_allclose(np.asarray(gu[name]),
                                   np.asarray(gp[name]), atol=1e-5,
                                   err_msg=name)


def test_pack_feed_pads_overlong_own_row_sample():
    """pack_samples gives an overlong sample its own row ('pad, never
    truncate'); pack_feed must widen the batch to fit it, not raise."""
    cost = _tagging_model()
    topo = Topology(cost)
    long = (list(range(1, 21)), [0] * 20)  # length 20 > max_len 16
    samples = [([1, 2], [0, 1]), long, ([3], [2])]
    rows = bucketing.pack_samples(samples, max_len=16)
    assert [len(s[0]) for r in rows for s in r].count(20) == 1
    feed = bucketing.pack_feed(topo, rows, max_len=16)
    assert feed["word"].max_len >= 20  # widened, nothing truncated
    lens = np.asarray(feed["word"].lengths)
    assert lens.max() == 20


def test_rebucket_top_bucket_grows_geometrically():
    """Samples longer than every bucket widen the list GEOMETRICALLY —
    a length-sorted stream must not mint one jit shape per new record
    length."""
    samples = [([1] * n, [0] * n) for n in range(1, 65)]  # sorted lengths
    base = minibatch.batch(lambda: iter(samples), 4)
    batches = list(bucketing.rebucket_batches(base, buckets=[4])())
    buckets = sorted({b.bucket for b in batches})
    assert buckets == [4, 16, 32, 64]  # log growth, not per-length
    got = sorted(len(s[0]) for b in batches for s in b)
    assert got == sorted(len(s[0]) for s in samples)


def test_reduction_layers_reject_packed_input():
    """pooling/last_seq would silently collapse packed neighbours into
    one output — they must refuse packed batches like crf does."""
    from paddle_tpu.pooling import AvgPooling

    reset_name_counters()
    word = L.data(name="word", type=dt.integer_value_sequence(20))
    pooled = L.pooling(input=L.embedding(input=word, size=4),
                       pooling_type=AvgPooling())
    score = L.fc(input=pooled, size=1)
    label = L.data(name="label", type=dt.integer_value_sequence(2))
    cost = L.square_error_cost(
        input=score, label=L.fc(input=L.pooling(
            input=L.embedding(input=label, size=1),
            pooling_type=AvgPooling()), size=1))
    topo = Topology(cost)
    samples = [([1, 2], [0, 1]), ([3], [1])]
    feed = bucketing.pack_feed(topo, bucketing.pack_samples(samples, 8),
                               max_len=8)
    params = topo.init_params(jax.random.PRNGKey(0))
    with pytest.raises(Exception, match="packed"):
        topo.apply(params, feed, mode="test")


def test_crf_rejects_packed_input():
    """Chain transitions would silently bridge packed neighbours — the
    crf layer refuses packed batches at trace time."""
    from paddle_tpu.models import text

    reset_name_counters()
    scores = text.sequence_tagging_rnn(word_dict_size=20, label_dict_size=4,
                                       emb_size=4, hidden=4)
    label = L.data(name="label", type=dt.integer_value_sequence(4))
    cost = L.crf(input=scores, label=label, name="packed_crf")
    topo = Topology(cost)
    samples = [([1, 2], [0, 1]), ([3], [2])]
    feed = bucketing.pack_feed(topo, bucketing.pack_samples(samples, 8),
                               max_len=8)
    params = topo.init_params(jax.random.PRNGKey(0))
    with pytest.raises(Exception, match="packed"):
        topo.apply(params, feed, mode="test")


# ---- DeviceFeeder ----------------------------------------------------------

def _dense_model():
    reset_name_counters()
    x = L.data(name="x", type=dt.dense_vector(6))
    y = L.data(name="y", type=dt.dense_vector(1))
    out = L.fc(input=L.fc(input=x, size=6), size=1)
    return L.square_error_cost(input=out, label=y)


def _dense_batches(n_batches, batch=4, seed=0):
    rng = np.random.RandomState(seed)
    data = []
    for _ in range(n_batches):
        data.append([(rng.randn(6).astype(np.float32),
                      np.array([rng.randn()], np.float32))
                     for _ in range(batch)])
    return data


def test_feeder_matches_sync_conversion():
    cost = _dense_model()
    topo = Topology(cost)
    batches = _dense_batches(4)
    reg = observe_metrics.MetricsRegistry()
    feeder = DeviceFeeder(lambda: iter(batches), topo, depth=2,
                          metrics_registry=reg)
    got = list(feeder.batches())
    assert len(got) == 4
    for fb, batch in zip(got, batches):
        want = convert_feed(topo, batch)
        for key in want:
            np.testing.assert_array_equal(np.asarray(fb.feed[key]),
                                          np.asarray(want[key]))
        assert fb.examples == len(batch)
        assert fb.stall_ms is not None and fb.convert_ms is not None
    snap = reg.snapshot()
    assert snap["counters"]["paddle_tpu_data_batches_total"] == 4
    assert snap["histograms"][
        "paddle_tpu_data_feed_stall_ms"]["count"] == 4


def test_feeder_propagates_reader_error():
    cost = _dense_model()
    topo = Topology(cost)
    batches = _dense_batches(2)

    def bad_reader():
        yield batches[0]
        raise RuntimeError("reader exploded")

    feeder = DeviceFeeder(bad_reader, topo,
                          metrics_registry=observe_metrics.MetricsRegistry())
    it = feeder.batches()
    next(it)
    with pytest.raises(RuntimeError, match="reader exploded"):
        list(it)
    # producer-thread exit is enforced by the suite-wide thread-leak
    # gate (paddle_tpu.analyze.pytest_plugin, wired in conftest)


def test_feeder_abandoned_consumer_cancels_producer():
    """Break out of the batch loop after one item: the producer thread
    must exit even though the queue was full (clean cancellation —
    the analyze thread-leak gate fails this test if it doesn't)."""
    cost = _dense_model()
    topo = Topology(cost)
    batches = _dense_batches(200)
    feeder = DeviceFeeder(lambda: iter(batches), topo, depth=1,
                          metrics_registry=observe_metrics.MetricsRegistry())
    it = feeder.batches()
    next(it)
    it.close()


def test_feeder_bucket_gauges():
    cost = _tagging_model()
    topo = Topology(cost)
    samples = _seq_samples(16, lengths=(2, 3))
    base = minibatch.batch(lambda: iter(samples), 4)
    bucketed = bucketing.rebucket_batches(base, buckets=[4, 8])
    reg = observe_metrics.MetricsRegistry()
    feeder = DeviceFeeder(bucketed, topo, metrics_registry=reg)
    seen = list(feeder.batches())
    assert seen and all(fb.bucket == 4 for fb in seen)
    snap = reg.snapshot()
    fill = snap["gauges"]['paddle_tpu_data_bucket_fill_ratio{bucket="4"}']
    waste = snap["gauges"][
        'paddle_tpu_data_padding_waste_ratio{bucket="4"}']
    assert fill + waste == pytest.approx(1.0)
    assert 0.0 < waste < 1.0


def test_feeder_sharding_aware_with_dataparallel():
    """With a DataParallel plan the producer thread applies the
    global-mesh batch placement itself (device_put onto the 'data'
    axis), so the transfer happens ahead of the step."""
    from paddle_tpu.parallel.mesh import DataParallel, build_mesh

    mesh = build_mesh({"data": jax.device_count()})
    dp = DataParallel(mesh)
    cost = _dense_model()
    topo = Topology(cost)
    batches = _dense_batches(2, batch=8)
    feeder = DeviceFeeder(lambda: iter(batches), topo, parallelism=dp,
                          metrics_registry=observe_metrics.MetricsRegistry())
    fbs = list(feeder.batches())
    assert len(fbs) == 2
    x = fbs[0].feed["x"]
    assert x.sharding.spec[0] == "data"  # batch axis sharded on the mesh
    assert not x.sharding.is_fully_replicated


def test_pipelined_dataparallel_matches_sync():
    from paddle_tpu.parallel.mesh import DataParallel, build_mesh

    def run(feed_pipeline):
        mesh = build_mesh({"data": jax.device_count()})
        cost = _dense_model()
        params = Parameters.create(cost)
        trainer = paddle.trainer.SGD(
            cost, params, opt.Momentum(learning_rate=1e-2, momentum=0.9),
            parallelism=DataParallel(mesh))
        batches = _dense_batches(3, batch=8, seed=11)
        losses = []
        trainer.train(lambda: iter(batches), num_passes=2,
                      event_handler=lambda e: losses.append(e.cost)
                      if isinstance(e, paddle.event.EndIteration) else None,
                      feed_pipeline=feed_pipeline)
        return losses

    assert run(False) == run(True)


# ---- trainer wiring --------------------------------------------------------

def _train_losses(feed_pipeline, num_passes=3, **train_kw):
    cost = _dense_model()
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost, params, opt.Momentum(learning_rate=1e-2, momentum=0.9))
    batches = _dense_batches(3, seed=7)
    losses = []
    trainer.train(
        lambda: iter(batches), num_passes=num_passes,
        event_handler=lambda e: losses.append(e.cost)
        if isinstance(e, paddle.event.EndIteration) else None,
        feed_pipeline=feed_pipeline, **train_kw)
    return losses


def test_pipelined_feed_identical_loss_trajectory():
    """THE pipeline acceptance test: fixed-seed loss trajectory of the
    pipelined feed is IDENTICAL (not just close) to the sync feed."""
    sync = _train_losses(False)
    piped = _train_losses(True)
    assert len(sync) == 9
    assert sync == piped


def test_pipelined_feed_depth_int():
    assert _train_losses(3) == _train_losses(False)


def test_bucketed_training_trains_and_bounds_shapes():
    cost = _tagging_model()
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(cost, params,
                                 opt.Adam(learning_rate=1e-2))
    samples = _seq_samples(32, seed=9)
    losses = []
    trainer.train(
        minibatch.batch(lambda: iter(samples), 8), num_passes=2,
        event_handler=lambda e: losses.append(e.cost)
        if isinstance(e, paddle.event.EndIteration) else None,
        feed_pipeline=True, buckets=[4, 10, 20])
    assert losses and all(np.isfinite(losses))


def test_trainer_feed_records_and_summary(tmp_path, monkeypatch):
    """Pipelined training under telemetry writes schema-valid ``feed``
    records, and summarize_dir/cli observe surface the stall
    percentiles."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    _train_losses(True, num_passes=2)
    path = next(p for p in os.listdir(str(tmp_path))
                if p.endswith(".steps.jsonl"))
    records = steplog.read_jsonl(os.path.join(str(tmp_path), path))
    feeds = [r for r in records if r["type"] == "feed"]
    assert len(feeds) == 6  # 2 passes x 3 batches
    golden = json.load(open(GOLDEN))
    spec = golden["record_types"]["feed"]
    for rec in feeds:
        assert not set(spec["required"]) - set(rec)
        assert not (set(rec) - set(spec["required"])
                    - set(spec["optional"]))
        assert rec["depth"] == 2 and rec["examples"] == 4
    steps = [r for r in records if r["type"] == "step"]
    # step records carry the stall as feed_ms and pair 1:1 with feeds
    assert len(steps) == 6
    summary = steplog.summarize_dir(str(tmp_path))
    run = summary["runs"][0]
    assert run["feed_batches"] == 6
    assert "feed_stall_ms_p50" in run and "feed_stall_ms_p95" in run

    from paddle_tpu import cli

    class A:
        directory = str(tmp_path)
        regress = None
        regress_tol = 10.0
        json = False

    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.cmd_observe(A()) == 0
    assert "feed stall ms" in buf.getvalue()

def test_feeder_cancel_honored_mid_skip_prefix():
    """Cancellation while the producer is still consuming the
    resume-skip prefix (train(resume=) deep into a pass over a slow
    reader) must stop it promptly — the skip branch converts nothing
    and never touches the queue, so it needs its own cancellation
    check or the consumer's cancel+join at abandonment hangs out its
    timeout and leaks the thread."""
    import itertools
    import queue as _queue
    import threading
    import time as _time

    cost = _dense_model()
    topo = Topology(cost)

    def slow_batches():
        for b in itertools.cycle(_dense_batches(8)):
            _time.sleep(0.02)  # an endless, slow skipped prefix
            yield b

    feeder = DeviceFeeder(slow_batches, topo, depth=1,
                          metrics_registry=observe_metrics.MetricsRegistry())
    q = _queue.Queue(maxsize=1)
    cancel = threading.Event()
    t = threading.Thread(target=feeder._produce,
                         args=(q, cancel, 10 ** 9),
                         name="data-feeder-producer", daemon=True)
    t.start()
    _time.sleep(0.15)  # well inside the skip prefix
    cancel.set()
    t.join(timeout=2.0)
    assert not t.is_alive()


# ---- the feeder's recycled host buffers ------------------------------------

from paddle_tpu.data import feeder as feeder_mod  # noqa: E402
from paddle_tpu.topology import convert_column  # noqa: E402

RING = 4  # depth 2 + the batch being assembled + the one in the step
REUSED = "paddle_tpu_data_feed_buffers_reused_total"
ALLOCATED = "paddle_tpu_data_feed_buffers_allocated_total"
BUFFER_WAIT = "paddle_tpu_data_feed_buffer_wait_ms"


def _labelled_model(dim=6, classes=4):
    reset_name_counters()
    x = L.data(name="x", type=dt.dense_vector(dim))
    y = L.data(name="y", type=dt.integer_value(classes))
    return L.classification_cost(input=L.fc(input=x, size=classes), label=y)


ROW_KINDS = {
    "float32": lambda v: v.astype(np.float32),
    "float64": lambda v: v.astype(np.float64),
    "int16": lambda v: (v * 100).astype(np.int16),
    "list": lambda v: [float(e) for e in v],
    "tuple": lambda v: tuple(float(e) for e in v),
    "nested_list": lambda v: [[float(e) for e in v[:3]],
                              [float(e) for e in v[3:]]],
}
LABEL_KINDS = {
    "int": int,
    "numpy_int64": np.int64,
    "zero_d_array": lambda i: np.array(i, dtype=np.int64),
    "float": float,
}


def _labelled_batches(n, rows=8, dim=6, classes=4, seed=0, row=None,
                      label=int):
    rng = np.random.RandomState(seed)
    row = row or ROW_KINDS["float32"]
    return [[(row(rng.randn(dim)), label(rng.randint(classes)))
             for _ in range(rows)] for _ in range(n)]


def _counters(reg):
    counters = reg.snapshot()["counters"]
    return counters.get(REUSED, 0), counters.get(ALLOCATED, 0)


def _aligned_like(host, align=64):
    """An empty array of ``host``'s shape whose bytes start on an
    ``align`` boundary: what the CPU platform wraps instead of copying."""
    raw = np.empty(host.nbytes + align, np.uint8)
    start = (-raw.ctypes.data) % align
    return raw[start:start + host.nbytes].view(host.dtype).reshape(host.shape)


@pytest.fixture
def aligned_slots(monkeypatch):
    """Every recycled buffer lies where the CPU platform would share it
    with the array placed from it: the worst case for an overwrite."""
    class AlignedSlot(feeder_mod._Slot):
        def __init__(self, host):
            super().__init__(_aligned_like(host))

    monkeypatch.setattr(feeder_mod, "_Slot", AlignedSlot)


@pytest.mark.parametrize("placement", ["one_device", "mesh"])
def test_recycled_buffers_never_change_a_feed_that_is_alive(
        placement, aligned_slots):
    """(a) ring + 3 batches of distinct rows, every feed kept alive: after
    the last batch was made each still holds its own rows."""
    from paddle_tpu.parallel.mesh import DataParallel, build_mesh

    parallelism = None if placement == "one_device" else DataParallel(
        build_mesh({"data": 2}, devices=jax.devices()[:2]))
    topo = Topology(_labelled_model())
    batches = _labelled_batches(RING + 3)
    reg = observe_metrics.MetricsRegistry()
    feeder = DeviceFeeder(lambda: iter(batches), topo, depth=2,
                          parallelism=parallelism, metrics_registry=reg)
    kept = list(feeder.batches())
    assert len(kept) == RING + 3
    assert _counters(reg) == (2 * 3, 2 * RING)  # the ring did wrap
    for fb, batch in zip(kept, batches):
        np.testing.assert_array_equal(
            np.asarray(fb.feed["x"]),
            np.asarray([r[0] for r in batch], dtype=np.float32))
        np.testing.assert_array_equal(
            np.asarray(fb.feed["y"]),
            np.asarray([r[1] for r in batch], dtype=np.int32))


def test_a_recycled_array_is_placed_as_a_copy():
    """The CPU platform wraps an aligned numpy array; what is placed from
    a recycled one must not see the next batch's bytes."""
    from paddle_tpu.topology import _lives_in, _place

    host = _aligned_like(np.empty((4, 6), np.float32))
    host[...] = 1.0
    fresh = _place(host)
    assert _lives_in(fresh, host)  # today's placement: shared, never written
    placed = _place(host, recycled=True)
    assert not _lives_in(placed, host)
    host[...] = 2.0
    assert float(np.asarray(placed).max()) == 1.0


@pytest.mark.parametrize("kind", sorted(ROW_KINDS))
def test_recycled_feeds_are_bit_identical_to_convert_feed(kind):
    """(b) with the pool a feeder's feeds equal `convert_feed` without it,
    value, dtype and shape, whatever the rows are made of."""
    topo = Topology(_labelled_model())
    batches = _labelled_batches(RING + 3, row=ROW_KINDS[kind], seed=3)
    reg = observe_metrics.MetricsRegistry()
    feeder = DeviceFeeder(lambda: iter(batches), topo, metrics_registry=reg)
    got = list(feeder.batches())
    assert _counters(reg) == (2 * 3, 2 * RING)
    for fb, batch in zip(got, batches):
        want = convert_feed(topo, batch)
        for name in ("x", "y"):
            assert fb.feed[name].dtype == want[name].dtype
            assert fb.feed[name].shape == want[name].shape
            np.testing.assert_array_equal(np.asarray(fb.feed[name]),
                                          np.asarray(want[name]))


@pytest.mark.parametrize("kind", sorted(LABEL_KINDS))
def test_recycled_labels_are_bit_identical_to_convert_feed(kind):
    topo = Topology(_labelled_model())
    batches = _labelled_batches(RING + 2, label=LABEL_KINDS[kind], seed=5)
    reg = observe_metrics.MetricsRegistry()
    got = list(DeviceFeeder(lambda: iter(batches), topo,
                            metrics_registry=reg).batches())
    assert _counters(reg) == (2 * 2, 2 * RING)
    for fb, batch in zip(got, batches):
        want = convert_feed(topo, batch)["y"]
        assert fb.feed["y"].dtype == want.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(fb.feed["y"]),
                                      np.asarray(want))


def _pool(slots=RING):
    reg = observe_metrics.MetricsRegistry()
    return feeder_mod.HostBuffers(
        slots, reused=reg.counter(REUSED), allocated=reg.counter(ALLOCATED)
    ), reg


@pytest.mark.parametrize("rows, error", [
    ([np.zeros(3), np.zeros(4)], ValueError),          # ragged arrays
    ([[1.0, 2.0], [1.0]], ValueError),                 # ragged lists
    ([np.zeros(3), np.zeros(1)], ValueError),          # would broadcast
    ([1, 2 ** 40], OverflowError),                     # no int32
    ([], None),                                        # no rows
])
def test_rows_that_form_no_array_take_the_old_line(rows, error):
    """The pool declines, and the caller's `np.asarray` raises what it
    raised (or makes the empty array it made)."""
    pool, reg = _pool()
    dtype = np.int32 if error is OverflowError else np.float32
    assert pool.assemble("x", rows, dtype) is None
    assert _counters(reg) == (0, 0)
    itype = dt.integer_value(4) if dtype == np.int32 else dt.dense_vector(3)
    if error is None:
        assert convert_column(rows, itype, assemble=lambda c, d:
                              pool.assemble("x", c, d)).shape == (0,)
        return
    with pytest.raises(error) as new:
        convert_column(rows, itype,
                       assemble=lambda c, d: pool.assemble("x", c, d))
    with pytest.raises(error) as old:
        convert_column(rows, itype)
    assert str(new.value) == str(old.value)


def test_allocations_stop_once_the_ring_has_filled():
    """(c) a 20-batch pass allocates a ring a column and reuses it for
    the rest; the pool outlives the pass, so a second pass allocates
    nothing."""
    topo = Topology(_labelled_model())
    batches = _labelled_batches(20)
    reg = observe_metrics.MetricsRegistry()
    feeder = DeviceFeeder(lambda: iter(batches), topo, depth=2,
                          metrics_registry=reg)
    seen = [_counters(reg) for _ in feeder.batches()]
    reused, allocated = _counters(reg)
    assert allocated == 2 * RING and reused == 2 * (20 - RING)
    assert reused / (reused + allocated) == pytest.approx(1 - RING / 20)
    # the producer runs ahead of the consumer, never behind it
    assert all(a <= 2 * RING for _, a in seen) and seen[-1][1] == 2 * RING
    waits = reg.snapshot()["histograms"][BUFFER_WAIT]
    assert waits["count"] == 20 - RING  # once a batch that recycled
    assert len(list(feeder.batches())) == 20
    assert _counters(reg) == (2 * (40 - RING), 2 * RING)
    assert reg.snapshot()["histograms"][BUFFER_WAIT]["count"] == 40 - RING


class _RecordingLeaf:
    """Stands for the device array made from a slot: records what the
    slot's host array held when the producer waited for it."""

    def __init__(self, host, log):
        self.host, self.log = host, log

    def block_until_ready(self):
        self.log.append(self.host.copy())
        return self


def test_a_slot_is_not_written_before_its_last_leaf_is_ready():
    """(d) the wait for the previous transfer comes before the write."""
    pool, reg = _pool(slots=1)
    first = [np.full(3, 1.0, np.float32), np.full(3, 2.0, np.float32)]
    second = [np.full(3, 7.0, np.float32), np.full(3, 8.0, np.float32)]
    host = pool.assemble("x", first, np.float32)
    log = []
    leaf = _RecordingLeaf(host, log)
    assert pool.sent({"x": leaf}) is None  # allocated, nothing to wait for
    again = pool.assemble("x", second, np.float32)
    assert again is host  # the one slot, recycled
    assert len(log) == 1  # waited once, and at that time ...
    np.testing.assert_array_equal(log[0], np.asarray(first))  # ... unwritten
    np.testing.assert_array_equal(host, np.asarray(second))
    slot = pool._rings["x"].slots[0]
    assert slot.leaf is None  # let go of: the pool pins no dead batch
    waited = pool.sent({"x": _RecordingLeaf(host, log)})
    assert waited is not None and waited >= 0.0
    assert _counters(reg) == (1, 1)


def _tagging_batches():
    samples = _seq_samples(16, lengths=(2, 3))
    base = minibatch.batch(lambda: iter(samples), 4)
    return bucketing.rebucket_batches(base, buckets=[4, 8])


def test_a_short_last_batch_gets_a_fresh_array_and_the_ring_stays():
    """(e) the short batch equals today's feed, touches no counter, and
    the full-size slots go on being recycled after it."""
    topo = Topology(_labelled_model())
    full = _labelled_batches(RING + 2, seed=1)
    short = _labelled_batches(1, rows=3, seed=2)
    stream = full + short + full[:2]
    reg = observe_metrics.MetricsRegistry()
    feeder = DeviceFeeder(lambda: iter(stream), topo, metrics_registry=reg)
    got = list(feeder.batches())
    for fb, batch in zip(got, stream):
        want = convert_feed(topo, batch)
        for name in ("x", "y"):
            np.testing.assert_array_equal(np.asarray(fb.feed[name]),
                                          np.asarray(want[name]))
    assert got[RING + 2].feed["x"].shape == (3, 6)
    assert _counters(reg) == (2 * 4, 2 * RING)  # as if it had not come


def test_sequence_slots_bypass_the_pool():
    """(e) a BucketBatch of sequence slots: padded shapes change from
    batch to batch, nothing is pooled."""
    topo = Topology(_tagging_model())
    reg = observe_metrics.MetricsRegistry()
    got = list(DeviceFeeder(_tagging_batches(), topo,
                            metrics_registry=reg).batches())
    assert got and _counters(reg) == (0, 0)
    assert BUFFER_WAIT not in reg.snapshot()["histograms"] or \
        reg.snapshot()["histograms"][BUFFER_WAIT]["count"] == 0
    for fb, batch in zip(got, _tagging_batches()()):
        want = convert_feed(topo, batch, max_len=batch.bucket)
        for name, value in want.items():
            np.testing.assert_array_equal(np.asarray(fb.feed[name].data),
                                          np.asarray(value.data))
            np.testing.assert_array_equal(np.asarray(fb.feed[name].lengths),
                                          np.asarray(value.lengths))


@pytest.mark.parametrize("itype, col", [
    (dt.sparse_binary_vector(5000), [[1, 7], [4999]]),         # SparseRows
    (dt.sparse_vector(5000), [[(1, 0.5)], [(9, 2.0)]]),  # SparseRows
    (dt.sparse_binary_vector(16), [[1, 7], [3]]),              # densified
    (dt.integer_value_sequence(9), [[1, 2, 3], [4]]),
    (dt.dense_vector_sequence(2), [[[1.0, 2.0]], [[3.0, 4.0], [5.0, 6.0]]]),
    (dt.integer_value_sub_sequence(9), [[[1, 2], [3]], [[4]]]),
], ids=["sparse_binary_rows", "sparse_float_rows", "sparse_densified",
        "index_sequence", "dense_sequence", "nested_sequence"])
def test_only_fixed_shape_dense_and_index_columns_ask_for_a_buffer(itype,
                                                                   col):
    """(e) every other slot kind converts as before and never asks."""
    asked = []

    def assemble(col, dtype):
        asked.append(dtype)

    got = convert_column(col, itype, assemble=assemble)
    want = convert_column(col, itype)
    assert asked == []
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_custom_convert_bypasses_the_pool():
    """(e) `convert=`: the feeder cannot say what that function keeps."""
    topo = Topology(_labelled_model())
    batches = _labelled_batches(RING + 2)
    seen = []

    def convert(topology, data_batch, feeding, max_len):
        seen.append(len(data_batch))
        return convert_feed(topology, data_batch, feeding, max_len=max_len)

    reg = observe_metrics.MetricsRegistry()
    got = list(DeviceFeeder(lambda: iter(batches), topo, convert=convert,
                            metrics_registry=reg).batches())
    assert seen == [8] * (RING + 2) and _counters(reg) == (0, 0)
    assert all(fb.buffer_wait_ms == 0.0 for fb in got)
    for fb, batch in zip(got, batches):
        np.testing.assert_array_equal(
            np.asarray(fb.feed["x"]), np.asarray(convert_feed(topo, batch)["x"]))


@pytest.mark.parametrize("call", ["convert_feed", "train", "test", "infer"])
def test_callers_without_a_feeder_recycle_nothing(call):
    """`convert_feed` outside a feeder's scope, `SGD.train(feed_pipeline=
    False)`, `SGD.test` and `paddle.infer` allocate per batch as they did:
    the process-wide counters do not move."""
    cost = _labelled_model()
    topo = Topology(cost)
    batches = _labelled_batches(RING + 2)
    reg = observe_metrics.get_registry()
    before = _counters(reg)
    if call == "convert_feed":
        for batch in batches:
            feed = convert_feed(topo, batch)
            np.testing.assert_array_equal(
                np.asarray(feed["x"]),
                np.asarray([r[0] for r in batch], dtype=np.float32))
    else:
        params = Parameters.create(cost)
        trainer = paddle.trainer.SGD(cost, params,
                                     opt.Momentum(learning_rate=1e-2))
        if call == "train":
            trainer.train(lambda: iter(batches), num_passes=1,
                          event_handler=lambda e: None)
        elif call == "test":
            trainer.test(lambda: iter(batches))
        else:
            out = cost.inputs[0]
            paddle.infer(output_layer=out, parameters=params,
                         input=[(r[0],) for r in batches[0]])
    assert _counters(reg) == before


def test_a_stand_in_for_convert_feed_keeps_its_four_arguments(monkeypatch):
    """The pool reaches `convert_feed` by a scope of the producer's
    thread, not by an argument: a function of the old signature put in
    its place (the benchmark's stand-ins) works, and still recycles."""
    from paddle_tpu import topology as topology_mod

    whole = topology_mod.convert_feed
    calls = []

    def doubled(topo, data_batch, feeding=None, max_len=None):
        calls.append(len(data_batch))
        return whole(topo, data_batch + data_batch, feeding, max_len=max_len)

    monkeypatch.setattr(topology_mod, "convert_feed", doubled)
    topo = Topology(_labelled_model())
    batches = _labelled_batches(RING + 2)
    reg = observe_metrics.MetricsRegistry()
    got = list(DeviceFeeder(lambda: iter(batches), topo,
                            metrics_registry=reg).batches())
    assert calls == [8] * (RING + 2)
    assert _counters(reg) == (2 * 2, 2 * RING)
    for fb, batch in zip(got, batches):
        np.testing.assert_array_equal(
            np.asarray(fb.feed["x"]),
            np.asarray([r[0] for r in batch + batch], dtype=np.float32))
    # the scope was the producer thread's, and is closed
    assert getattr(topology_mod._owner, "buffers", None) is None


def test_the_recycling_scope_nests_and_restores():
    from paddle_tpu import topology as topology_mod

    outer, _ = _pool()
    inner, reg = _pool()
    topo = Topology(_labelled_model())
    batch = _labelled_batches(1)[0]
    with topology_mod.recycling_into(outer):
        with topology_mod.recycling_into(inner):
            convert_feed(topo, batch)
        assert topology_mod._owner.buffers is outer
        assert set(inner._rings) == {"x", "y"} and not outer._rings
    assert topology_mod._owner.buffers is None
    assert _counters(reg) == (0, 2)


def test_an_open_chunk_holds_k_distinct_buffers_bytes():
    """(f) chunks(k=3) over depth=2: the queue deepens to 3, the ring to
    5, and the three members of an open chunk (and every chunk kept) hold
    their own rows."""
    topo = Topology(_labelled_model())
    batches = _labelled_batches(12, seed=4)
    reg = observe_metrics.MetricsRegistry()
    feeder = DeviceFeeder(lambda: iter(batches), topo, depth=2,
                          metrics_registry=reg)
    chunks = list(feeder.chunks(3))
    assert feeder.depth == 3 and feeder._buffers.slots == 5
    assert [c.steps for c in chunks] == [3, 3, 3, 3]
    assert _counters(reg) == (2 * (12 - 5), 2 * 5)
    members = [fb for c in chunks for fb in c.batches]
    for chunk in chunks:
        assert len({id(m) for m in chunk.feed}) == 3
    for fb, batch in zip(members, batches):
        np.testing.assert_array_equal(
            np.asarray(fb.feed["x"]),
            np.asarray([r[0] for r in batch], dtype=np.float32))
    first = [np.asarray(m["x"]) for m in chunks[0].feed]
    assert not np.array_equal(first[0], first[1])
    assert not np.array_equal(first[1], first[2])


def test_on_a_mesh_a_slot_holds_the_mesh_leaf_not_the_device0_array():
    """(g) under a DataParallel the slot waits for the sharded leaf (ready
    means the one crossing, every device's transfer out of the slot's host
    array, is done) and no device-0 copy of the batch was ever made."""
    n = 4
    dp = _mesh_of(n)
    topo = Topology(_labelled_model())
    batches = _labelled_batches(3)
    reg = observe_metrics.MetricsRegistry()
    moved = _resharded()
    feeder = DeviceFeeder(lambda: iter(batches), topo, parallelism=dp,
                          metrics_registry=reg)
    got = list(feeder.batches())
    for name in ("x", "y"):
        slots = feeder._buffers._rings[name].slots
        assert len(slots) == 3
        for slot, fb in zip(slots, got):
            assert slot.leaf is fb.feed[name]
            assert len(slot.leaf.sharding.device_set) == n
            assert not slot.leaf.sharding.is_fully_replicated
    assert reg.snapshot()["counters"][PLACED_SHARDED] == 2 * 3
    assert _resharded() == moved


# ---- on a mesh a recycled column goes straight to its shards ---------------

PLACED_SHARDED = "paddle_tpu_data_feed_placed_sharded_total"
RESHARDED = "paddle_tpu_data_feed_resharded_total"


def _mesh_of(n):
    from paddle_tpu.parallel.mesh import DataParallel, build_mesh

    return DataParallel(build_mesh({"data": n}, devices=jax.devices()[:n]))


def _resharded():
    """`shard_batch` counts in the process's registry: it serves the step
    thread and evaluation too, whatever registry a feeder was given."""
    return observe_metrics.get_registry().counter(RESHARDED).value


def _placed(reg):
    return reg.snapshot()["counters"].get(PLACED_SHARDED, 0)


class _PlacementSpy:
    """Every hand-over of a host array to a device that the feed's two
    placing calls make: (call, shape, devices the target spans)."""

    def __init__(self, monkeypatch):
        self.seen = []
        put, asarray = jax.device_put, jnp.asarray

        def device_put(x, device=None, **kw):
            if isinstance(x, np.ndarray):
                spans = len(device.device_set) \
                    if isinstance(device, jax.sharding.Sharding) else 1
                self.seen.append(("device_put", x.shape, spans))
            return put(x, device, **kw)

        def spied_asarray(a, *args, **kw):
            if isinstance(a, np.ndarray):
                self.seen.append(("asarray", a.shape, 1))
            return asarray(a, *args, **kw)

        monkeypatch.setattr(jax, "device_put", device_put)
        monkeypatch.setattr(jnp, "asarray", spied_asarray)


def _assert_shards_hold_their_rows(leaf, rows, n):
    assert len(leaf.addressable_shards) == n
    assert len({shard.device for shard in leaf.addressable_shards}) == n
    each = rows.shape[0] // n
    for shard in leaf.addressable_shards:
        assert shard.data.shape[0] == each
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      rows[shard.index])


@pytest.mark.parametrize("n", [2, 4])
def test_on_a_mesh_recycled_columns_go_straight_to_their_shards(
        n, monkeypatch, aligned_slots):
    """(i) shard by shard the rows, bit for bit; (ii) the very sharding
    `shard_batch` would choose, so it passes them through by identity;
    (iii) never an array on one device: counted as placed, none resharded,
    and no single-device hand-over of a column's shape was made."""
    dp = _mesh_of(n)
    topo = Topology(_labelled_model())
    batches = _labelled_batches(RING + 3, seed=6)
    reg = observe_metrics.MetricsRegistry()
    moved = _resharded()
    spy = _PlacementSpy(monkeypatch)
    got = list(DeviceFeeder(lambda: iter(batches), topo, parallelism=dp,
                            metrics_registry=reg).batches())
    assert len(got) == RING + 3 and _counters(reg) == (2 * 3, 2 * RING)
    for fb, batch in zip(got, batches):
        rows = {"x": np.asarray([r[0] for r in batch], dtype=np.float32),
                "y": np.asarray([r[1] for r in batch], dtype=np.int32)}
        one_device = convert_feed(topo, batch)
        want = dp.shard_batch(one_device)
        again = dp.shard_batch(fb.feed)
        for name in ("x", "y"):
            leaf = fb.feed[name]
            assert leaf.dtype == one_device[name].dtype
            _assert_shards_hold_their_rows(leaf, rows[name], n)
            assert leaf.sharding == want[name].sharding \
                == dp.batch_leaf_sharding(rows[name].shape)
            assert again[name] is leaf
    assert _placed(reg) == 2 * (RING + 3)
    # the comparison's own `shard_batch(convert_feed(...))` moved two a batch
    assert _resharded() - moved == 2 * (RING + 3)
    feeders = [s for s in spy.seen if s[0] == "device_put"]
    assert feeders == [("device_put", shape, n)
                       for _ in batches for shape in ((8, 6), (8,))]


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("case", ["rows_not_divided", "short_last_batch"])
def test_on_a_mesh_rows_the_direct_path_declines_take_todays(case, n):
    """(iv) a row count the mesh does not divide, and a short last batch
    (which no ring takes): one device first, then `shard_batch`, with the
    result `shard_batch(convert_feed(...))` gives."""
    dp = _mesh_of(n)
    topo = Topology(_labelled_model())
    if case == "rows_not_divided":
        stream = _labelled_batches(RING + 2, rows=7, seed=8)
        odd = list(range(len(stream)))
    else:
        stream = _labelled_batches(RING + 1, seed=8) \
            + _labelled_batches(1, rows=4, seed=9)
        odd = [RING + 1]
    reg = observe_metrics.MetricsRegistry()
    moved = _resharded()
    got = list(DeviceFeeder(lambda: iter(stream), topo, parallelism=dp,
                            metrics_registry=reg).batches())
    assert _resharded() - moved == 2 * len(odd)
    assert _placed(reg) == 2 * (len(stream) - len(odd))
    for i, (fb, batch) in enumerate(zip(got, stream)):
        want = dp.shard_batch(convert_feed(topo, batch))
        for name in ("x", "y"):
            assert fb.feed[name].sharding == want[name].sharding
            assert fb.feed[name].dtype == want[name].dtype
            np.testing.assert_array_equal(np.asarray(fb.feed[name]),
                                          np.asarray(want[name]))
        replicated = fb.feed["x"].sharding.is_fully_replicated
        assert replicated == (case == "rows_not_divided")
        assert (i in odd) or not replicated


def _sparse_model(dim=5000, classes=4):
    reset_name_counters()
    ids = L.data(name="ids", type=dt.sparse_binary_vector(dim))
    y = L.data(name="y", type=dt.integer_value(classes))
    return L.classification_cost(input=L.fc(input=ids, size=classes),
                                 label=y)


@pytest.mark.parametrize("slot", ["sequence", "sparse", "convert"])
def test_on_a_mesh_other_slots_are_moved_by_shard_batch_as_before(slot):
    """(v) what is made on one device (inside `SequenceBatch.from_sequences`
    and `SparseRows.from_rows`, or by a `convert=` the feeder cannot see
    into) still crosses twice, lands where `shard_batch` puts it, and is
    counted as resharded."""
    dp = _mesh_of(4)
    convert = None
    if slot == "sequence":
        topo, reader = Topology(_tagging_model()), _tagging_batches()
    elif slot == "sparse":
        topo = Topology(_sparse_model())
        rng = np.random.RandomState(2)
        made = [[(sorted(rng.choice(5000, 3, replace=False).tolist()),
                  int(rng.randint(4))) for _ in range(8)] for _ in range(3)]
        reader = lambda: iter(made)  # noqa: E731
    else:
        topo = Topology(_labelled_model())
        made = _labelled_batches(3)
        reader = lambda: iter(made)  # noqa: E731

        def convert(topology, data_batch, feeding, max_len):
            return convert_feed(topology, data_batch, feeding,
                                max_len=max_len)

    reg = observe_metrics.MetricsRegistry()
    moved = _resharded()
    got = list(DeviceFeeder(reader, topo, parallelism=dp, convert=convert,
                            metrics_registry=reg).batches())
    by_shard_batch = _resharded() - moved
    leaves = 0
    for fb, batch in zip(got, reader()):
        want = dp.shard_batch(convert_feed(
            topo, batch, max_len=getattr(batch, "bucket", None)))
        assert jax.tree_util.tree_structure(fb.feed) \
            == jax.tree_util.tree_structure(want)
        for a, b in zip(jax.tree_util.tree_leaves(fb.feed),
                        jax.tree_util.tree_leaves(want)):
            assert a.sharding == b.sharding and a.dtype == b.dtype
            assert len(a.sharding.device_set) == 4
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            leaves += 1
    # the recycled label column of the sparse model went straight there
    direct = len(got) if slot == "sparse" else 0
    assert _placed(reg) == direct
    assert by_shard_batch == leaves - direct and by_shard_batch > 0


@pytest.mark.parametrize("n", [2, 4])
def test_on_a_mesh_chunks_are_stacks_of_leaves_placed_once(n):
    """(vi) `chunks(3)`: every member of a stack lies on the mesh, holds
    its rows, and no leaf was moved from a device to get there."""
    dp = _mesh_of(n)
    topo = Topology(_labelled_model())
    batches = _labelled_batches(9, seed=12)
    reg = observe_metrics.MetricsRegistry()
    moved = _resharded()
    chunks = list(DeviceFeeder(lambda: iter(batches), topo, depth=2,
                               parallelism=dp,
                               metrics_registry=reg).chunks(3))
    assert [c.steps for c in chunks] == [3, 3, 3]
    assert all(c.stacked for c in chunks)
    members = [feed for c in chunks for feed in c.feed]
    for feed, batch in zip(members, batches):
        rows = np.asarray([r[0] for r in batch], dtype=np.float32)
        _assert_shards_hold_their_rows(feed["x"], rows, n)
        assert dp.shard_batch(feed)["x"] is feed["x"]
        assert dp.shard_batch(feed)["y"] is feed["y"]
    assert _resharded() == moved and _placed(reg) == 2 * 9


def test_a_plan_without_the_rule_gets_its_shard_batch_alone():
    """A `parallelism` that has a `shard_batch` and no
    `batch_leaf_sharding`: the feeder places on one device and hands the
    feed to that `shard_batch`, as before this rule existed."""
    dp = _mesh_of(2)

    class OldPlan:
        def shard_batch(self, tree):
            return dp.shard_batch(tree)

    topo = Topology(_labelled_model())
    batches = _labelled_batches(RING + 2)
    reg = observe_metrics.MetricsRegistry()
    moved = _resharded()
    got = list(DeviceFeeder(lambda: iter(batches), topo,
                            parallelism=OldPlan(),
                            metrics_registry=reg).batches())
    assert _placed(reg) == 0 and _resharded() - moved == 2 * (RING + 2)
    assert _counters(reg) == (2 * 2, 2 * RING)  # it recycles all the same
    for fb, batch in zip(got, batches):
        assert len(fb.feed["x"].sharding.device_set) == 2
        np.testing.assert_array_equal(
            np.asarray(fb.feed["x"]),
            np.asarray([r[0] for r in batch], dtype=np.float32))


@pytest.mark.parametrize("n", [1, 2, 4])
def test_a_placed_array_is_looked_for_in_every_shard(n):
    """`_lives_in` on a mesh: a CPU device may wrap its own aligned slice
    of the host array, so each addressable shard is asked; and the copy
    `_place` makes of such an array keeps its sharding."""
    from paddle_tpu.topology import _lives_in

    host = _aligned_like(np.empty((64, 16), np.float32))  # 4 KiB a row block
    host[...] = np.arange(64, dtype=np.float32)[:, None]
    other = _aligned_like(host)
    dp = _mesh_of(n)
    want = dp.batch_leaf_sharding(host.shape)
    placed = jax.device_put(host, want)
    assert _lives_in(placed, host) and not _lives_in(placed, other)
    copied = jnp.copy(placed)
    assert copied.sharding == want and not _lives_in(copied, host)
    host[...] = -1.0
    np.testing.assert_array_equal(
        np.asarray(copied)[:, 0], np.arange(64, dtype=np.float32))


def test_a_new_full_shape_starts_the_columns_ring_afresh():
    """More rows than the ring's (or other trailing dims) is not a short
    last batch: the ring is remade at the new shape."""
    pool, reg = _pool(slots=2)
    small = [np.zeros(3, np.float32)] * 2
    big = [np.ones(3, np.float32)] * 5
    assert pool.assemble("x", small, np.float32).shape == (2, 3)
    pool.sent({"x": _RecordingLeaf(None, [])})
    assert pool.assemble("x", big, np.float32).shape == (5, 3)
    pool.sent({"x": _RecordingLeaf(None, [])})
    assert pool.assemble("x", small, np.float32) is None  # now the short one
    assert _counters(reg) == (0, 2)
