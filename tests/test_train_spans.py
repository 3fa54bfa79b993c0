"""The train path's phases (docs/observability.md "Train-path spans"): the
program's spans on the profiler's clock, the always-on histograms that
split the feed and the step, and the slowest-steps reservoir."""

import glob
import logging
import os
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import activation as A, data_type as dt, layer as L
from paddle_tpu import event as v2_event, optimizer as opt
from paddle_tpu.data.feeder import DeviceFeeder
from paddle_tpu.graph import reset_name_counters
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import spans
from paddle_tpu.parallel.mesh import DataParallel, build_mesh
from paddle_tpu.parameters import Parameters
from paddle_tpu.utils.logger import logger
from paddle_tpu.utils.stat import profiler_trace

STEP_NAMES = ("feed_read", "feed_convert", "feed_place", "feed_buffer_wait",
              "feed_put", "feed", "train_step", "eval_readback", "handler")
HISTOGRAMS = ("paddle_tpu_data_feed_read_ms", "paddle_tpu_data_feed_host_ms",
              "paddle_tpu_data_feed_place_ms",
              "paddle_tpu_data_feed_backpressure_ms",
              "paddle_tpu_train_dispatch_ms", "paddle_tpu_train_readback_ms",
              "paddle_tpu_train_handler_ms")
PRODUCERS = HISTOGRAMS[:4]
BUFFER_WAIT = "paddle_tpu_data_feed_buffer_wait_ms"
DEPTH = 2
RING = DEPTH + 2  # the feeder's recycled host buffers a column


def _trainer(parallelism=None, dim=8, classes=4):
    reset_name_counters()
    x = L.data(name="x", type=dt.dense_vector(dim))
    lab = L.data(name="y", type=dt.integer_value(classes))
    hidden = L.fc(input=x, size=16, act=A.Tanh())
    cost = L.classification_cost(input=L.fc(input=hidden, size=classes),
                                 label=lab)
    return paddle.trainer.SGD(cost, Parameters.create(cost),
                              opt.Momentum(learning_rate=0.1),
                              parallelism=parallelism)


def _batches(n, rows=8, dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    made = [[(rng.randn(dim).astype(np.float32), int(rng.randint(classes)))
             for _ in range(rows)] for _ in range(n)]
    return lambda: iter(made)


def _counts():
    hists = observe_metrics.get_registry().snapshot()["histograms"]
    return {name: hists.get(name, {"count": 0})["count"]
            for name in HISTOGRAMS}


@pytest.fixture
def recorded():
    """The process-global tracer, recording, emptied before and after."""
    tracer = spans.get_tracer()
    previous = tracer.record_events
    tracer.record_events = True
    tracer.reset()
    yield tracer
    tracer.record_events = previous
    tracer.reset()


# -- (a) every span of a step lies on the profiler's clock -------------------

def test_a_steps_spans_lie_on_the_profilers_host_lines(tmp_path):
    from jax.profiler import ProfileData

    trainer = _trainer()
    trainer.train(_batches(1), event_handler=lambda e: None,
                  feed_pipeline=True)  # compiles outside the trace
    steps = RING + 2  # the last two batches recycle a host buffer
    with profiler_trace(str(tmp_path)):
        trainer.train(_batches(steps), event_handler=lambda e: None,
                      feed_pipeline=True)
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines = {}  # line number -> {name: [(start, end, stats)]}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            assert not any(e.name.startswith("paddle_tpu.")
                           for line in plane.lines for e in line.events)
            continue
        for number, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("paddle_tpu."):
                    lines.setdefault(number, {}).setdefault(
                        e.name[len("paddle_tpu."):], []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    seen = set().union(*lines.values())
    assert set(STEP_NAMES) <= seen, sorted(seen)
    producer = [n for n, names in lines.items() if "feed_convert" in names]
    step = [n for n, names in lines.items() if "train_step" in names]
    assert len(producer) == 1 and len(step) == 1 and producer != step
    # the producer's four are on its line, the step thread's four on its own
    assert {"feed_read", "feed_convert", "feed_place", "feed_buffer_wait",
            "feed_put"} <= set(lines[producer[0]])
    assert {"feed", "train_step", "eval_readback",
            "handler"} <= set(lines[step[0]])
    assert not {"feed_place", "feed_buffer_wait"} & set(lines[step[0]])
    converts = lines[producer[0]]["feed_convert"]
    assert len(converts) == steps
    for name in ("feed_place", "feed_buffer_wait"):
        for start, end, _ in lines[producer[0]][name]:
            assert any(c0 <= start and end <= c1 for c0, c1, _ in converts)
    assert len(lines[producer[0]]["feed_buffer_wait"]) == 2 * 2
    # the number that varies is the event's stat, never part of its name
    assert sorted(stats["batch"] for _, _, stats
                  in converts) == list(range(steps))
    assert sorted(stats["batch"] for _, _, stats
                  in lines[step[0]]["train_step"]) == list(range(steps))


# -- (c) the seven histograms, and host + place == convert -------------------

def test_each_phase_histogram_counts_every_step():
    steps = 6
    trainer = _trainer()
    before = _counts()
    trainer.train(_batches(steps), event_handler=lambda e: None,
                  feed_pipeline=True)
    after = _counts()
    for name in HISTOGRAMS:
        made = after[name] - before[name]
        if name in PRODUCERS:
            assert steps - DEPTH <= made <= steps + DEPTH, (name, made)
        else:
            assert made == steps, (name, made)


def _places_per_convert(tracer):
    events = tracer.events()
    converts = [e for e in events if e[0] == "feed_convert"]
    places = [e for e in events if e[0] == "feed_place"]
    assert converts and all(e[6] == "feed_convert" for e in places)
    assert all(e[4]["batch"] == i for i, e in enumerate(converts))
    return len(places) / len(converts)


def test_host_plus_place_is_convert_and_the_mesh_places_once_more(recorded):
    """... once more than nothing: a recycled column is one `feed_place`
    on a mesh as on one device, since it goes straight to its shards
    (two before PR 27: device 0, then `shard_batch`'s copy from there)."""
    trainer = _trainer()
    taken = list(DeviceFeeder(_batches(4), trainer.topology).batches())
    assert [fb.seq for fb in taken] == [0, 1, 2, 3]
    for fb in taken:
        # no batch of the first ring recycles a buffer: the wait is 0 and
        # the identity is PR 24's
        assert fb.buffer_wait_ms == 0.0
        assert fb.host_ms + fb.place_ms == pytest.approx(fb.convert_ms,
                                                         abs=1e-6)
        assert fb.place_ms > 0 and fb.host_ms > 0 and fb.read_ms >= 0
    assert _places_per_convert(recorded) == 2  # the two columns
    recorded.reset()
    mesh = DataParallel(build_mesh({"data": 2}, devices=jax.devices()[:2]))
    sharded = list(DeviceFeeder(_batches(4), trainer.topology,
                                parallelism=mesh).batches())
    for fb in sharded:
        assert fb.host_ms + fb.place_ms == pytest.approx(fb.convert_ms,
                                                         abs=1e-6)
        assert len(fb.feed["x"].sharding.device_set) == 2
    assert _places_per_convert(recorded) == 2
    # a batch the ring does not take still crosses twice: one device, then
    # shard_batch's hand-over, a feed_place more for each leaf it moves
    recorded.reset()
    stream = list(_batches(RING)()) + list(_batches(1, rows=4)())
    list(DeviceFeeder(lambda: iter(stream), trainer.topology,
                      parallelism=mesh).batches())
    places = [e for e in recorded.events() if e[0] == "feed_place"]
    assert len(places) == 2 * RING + 4


@pytest.mark.parametrize("n", [2, 4])
def test_on_a_mesh_a_recycled_column_is_one_feed_place(n, recorded):
    """Each recycled column has exactly one `feed_place` child under its
    batch's `feed_convert`, on the producer's line, and the identity
    `host = convert - place - buffer_wait` closes with the ring wrapped;
    so do the histograms' sums, which the two sums of PERF.md section 3
    are made of."""
    mesh = DataParallel(build_mesh({"data": n}, devices=jax.devices()[:n]))
    trainer = _trainer()
    registry = observe_metrics.MetricsRegistry()
    steps = RING + 3
    taken = list(DeviceFeeder(_batches(steps), trainer.topology,
                              parallelism=mesh,
                              metrics_registry=registry).batches())
    events = recorded.events()
    converts = [e for e in events if e[0] == "feed_convert"]
    places = [e for e in events if e[0] == "feed_place"]
    waits = [e for e in events if e[0] == "feed_buffer_wait"]
    assert len(converts) == steps and len(places) == 2 * steps
    assert len(waits) == 2 * 3
    assert all(e[6] == "feed_convert" for e in places + waits)
    producer = {e[3] for e in converts}
    assert len(producer) == 1 and {e[3] for e in places + waits} == producer
    for convert in converts:  # two inside each batch's own interval
        inside = [e for e in places if convert[1] <= e[1]
                  and e[1] + e[2] <= convert[1] + convert[2]]
        assert len(inside) == 2
    for i, fb in enumerate(taken):
        assert (fb.buffer_wait_ms > 0) == (i >= RING)
        assert fb.place_ms > 0 and fb.host_ms > 0
        assert fb.host_ms + fb.place_ms + fb.buffer_wait_ms == pytest.approx(
            fb.convert_ms, abs=1e-9)
    hists = registry.snapshot()["histograms"]
    place_ms = sum(e[2] for e in places) * 1e3
    wait_ms = sum(e[2] for e in waits) * 1e3
    convert_ms = sum(e[2] for e in converts) * 1e3
    assert hists["paddle_tpu_data_feed_place_ms"]["sum"] == \
        pytest.approx(place_ms)
    assert hists[BUFFER_WAIT]["sum"] == pytest.approx(wait_ms)
    # the producer's sum: host + place + buffer_wait = convert ...
    assert hists["paddle_tpu_data_feed_host_ms"]["sum"] + place_ms \
        + wait_ms == pytest.approx(convert_ms)
    # ... and the consumer's histogram of convert is the same batches'
    assert hists["paddle_tpu_data_feed_convert_ms"]["sum"] == \
        pytest.approx(convert_ms)
    counters = registry.snapshot()["counters"]
    assert counters["paddle_tpu_data_feed_placed_sharded_total"] == 2 * steps


def test_lives_in_looks_at_both_devices_of_a_two_device_array():
    """It unpacked exactly one device before PR 27 and raised here."""
    from paddle_tpu.topology import _lives_in, _place

    mesh = DataParallel(build_mesh({"data": 2}, devices=jax.devices()[:2]))
    raw = np.empty(8 * 16 * 4 + 64, np.uint8)
    start = (-raw.ctypes.data) % 64
    host = raw[start:start + 8 * 16 * 4].view(np.float32).reshape(8, 16)
    host[...] = 3.0
    placed = jax.device_put(host, mesh.batch_leaf_sharding(host.shape))
    assert len(placed.devices()) == 2
    assert _lives_in(placed, host)  # each device wraps its aligned rows
    assert not _lives_in(placed, np.empty_like(host))
    assert not _lives_in(jax.numpy.copy(placed), host)
    # one device, as ever: a fresh array may be shared, a recycled one not
    assert not _lives_in(_place(host, recycled=True), host)


def test_host_plus_place_plus_buffer_wait_is_convert(recorded):
    """The identity with its new term: `feed_convert`'s children are its
    placements and its waits for a recycled buffer, `place_ms` is the
    placements only, and what is left is host assembly."""
    trainer = _trainer()
    registry = observe_metrics.MetricsRegistry()
    steps = RING + 3
    taken = list(DeviceFeeder(_batches(steps), trainer.topology,
                              metrics_registry=registry).batches())
    assert len(taken) == steps
    events = recorded.events()
    waits = [e for e in events if e[0] == "feed_buffer_wait"]
    places = [e for e in events if e[0] == "feed_place"]
    converts = [e for e in events if e[0] == "feed_convert"]
    # two columns a batch, each batch after the ring has filled
    assert len(waits) == 2 * 3 and len(places) == 2 * steps
    assert all(e[6] == "feed_convert" for e in waits)
    producer = {e[3] for e in converts}
    assert len(producer) == 1 and {e[3] for e in waits + places} == producer
    wait_ms = sum(e[2] for e in waits) * 1e3
    place_ms = sum(e[2] for e in places) * 1e3
    for i, fb in enumerate(taken):
        assert (fb.buffer_wait_ms > 0) == (i >= RING)
        assert fb.place_ms > 0 and fb.host_ms > 0
        assert fb.host_ms + fb.place_ms + fb.buffer_wait_ms == pytest.approx(
            fb.convert_ms, abs=1e-9)
    assert sum(fb.buffer_wait_ms for fb in taken) == pytest.approx(wait_ms)
    assert sum(fb.place_ms for fb in taken) == pytest.approx(place_ms)
    hists = registry.snapshot()["histograms"]
    assert hists[BUFFER_WAIT]["count"] == 3  # once a batch that recycled
    assert hists[BUFFER_WAIT]["sum"] == pytest.approx(wait_ms)
    assert hists["paddle_tpu_data_feed_place_ms"]["sum"] == \
        pytest.approx(place_ms)
    assert hists["paddle_tpu_data_feed_host_ms"]["count"] == steps


def test_the_resume_cursor_keeps_the_batch_numbers(recorded):
    trainer = _trainer()
    taken = list(DeviceFeeder(_batches(5), trainer.topology).batches(skip=2))
    assert [fb.seq for fb in taken] == [2, 3, 4]
    waits = [e for e in recorded.events() if e[0] == "feed"]
    assert [e[4]["batch"] for e in waits] == [2, 3, 4, 5]  # the last: the end


# -- (d) the slowest steps keep their phases ---------------------------------

class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.mark.parametrize("loop", ["pipelined", "plain", "fused"])
def test_a_slow_handler_names_the_slowest_step(loop, monkeypatch):
    trainer = _trainer()
    kwargs = {"pipelined": {"feed_pipeline": True}, "plain": {},
              "fused": {"steps_per_call": 2}}[loop]
    trainer.train(_batches(2), event_handler=lambda e: None, **kwargs)
    trainer.slow_steps.reset()  # the compiling steps

    def handler(event):
        if isinstance(event, v2_event.EndIteration) and event.batch_id == 3:
            time.sleep(0.05)

    trainer.train(_batches(8), event_handler=handler, **kwargs)
    slowest = trainer.slow_steps.slowest()
    assert len(slowest) == (4 if loop == "fused" else 5)
    worst = slowest[0]
    assert worst["latency_ms"] >= 50.0
    assert max(worst["phases"], key=worst["phases"].get) == "handler"
    assert worst["phases"]["handler"] >= 50.0
    # the phases are those of the step's own wall interval: they sum to it
    # less the loop's unspanned lines
    own = sum(worst["phases"][k] for k in ("wait", "dispatch", "readback",
                                           "handler"))
    assert 0.75 * worst["latency_ms"] <= own <= worst["latency_ms"] + 1e-3
    if loop == "pipelined":
        assert {"read", "host", "place", "buffer_wait",
                "backpressure"} <= set(worst["phases"])

    # the operator's reader: the per-pass dump under PADDLE_TPU_STATS=1
    monkeypatch.setenv("PADDLE_TPU_STATS", "1")
    seen = _Lines()
    level = logger.level
    logger.addHandler(seen)
    logger.setLevel(logging.INFO)
    try:
        trainer.train(_batches(8), event_handler=handler, **kwargs)
    finally:
        logger.setLevel(level)
        logger.removeHandler(seen)
    head = [i for i, text in enumerate(seen.lines)
            if text.startswith("======= slowest steps of pass 0")]
    assert len(head) == 1
    first = seen.lines[head[0] + 1]
    assert first.split()[0] == "step" and "handler=5" in first
    assert trainer.slow_steps.slowest() == []  # reset with the StatSet


# -- (e) a span is cheap when nobody traces ----------------------------------

def test_a_span_is_cheap_when_nobody_traces():
    tracer = spans.SpanTracer("t", stats=None, record_events=False)
    rounds = 2000
    best = float("inf")
    for _ in range(5):
        start = time.perf_counter()
        for i in range(rounds):
            with tracer.span("feed_place", args={"batch": i}):
                pass
        best = min(best, (time.perf_counter() - start) / rounds)
    # a guard against a slow path (an import, a lock, a file), not a timing
    assert best < 20e-6, best
