"""Set-up's phases (docs/observability.md "Set-up spans"): what a process
does before its first step and round each `train` call lies on the
profiler's clock and in always-on histograms, and JAX's own compile phases
are timed once each by the process's one `jax.monitoring` listener."""

import glob
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import activation as A, data_type as dt, layer as L
from paddle_tpu import optimizer as opt
from paddle_tpu.graph import reset_name_counters
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import spans, steplog
from paddle_tpu.parameters import Parameters
from paddle_tpu.utils import compile_cache
from paddle_tpu.utils.stat import profiler_trace

PHASES = dict((name, hist) for name, (hist, _)
              in spans.PHASE_HISTOGRAMS.items())
COMPILE = tuple(hist for hist, _ in compile_cache.PHASE_HISTOGRAMS.values())
TRACE, LOWER, BACKEND = COMPILE


def _cost(dim=8, classes=4):
    reset_name_counters()
    x = L.data(name="x", type=dt.dense_vector(dim))
    lab = L.data(name="y", type=dt.integer_value(classes))
    hidden = L.fc(input=x, size=16, act=A.Tanh())
    return L.classification_cost(input=L.fc(input=hidden, size=classes),
                                 label=lab)


def _trainer():
    cost = _cost()
    return paddle.trainer.SGD(cost, Parameters.create(cost),
                              opt.Momentum(learning_rate=0.1))


def _batches(n, rows=8, dim=8, classes=4):
    rng = np.random.RandomState(0)
    made = [[(rng.randn(dim).astype(np.float32), int(rng.randint(classes)))
             for _ in range(rows)] for _ in range(n)]
    return lambda: iter(made)


def _hists():
    """{histogram: (count, sum in ms)} of the process's registry."""
    held = observe_metrics.get_registry().snapshot()["histograms"]
    return {name: (h["count"], h["sum"]) for name, h in held.items()}


def _added(before, after, name):
    c0, s0 = before.get(name, (0, 0.0))
    c1, s1 = after.get(name, (0, 0.0))
    return c1 - c0, s1 - s0


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


# -- the spans, on the profiler's clock and in their histograms --------------

def test_every_setup_span_lies_on_the_profilers_host_line(tmp_path):
    from jax.profiler import ProfileData

    import importlib
    import sys

    # a submodule no test imports by itself, so that its first use is here
    sys.modules.pop("paddle_tpu.interop", None)
    vars(paddle).pop("interop", None)
    before = _hists()
    with profiler_trace(str(tmp_path)):
        assert paddle.interop is importlib.import_module("paddle_tpu.interop")
        assert paddle.interop  # the second use imports nothing
        paddle.init(use_tpu=False)
        cost = _cost()
        params = Parameters.create(cost)
        params.update_from({name: params.get(name)
                            for name in params.names()})
        trainer = paddle.trainer.SGD(cost, params,
                                     opt.Momentum(learning_rate=0.1))
        trainer.train(_batches(2), event_handler=lambda e: None,
                      feed_pipeline=True)
    after = _hists()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines = {}  # line number -> {name: [(start, end, stats)]}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for number, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("paddle_tpu."):
                    lines.setdefault(number, {}).setdefault(
                        e.name[len("paddle_tpu."):], []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    step = [names for names in lines.values() if "train_step" in names]
    assert len(step) == 1
    step = step[0]
    # all of them on the thread that made the calls, the step's own
    assert set(PHASES) <= set(step), sorted(step)
    for name in ("init", "params_create", "trainer_prepare", "train_enter",
                 "train_exit"):
        assert len(step[name]) == 1, name
        assert _added(before, after, PHASES[name])[0] == 1, name
    # a first use inside another's import is that one's time: the
    # outermost observe (here also whatever else this process first uses)
    imports = step["import"]
    outermost = [e for e in imports
                 if not any(o is not e and o[0] <= e[0] and e[1] <= o[1]
                            for o in imports)]
    assert "interop" in [e[2]["module"] for e in outermost]
    assert _added(before, after, PHASES["import"])[0] == len(outermost)
    # a one-pass call reads every parameter back twice: at its pass's end
    # and at its own; each lies inside the way out, update_from inside each
    assert len(step["sync_back"]) == 2
    assert _added(before, after, PHASES["sync_back"])[0] == 2
    (leave0, leave1, _), = step["train_exit"]
    for start, end, stats in step["sync_back"]:
        assert leave0 <= start and end <= leave1
        assert stats["bytes"] > 0
    # the user's update_from observes; the two inside sync_back do not
    assert len(step["params_update"]) == 3
    assert _added(before, after, PHASES["params_update"])[0] == 1
    inside = [u for u in step["params_update"]
              if any(s0 <= u[0] and u[1] <= s1
                     for s0, s1, _ in step["sync_back"])]
    assert len(inside) == 2
    # the way in ends before the first step, the way out begins after the
    # last one was read back
    (enter0, enter1, _), = step["train_enter"]
    assert enter1 <= min(s for s, _, _ in step["train_step"])
    assert leave0 >= max(e for _, e, _ in step["eval_readback"])
    (_, _, prepare), = step["trainer_prepare"]
    assert prepare["bytes"] == sum(params.get(n).nbytes
                                   for n in params.names())


def test_sync_back_counts_an_occurrence_and_covers_update_from(recorded):
    trainer = _trainer()
    before = _hists()
    trainer.train(_batches(3), event_handler=lambda e: None, num_passes=2)
    after = _hists()
    events = recorded.events()
    syncs = [e for e in events if e[0] == "sync_back"]
    # one a pass's end, one at the call's: the last pass's and the call's
    # own are the way out's children, an earlier pass's lies in the loop
    assert [e[6] for e in syncs] == [None, "train_exit", "train_exit"]
    count, total_ms = _added(before, after, PHASES["sync_back"])
    assert count == 3
    assert total_ms == pytest.approx(sum(e[2] for e in syncs) * 1e3)
    updates = [e for e in events if e[0] == "params_update"]
    assert len(updates) == 3 and all(e[6] == "sync_back" for e in updates)
    for update, sync in zip(updates, syncs):
        assert sync[1] <= update[1]
        assert update[1] + update[2] <= sync[1] + sync[2] + 1e-9
    assert _added(before, after, PHASES["params_update"])[0] == 0
    assert _added(before, after, PHASES["train_enter"])[0] == 1
    count, exit_ms = _added(before, after, PHASES["train_exit"])
    assert count == 1
    # the reader's subtraction: the way out is at least its sync_backs
    assert exit_ms >= sum(e[2] for e in syncs[1:]) * 1e3
    # nothing is read back where the caller said so
    before = after
    trainer.train(_batches(2), event_handler=lambda e: None,
                  sync_params=False)
    after = _hists()
    assert _added(before, after, PHASES["sync_back"])[0] == 0
    assert _added(before, after, PHASES["train_exit"])[0] == 1


def test_a_train_call_that_raises_closes_both_spans(recorded):
    trainer = _trainer()

    def handler(event):
        raise RuntimeError("handler")

    before = _hists()
    with pytest.raises(RuntimeError):
        trainer.train(_batches(2), event_handler=handler)
    after = _hists()
    assert _added(before, after, PHASES["train_enter"])[0] == 1
    assert _added(before, after, PHASES["train_exit"])[0] == 0
    with spans.span("feed") as scope:  # nothing was left open above it
        pass
    assert scope.parent is None


# -- the compile phases ------------------------------------------------------

def test_a_trace_with_three_jitted_functions_inside_counts_its_time_once():
    compile_cache.listen()
    nap = 0.05

    def slow():
        def fn(x):
            time.sleep(nap)  # runs while tracing only
            return jax.lax.mul(x, x)  # a primitive: no jitted jnp inside
        return jax.jit(fn)

    inner = [slow() for _ in range(3)]

    @jax.jit
    def outer(x):
        a, b, c = (fn(x) for fn in inner)
        return jax.lax.add(jax.lax.add(a, b), c)

    x = jnp.ones((3,), jnp.float32)
    before = _hists()
    start = time.perf_counter()
    jax.block_until_ready(outer(x))
    wall_ms = (time.perf_counter() - start) * 1e3
    after = _hists()
    traces, trace_ms = _added(before, after, TRACE)
    assert traces == 4  # the outer function's and the three inside it
    assert 3 * nap * 1e3 <= trace_ms <= wall_ms
    # counted four times over, the three naps would be there twice
    assert trace_ms < 5 * nap * 1e3
    assert _added(before, after, LOWER)[0] == 1
    assert _added(before, after, BACKEND)[0] == 1
    phases_ms = sum(_added(before, after, name)[1] for name in COMPILE)
    assert phases_ms <= wall_ms
    # a second call of the compiled function observes nothing
    before = after
    jax.block_until_ready(outer(x))
    after = _hists()
    assert [_added(before, after, name)[0] for name in COMPILE] == [0, 0, 0]


def test_a_second_step_of_a_compiled_trainer_observes_nothing():
    compile_cache.listen()  # `paddle.init` does, in a process that calls it
    trainer = _trainer()
    before = _hists()
    trainer.train(_batches(1), event_handler=lambda e: None)
    after = _hists()
    assert all(_added(before, after, name)[0] >= 1 for name in COMPILE)
    assert compile_cache.stats().keys() == {"dir", "entries", "requests",
                                            "hits"}
    before = after
    trainer.train(_batches(1), event_handler=lambda e: None)
    after = _hists()
    assert [_added(before, after, name)[0] for name in COMPILE] == [0, 0, 0]


def test_telemetry_counts_through_the_one_listener(tmp_path, monkeypatch):
    from jax._src import monitoring

    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    trainer = _trainer()  # its own small compiles come before the steplog
    before = _hists()
    with steplog.watch_compiles() as watcher:
        trainer.train(_batches(2), event_handler=lambda e: None)
    after = _hists()
    compiled = _added(before, after, BACKEND)[0]
    assert compiled >= 1 and watcher.compiles == compiled
    records = [json.loads(line) for line in
               open(os.path.join(str(tmp_path), "train.steps.jsonl"))]
    events = [r for r in records if r["type"] == "event"]
    assert sum("backend_compile" in r["event"] for r in events) == compiled
    # the log mirrors every duration event, whole durations as ever
    assert sum("jaxpr_trace" in r["event"] for r in events) == \
        _added(before, after, TRACE)[0]
    # but for JAX's reckoning of what a read saved: it keeps a program's
    # compile time in whole seconds, so a quick program that the cache
    # answered reads 0 less the time of the read
    assert all(r["secs"] >= 0 for r in events
               if "compile_time_saved" not in r["event"])

    def ours(listeners):
        return [fn for fn in listeners
                if getattr(fn, "__module__", "").startswith("paddle_tpu")]

    # one registration of each kind, all of them compile_cache's
    assert ours(monitoring.get_event_listeners()) == \
        [compile_cache._on_event]
    assert ours(monitoring.get_scalar_listeners()) == \
        [compile_cache._on_start]
    assert ours(monitoring.get_event_duration_listeners()) == \
        [compile_cache._on_duration]
    assert ours(monitoring.get_event_time_span_listeners()) == []


# -- what a phase costs ------------------------------------------------------

def test_a_phase_is_cheap_when_nobody_traces():
    # many short rounds: under a loaded machine one of them runs undisturbed
    rounds = 500
    best = float("inf")
    for _ in range(40):
        start = time.perf_counter()
        for i in range(rounds):
            with spans.phase("sync_back", args={"bytes": i}):
                pass
        best = min(best, (time.perf_counter() - start) / rounds)
    # the guard of test_a_span_is_cheap_when_nobody_traces: against a slow
    # path (an import, a lock held, a file), not a timing
    assert best < 20e-6, best
