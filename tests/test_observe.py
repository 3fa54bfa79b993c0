"""paddle_tpu.observe tests — spans, device attribution, step telemetry.

Covers the observability subsystem contract (docs/observability.md):
span nesting + Chrome-trace export that Perfetto can load, the multi-file
trace merge (regression: traceutil.capture used to read only files[0] of
a multi-host capture), the dispatch-gap detector, the steplog JSONL
schema (golden: tests/golden/steplog_schema.json), and the end-to-end
CPU telemetry smoke: a 3-step dense train with PADDLE_TPU_TELEMETRY set
must emit a valid JSONL step log and a parseable Chrome trace.
"""

import glob
import gzip
import json
import os

import numpy as np
import pytest

from paddle_tpu.observe import attribution, spans, steplog
from paddle_tpu.utils.stat import StatSet

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "steplog_schema.json")


# -- spans -------------------------------------------------------------------

def test_span_nesting_durations_and_stats():
    stats = StatSet("test")
    tracer = spans.SpanTracer("t", stats=stats)
    with tracer.span("outer") as outer:
        with tracer.span("inner") as inner:
            pass
    assert inner.dur is not None and outer.dur is not None
    assert outer.dur >= inner.dur  # containment holds by construction
    names = [ev[0] for ev in tracer.events()]
    assert names == ["inner", "outer"]  # closed in nesting order
    agg = stats.as_dict()
    assert agg["outer"]["count"] == 1 and agg["inner"]["count"] == 1


def test_span_parent_and_self_time_on_two_threads_at_once():
    """Each thread keeps its own stack of open spans: a span's parent is
    the one open on ITS thread, its self time its duration less what its
    children covered, and the Chrome export's args carry the parent."""
    import threading
    import time

    tracer = spans.SpanTracer("t", stats=None)
    both_open = threading.Barrier(2)
    seen = {}

    def work(tag, nap):
        with tracer.span(tag + "_outer") as outer:
            both_open.wait(timeout=10)  # the other thread's outer is open
            with tracer.span(tag + "_inner") as first:
                time.sleep(nap)
            with tracer.span(tag + "_inner") as second:
                with tracer.span(tag + "_leaf") as leaf:
                    time.sleep(nap)
        seen[tag] = (outer, first, second, leaf)

    threads = [threading.Thread(target=work, args=(tag, nap),
                                name="span-test-" + tag)
               for tag, nap in (("a", 0.01), ("b", 0.02))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    for tag in ("a", "b"):
        outer, first, second, leaf = seen[tag]
        assert outer.parent is None
        assert first.parent == second.parent == tag + "_outer"
        assert leaf.parent == tag + "_inner"
        assert outer.child_dur == pytest.approx(first.dur + second.dur)
        assert outer.self_dur == pytest.approx(
            outer.dur - first.dur - second.dur)
        assert first.child_dur == 0.0 and first.self_dur == first.dur
        assert second.self_dur == pytest.approx(second.dur - leaf.dur)
        assert 0.0 <= second.self_dur < leaf.dur
    parents = {e["name"]: e.get("args", {}).get("parent")
               for e in tracer.to_chrome_trace()["traceEvents"]
               if e["ph"] == "X"}
    assert parents == {"a_outer": None, "a_inner": "a_outer",
                       "a_leaf": "a_inner", "b_outer": None,
                       "b_inner": "b_outer", "b_leaf": "b_inner"}


def test_span_left_open_across_a_yield_closes_out_of_order():
    """A generator that yields inside a span lets the caller open and
    close spans of its own meanwhile: each still leaves the stack."""
    tracer = spans.SpanTracer("t", stats=None)

    def gen():
        with tracer.span("held"):
            yield

    with tracer.span("outer"):
        g = gen()
        next(g)  # "held" is open, above "outer"
    g.close()  # closes "held" after "outer" has gone
    with tracer.span("after") as after:
        pass
    assert after.parent is None
    assert [ev[0] for ev in tracer.events()] == ["outer", "held", "after"]


def test_span_disabled_records_nothing_but_still_times():
    stats = StatSet("test")
    tracer = spans.SpanTracer("t", stats=stats)
    tracer.enabled = False
    with tracer.span("x") as scope:
        pass
    # callers consume scope.dur arithmetically (trainer feed_ms, harness
    # slopes) — disabling the tracer must not null it out
    assert scope.dur is not None and scope.dur >= 0
    assert tracer.events() == []
    assert stats.as_dict() == {}


def test_span_sync_blocks_on_device_value():
    import jax.numpy as jnp

    tracer = spans.SpanTracer("t", stats=None)
    y = None
    with tracer.span("device", sync=None) as scope:
        y = jnp.ones((8, 8)) * 2.0
    with tracer.span("device_sync", sync=y):
        pass
    assert scope.dur is not None
    assert [ev[0] for ev in tracer.events()] == ["device", "device_sync"]


def test_chrome_trace_export_parses(tmp_path):
    tracer = spans.SpanTracer("unit", stats=None)
    with tracer.span("step", args={"batch": 3}):
        with tracer.span("feed"):
            pass
    path = tracer.export(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    assert "traceEvents" in data
    evs = data["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert meta and meta[0]["args"]["name"] == "unit"
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"step", "feed"}
    # "X" complete events need ts + dur in µs; args survive the export
    assert xs["step"]["dur"] >= xs["feed"]["dur"] >= 0
    assert xs["step"]["args"] == {"batch": 3}
    # thread metadata names every used row
    tids = {e["tid"] for e in evs if e["ph"] == "X"}
    named = {e["tid"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tids <= named


def test_chrome_trace_gz_export(tmp_path):
    tracer = spans.SpanTracer("unit", stats=None)
    with tracer.span("marker"):
        pass
    path = tracer.export(str(tmp_path / "trace.json.gz"))
    with gzip.open(path, "rt") as fh:
        data = json.load(fh)
    assert any(e.get("name") == "marker" for e in data["traceEvents"])


def test_span_cap_drops_excess_but_keeps_stats():
    stats = StatSet("test")
    tracer = spans.SpanTracer("t", stats=stats)
    tracer.MAX_EVENTS = 2  # instance attr overrides the class cap
    for i in range(5):
        with tracer.span("s"):
            pass
    assert len(tracer.events()) == 2
    assert tracer.to_chrome_trace()["metadata"]["dropped_spans"] == 3
    assert stats.as_dict()["s"]["count"] == 5  # stats see every span
    tracer.reset()
    assert tracer.events() == []


def test_global_tracer_span_feeds_global_stats(monkeypatch):
    from paddle_tpu.utils.stat import global_stats

    tracer = spans.get_tracer()
    monkeypatch.setattr(tracer, "record_events", True)
    tracer.reset()
    before = global_stats.as_dict().get("observe_unit", {}).get("count", 0)
    with spans.span("observe_unit"):
        pass
    assert global_stats.as_dict()["observe_unit"]["count"] == before + 1
    assert any(ev[0] == "observe_unit" for ev in tracer.events())
    tracer.reset()


def test_global_tracer_auto_recording_gated_on_telemetry(monkeypatch):
    """With no possible trace consumer (record_events=None = auto, no
    PADDLE_TPU_TELEMETRY) the global tracer must not retain event tuples
    — long un-instrumented runs would otherwise grow the buffer to
    MAX_EVENTS for nothing. Stats still see every span."""
    from paddle_tpu.utils.stat import global_stats

    tracer = spans.get_tracer()
    monkeypatch.setattr(tracer, "record_events", None)
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY", raising=False)
    tracer.reset()
    with spans.span("auto_gate_unit"):
        pass
    assert tracer.events() == []
    assert global_stats.as_dict()["auto_gate_unit"]["count"] >= 1
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", "/tmp/anywhere")
    with spans.span("auto_gate_unit"):
        pass
    assert any(ev[0] == "auto_gate_unit" for ev in tracer.events())
    tracer.reset()


# -- attribution: trace parsing / multi-file merge ---------------------------

def _write_trace(path, module_durs, op_durs, pid=1, ts0=0.0):
    """A minimal device trace: one "XLA Modules" and one "XLA Ops" track."""
    evs = [
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": 1,
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": 3,
         "args": {"name": "host thread"}},
        # an event on a non-device track must be ignored
        {"ph": "X", "name": "python_noise", "pid": pid, "tid": 3,
         "ts": ts0, "dur": 999.0},
    ]
    ts = ts0
    for dur in module_durs:
        evs.append({"ph": "X", "name": "jit_step", "pid": pid, "tid": 1,
                    "ts": ts, "dur": dur})
        ts += dur * 2  # leave an idle gap equal to the busy time
    ts = ts0
    for name, dur in op_durs:
        evs.append({"ph": "X", "name": name, "pid": pid, "tid": 2,
                    "ts": ts, "dur": dur})
        ts += dur
    payload = json.dumps({"traceEvents": evs})
    if path.endswith(".gz"):
        with gzip.open(path, "wt") as fh:
            fh.write(payload)
    else:
        with open(path, "w") as fh:
            fh.write(payload)


def test_parse_trace_files_merges_all_files(tmp_path):
    """Regression: the old traceutil.capture read only files[0] of the
    captured set — a multi-host/multi-device capture produces several
    trace files and ALL of them must contribute."""
    f1 = str(tmp_path / "host0.trace.json.gz")
    f2 = str(tmp_path / "host1.trace.json")
    # same pid on both hosts, but the tid→track mapping differs per file:
    # host1 swaps the track ids, so a global (pid, tid) map would
    # misattribute its events — the per-file resolution must hold
    _write_trace(f1, module_durs=[100.0, 50.0],
                 op_durs=[("fusion.1", 90.0), ("copy.2", 60.0)], pid=7)
    evs2 = [
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 2,
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "name": "jit_step", "pid": 7, "tid": 2,
         "ts": 0.0, "dur": 25.0},
        {"ph": "X", "name": "fusion.1", "pid": 7, "tid": 1,
         "ts": 0.0, "dur": 10.0},
    ]
    with open(f2, "w") as fh:
        json.dump({"traceEvents": evs2}, fh)

    trace = attribution.parse_trace_files([f1, f2])
    assert trace.n_files == 2
    assert trace.module_us == pytest.approx(175.0)  # 150 + 25, not 150
    assert trace.per_op_us["fusion.1"] == pytest.approx(100.0)
    assert trace.per_op_us["copy.2"] == pytest.approx(60.0)
    assert trace.calls["fusion.1"] == 2
    assert len(trace.module_events) == 3
    # single-file parse must equal the old files[0]-only view
    assert attribution.parse_trace_files([f1]).module_us == pytest.approx(150.0)


def test_parse_trace_dir_globs_gz_and_plain(tmp_path):
    sub = tmp_path / "plugins" / "profile"
    sub.mkdir(parents=True)
    _write_trace(str(sub / "a.trace.json.gz"), [10.0], [("op", 5.0)])
    _write_trace(str(sub / "b.trace.json"), [20.0], [("op", 7.0)])
    trace = attribution.parse_trace_dir(str(tmp_path))
    assert trace.n_files == 2
    assert trace.module_us == pytest.approx(30.0)
    assert trace.per_op_us["op"] == pytest.approx(12.0)
    assert attribution.parse_trace_dir(str(tmp_path / "empty")) is None


def test_traceutil_is_a_compat_shim():
    from benchmark import traceutil

    assert traceutil.capture is attribution.capture
    assert traceutil.DeviceTrace is attribution.DeviceTrace
    assert traceutil.parse_trace_files is attribution.parse_trace_files


def test_capture_degrades_on_cpu():
    """On the CPU backend capture either returns None or a trace with no
    'XLA Modules' device track — device_busy_ms must turn both into None
    (the documented no-op degradation)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((4,))
    f(x).block_until_ready()
    trace = attribution.capture(lambda: f(x),
                                lambda: f(x).block_until_ready())
    assert trace is None or trace.module_us == 0

    class Bundle:
        carry = x

        def step(self, c):
            return f(c)

        def fetch(self, c):
            return float(c[0])

    assert attribution.device_busy_ms(Bundle(), steps=3) is None


# -- attribution: reports / HLO join / dispatch gap --------------------------

_HLO = """\
HloModule jit_step

ENTRY %main {
  %fusion.1 = f32[64,56,56]{2,1,0} fusion(%p0), kind=kOutput, metadata={op_name="jit(step)/resnet/stage1/conv_general_dilated" source_file="x.py"}, backend_config={"cost":{"estimated_cycles":"94000"}}
  %convolution.2 = bf16[64,28,28]{2,1,0} convolution(%p1, %p2), metadata={op_name="jit(step)/transpose(jvp(resnet))/stage2/conv_general_dilated"}, backend_config={"cost":{"estimated_cycles":"47000"}}
  %copy.3 = f32[64,56,56]{2,1,0} copy(%fusion.1), metadata={op_name="jit(step)/resnet/stage1/relu"}
}
"""


def _synthetic_trace():
    import collections

    per_op = collections.Counter(
        {"fusion.1": 900.0, "convolution.2": 500.0, "copy.3": 100.0})
    calls = collections.Counter(
        {"fusion.1": 10, "convolution.2": 10, "copy.3": 10})
    module_events = [(i * 200.0, 150.0) for i in range(10)]
    return attribution.DeviceTrace(1500.0, per_op, calls, module_events)


def test_load_hlo_defs_and_op_report(tmp_path):
    hlo = tmp_path / "step.hlo.txt"
    hlo.write_text(_HLO)
    defs = attribution.load_hlo_defs(str(hlo))
    assert defs["fusion.1"][0] == "jit(step)/resnet/stage1/conv_general_dilated"
    assert defs["copy.3"][0] == "jit(step)/resnet/stage1/relu"

    trace = _synthetic_trace()
    rows = attribution.op_report(trace, steps=10, hlo_defs=defs)
    assert [r["name"] for r in rows] == ["fusion.1", "convolution.2", "copy.3"]
    top = rows[0]
    assert top["class"] == "fusion"
    assert top["ms_per_step"] == pytest.approx(0.09)
    assert top["calls_per_step"] == pytest.approx(1.0)
    assert top["shape"] == "f32[64,56,56]"
    # estimated_cycles @940MHz = 0.1 ms optimal vs 0.09 ms measured →
    # the utilization estimate caps at 1.0
    assert top["mxu_util_est"] == pytest.approx(1.0)
    assert rows[1]["mxu_util_est"] == pytest.approx(1.0)
    assert "mxu_util_est" not in rows[2]  # no cost-model metadata


def test_class_fusion_and_conv_reports(tmp_path):
    hlo = tmp_path / "step.hlo.txt"
    hlo.write_text(_HLO)
    defs = attribution.load_hlo_defs(str(hlo))
    trace = _synthetic_trace()

    classes = dict((tag, ms) for tag, ms, _ in
                   attribution.class_report(trace, steps=10))
    assert classes["fusion"] == pytest.approx(0.09)
    assert classes["conv"] == pytest.approx(0.05)
    assert classes["copy"] == pytest.approx(0.01)

    groups = dict(attribution.fusion_groups(trace, 10, defs))
    assert groups["stage1/conv_general_dilated"] == pytest.approx(0.09)
    assert groups["stage1/relu"] == pytest.approx(0.01)

    convs = attribution.conv_detail(trace, 10, defs)
    assert [(r["name"], r["kind"]) for r in convs] == [
        ("fusion.1", "fwd"), ("convolution.2", "bwd")]


def test_dispatch_gap_flags_scan_dispatch_bound():
    """Many short executions with idle gaps == the NMT/CRF scan profile."""
    events = [(i * 30.0, 10.0) for i in range(30)]  # 66% idle, 30 execs
    trace = attribution.DeviceTrace(300.0, {}, {}, events)
    gap = attribution.dispatch_gap(trace, steps=2)
    assert gap["dispatch_bound"] is True
    assert "dispatch-bound" in gap["diagnosis"]
    assert gap["execs_per_step"] == pytest.approx(15.0)
    assert gap["device_busy_ms_per_step"] == pytest.approx(0.15)
    assert gap["gap_pct"] > 60.0


def test_dispatch_gap_device_bound_and_wall():
    events = [(0.0, 990.0), (991.0, 1000.0)]  # one long program, no gaps
    trace = attribution.DeviceTrace(1990.0, {}, {}, events)
    gap = attribution.dispatch_gap(trace, steps=2, wall_ms_per_step=1.5)
    assert gap["dispatch_bound"] is False
    assert "device-bound" in gap["diagnosis"]
    assert gap["wall_gap_ms_per_step"] == pytest.approx(1.5 - 0.995)
    assert attribution.dispatch_gap(
        attribution.DeviceTrace(0, {}, {}, []), steps=1) is None


def test_achieved_is_the_one_peak_application():
    v5e = "TPU v5 lite"
    peak = attribution.DEVICE_PEAKS[v5e]["bf16_tflops"]
    tflops, mfu = attribution.achieved(peak * 1e12, 1000.0, device_kind=v5e)
    assert tflops == pytest.approx(peak)
    assert mfu == pytest.approx(100.0)
    assert attribution.achieved(None, 5.0) == (None, None)
    assert attribution.achieved(1e12, 0.0) == (None, None)
    assert attribution.achieved(1e12, float("nan")) == (None, None)
    # a device that is not in the table has a rate but no MFU: neither an
    # unknown kind nor the CPU these tests run on borrows the v5e's peak
    assert attribution.achieved(1e12, 1000.0, device_kind="TPU v99") \
        == (pytest.approx(1.0), None)
    assert attribution.achieved(1e12, 1000.0) == (pytest.approx(1.0), None)
    # harness re-exports the same objects — no second constant anywhere
    from benchmark import harness

    assert harness.achieved is attribution.achieved


def test_report_text_sections(tmp_path):
    hlo = tmp_path / "step.hlo.txt"
    hlo.write_text(_HLO)
    defs = attribution.load_hlo_defs(str(hlo))
    text = attribution.report_text(_synthetic_trace(), 10, hlo_defs=defs,
                                   flops_per_step=1e9,
                                   wall_ms_per_step=0.3)
    for needle in ("module total", "MFU", "dispatch gap", "by class",
                   "top ops", "HLO attribution", "conv detail"):
        assert needle in text, needle


# -- steplog -----------------------------------------------------------------

def test_from_env_disabled_returns_none(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY", raising=False)
    from paddle_tpu.utils import flags

    flags.set_flag("telemetry", "")
    assert steplog.from_env() is None
    assert steplog.telemetry_dir() is None


def test_telemetry_dir_env_beats_flag(tmp_path, monkeypatch):
    from paddle_tpu.utils import flags

    flags.set_flag("telemetry", "/flag/dir")
    assert steplog.telemetry_dir() == "/flag/dir"
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    assert steplog.telemetry_dir() == str(tmp_path)


def test_stats_enabled(monkeypatch):
    from paddle_tpu.utils import flags

    monkeypatch.delenv("PADDLE_TPU_STATS", raising=False)
    flags.set_flag("stats", False)
    assert steplog.stats_enabled() is False
    flags.set_flag("stats", True)
    assert steplog.stats_enabled() is True
    monkeypatch.setenv("PADDLE_TPU_STATS", "0")
    assert steplog.stats_enabled() is False
    monkeypatch.setenv("PADDLE_TPU_STATS", "1")
    assert steplog.stats_enabled() is True


def _full_featured_log(tmp_path):
    with steplog.StepLog(str(tmp_path), run_name="unit",
                         compile_events=False) as slog:
        slog.register_flops(2e9)
        slog.log_step(step=1, pass_id=0, batch_id=0, wall_ms=5.0,
                      feed_ms=0.4, cost=1.25, examples=64, device_ms=4.0,
                      metrics={"err": 0.5, "skipme": "str"})
        slog.log_step(step=2, wall_ms=3.0)
        slog.write({"type": "event", "event": "compile", "secs": 0.01})
        slog.write({"type": "bench_row", "metric": "x", "value": 1.0})
        slog.log_feed(step=2, stall_ms=0.8, convert_ms=1.1, examples=64,
                      depth=2, bucket=32, fill_tokens=100, pad_tokens=28)
        slog.log_checkpoint(step=2, duration_ms=3.25, nbytes=4096,
                            overlapped=True, step_thread_ms=0.12,
                            pass_id=0, path="pass-00000-step-00000002")
        slog.log_serve_request(rows=1, queue_ms=0.5, latency_ms=2.5,
                               req_id=1)
        slog.log_serve_batch(rows=3, bucket=4, infer_ms=1.2, batch_id=1,
                             pad_rows=1, requests=2, queue_ms_max=0.7,
                             flush="deadline")
        slog.log_slo_status(state="burning", prev_state="ok",
                            objective_p99_ms=50.0, availability=99.0,
                            current_p99_ms=61.2, fast_burn=1.4,
                            slow_burn=0.7, budget_remaining=0.3,
                            breaching_phase="queue_ms", worker="1",
                            model="mnist_mlp")
        slog.log_anomaly(step=2, kind="cost_spike", cost=9.5,
                         threshold=3.0, mode="warn", worker="trainer-0")
        slog.log_crash_report(reason="anomaly:cost_spike",
                              steps=[{"step": 2, "wall_ms": 3.0}],
                              captured=1, capacity=64, mode="warn",
                              worker="trainer-0")
        slog.log_elastic_event("worker_lost", worker="trainer-0",
                               members=["trainer-0"], lost=["trainer-1"],
                               detail="lease expired")
        slog.log_elastic_event("rewind", worker="trainer-0",
                               members=["trainer-0"],
                               checkpoint="pass-00000-step-00000002")
        slog.log_elastic_event("checkpoint_commit", worker="trainer-0",
                               step=2,
                               checkpoint="pass-00000-step-00000002")
        slog.log_serve_host_event("join", host="hostA",
                                  hosts=["hostA"], detail="lease 2.0s")
        slog.log_serve_host_event("session_rehome", host="hostB",
                                  session="u1", target="hostA")
        slog.log_pass(0, metrics={"err": 0.25})
    return steplog.read_jsonl(os.path.join(str(tmp_path),
                                           "unit.steps.jsonl"))


def test_steplog_schema_matches_golden(tmp_path):
    """Golden-file check: every emitted field must be declared in
    tests/golden/steplog_schema.json — the schema can gain fields only by
    updating the golden (and docs/observability.md) in the same change."""
    golden = json.load(open(GOLDEN))
    assert golden["schema_version"] == steplog.SCHEMA_VERSION
    records = _full_featured_log(tmp_path)
    assert records[0]["type"] == "meta" and records[-1]["type"] == "end"
    for rec in records:
        spec = golden["record_types"][rec["type"]]
        keys = set(rec)
        missing = set(spec["required"]) - keys
        assert not missing, (rec["type"], missing)
        if rec["type"] != "bench_row":  # mirrored rows are free-form
            unknown = keys - set(spec["required"]) - set(spec["optional"])
            assert not unknown, (rec["type"], unknown)


def test_steplog_derived_fields(tmp_path):
    records = _full_featured_log(tmp_path)
    steps = [r for r in records if r["type"] == "step"]
    full, bare = steps
    assert full["examples_per_sec"] == pytest.approx(64 / 5.0 * 1000.0)
    # the rate leads with device_ms when present: 2 GFLOP / 4 ms = 0.5
    # TFLOP/s; the CPU these tests run on is not in DEVICE_PEAKS, so the
    # record carries no MFU (it used to report a v5e's)
    assert full["tflops"] == pytest.approx(0.5)
    assert "mfu_pct" not in full
    assert full["metrics"] == {"err": 0.5}  # non-numeric values dropped
    assert bare["tflops"] == pytest.approx(2e9 / 3e-3 / 1e12, abs=0.005)
    assert records[-1]["steps"] == 2
    # write-after-close is swallowed, not an error
    pass


def test_steplog_never_clobbers_earlier_run(tmp_path):
    """A second run of the same name in the same telemetry dir gets a -N
    suffix (train -> train-2) instead of truncating the first run's log;
    the paired trace path follows the suffix."""
    with steplog.StepLog(str(tmp_path), run_name="train",
                         compile_events=False) as first:
        first.log_step(step=1, wall_ms=1.0)
    second = steplog.StepLog(str(tmp_path), run_name="train",
                             compile_events=False)
    assert os.path.basename(second.path) == "train-2.steps.jsonl"
    assert os.path.basename(second.trace_path) == "train-2.trace.json"
    second.close()
    records = steplog.read_jsonl(first.path)  # first run intact
    assert [r["type"] for r in records] == ["meta", "step", "end"]
    assert len(steplog.summarize_dir(str(tmp_path))["runs"]) == 2


def test_summarize_dir_and_cli_observe(tmp_path, capsys):
    _full_featured_log(tmp_path)
    spans.SpanTracer("unit", stats=None).export(
        str(tmp_path / "trace.json"))
    spans.SpanTracer("unit", stats=None).export(
        str(tmp_path / "trace2.json.gz"))  # gz exports must be listed too
    summary = steplog.summarize_dir(str(tmp_path))
    assert len(summary["runs"]) == 1
    run = summary["runs"][0]
    assert run["run"] == "unit" and run["steps"] == 2
    assert run["wall_ms_steady_mean"] == pytest.approx(3.0)
    assert run["compile_events"] == 1
    assert summary["trace_files"] == ["trace.json", "trace2.json.gz"]

    from paddle_tpu import cli

    assert cli.main(["observe", str(tmp_path)]) in (0, None)
    out = capsys.readouterr().out
    assert "unit" in out and "steady p50" in out
    assert cli.main(["observe", str(tmp_path), "--json"]) in (0, None)
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["runs"][0]["steps"] == 2


# -- end-to-end: trainer telemetry smoke (tier-1-safe, CPU) ------------------

def _dense_toy(n_batches=3, batch=8, dim=6, classes=3):
    import paddle_tpu as paddle
    from paddle_tpu import activation as A
    from paddle_tpu import data_type as dt
    from paddle_tpu import evaluator
    from paddle_tpu import layer as L
    from paddle_tpu import minibatch
    from paddle_tpu import optimizer as opt
    from paddle_tpu.parameters import Parameters

    x = L.data(name="x", type=dt.dense_vector(dim))
    lab = L.data(name="y", type=dt.integer_value(classes))
    out = L.fc(input=L.fc(input=x, size=12, act=A.Tanh()), size=classes)
    cost = L.classification_cost(input=out, label=lab)
    err = evaluator.classification_error(input=out, label=lab)
    params = Parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost, params, opt.Momentum(momentum=0.9, learning_rate=0.1),
        extra_layers=[err])

    def reader():
        rng = np.random.RandomState(7)
        W = rng.randn(dim, classes)
        for _ in range(n_batches * batch):
            xv = rng.randn(dim).astype(np.float32)
            yield xv, int(np.argmax(xv @ W))

    return trainer, minibatch.batch(reader, batch), err


def test_trainer_telemetry_smoke(tmp_path, monkeypatch):
    """The ISSUE acceptance check: a 3-step dense CPU train with
    PADDLE_TPU_TELEMETRY set produces a schema-valid JSONL step log and a
    Chrome-trace export that parses (loads in Perfetto)."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", str(tmp_path))
    trainer, reader, err = _dense_toy(n_batches=3)
    trainer.train(reader, num_passes=1)

    records = steplog.read_jsonl(str(tmp_path / "train.steps.jsonl"))
    golden = json.load(open(GOLDEN))
    for rec in records:  # every record schema-valid
        spec = golden["record_types"][rec["type"]]
        assert set(spec["required"]) <= set(rec)
    assert records[0]["type"] == "meta"
    assert records[0]["schema"] == steplog.SCHEMA_VERSION
    assert records[0]["phase"] == "train"
    steps = [r for r in records if r["type"] == "step"]
    assert len(steps) == 3
    assert [s["step"] for s in steps] == [1, 2, 3]
    for s in steps:
        assert s["pass"] == 0 and s["wall_ms"] > 0 and s["examples"] == 8
        assert "cost" in s and "feed_ms" in s
        assert err.name in s["metrics"]
    passes = [r for r in records if r["type"] == "pass"]
    assert len(passes) == 1 and err.name in passes[0]["metrics"]
    assert records[-1] == {"type": "end", "steps": 3}

    trace = json.load(open(tmp_path / "train.trace.json"))
    names = {e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert {"feed", "train_step", "eval_readback"} <= names
    for ev in trace["traceEvents"]:
        if ev.get("ph") == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0


def test_trainer_without_telemetry_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY", raising=False)
    monkeypatch.delenv("PADDLE_TPU_STATS", raising=False)
    trainer, reader, _ = _dense_toy(n_batches=2)
    trainer.train(reader, num_passes=1)
    assert glob.glob(str(tmp_path / "*.jsonl")) == []


# -- benchmark.traceutil compat shim ----------------------------------------

def test_traceutil_shim_deprecation_and_equivalence():
    """The shim must (a) emit ONE DeprecationWarning at import pointing
    at paddle_tpu.observe.attribution and (b) stay import-equivalent —
    every re-exported symbol IS the attribution object, so old callers
    and new callers share state."""
    import importlib
    import sys
    import warnings

    sys.modules.pop("benchmark.traceutil", None)
    with pytest.warns(DeprecationWarning,
                      match="paddle_tpu.observe.attribution"):
        shim = importlib.import_module("benchmark.traceutil")
    for name in ("DeviceTrace", "capture", "device_busy_ms",
                 "parse_trace_dir", "parse_trace_files"):
        assert getattr(shim, name) is getattr(attribution, name), name
    # one-time: a second import of the cached module must not warn again
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        importlib.import_module("benchmark.traceutil")
