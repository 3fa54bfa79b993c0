"""Per-op device attribution from jax-profiler traces.

One place holds the trace-layout knowledge (pid/tid -> thread-name
metadata map, "X" duration events, the "XLA Modules"/"XLA Ops" track
names) — promoted from benchmark/traceutil.py so the experiment scripts,
bench.py, and run.py can't drift apart on it — plus the report layer the
round-5 ResNet floor analysis was hand-built from: top-N ops by device
time, fusion grouping via HLO metadata, a per-op MXU-utilization
estimate, and a dispatch-gap detector that compares device-busy time
against the trace window and flags scan/while-loop dispatch-bound
regions (the diagnosis that took manual trace reading for NMT and CRF).

A capture that fails raises. A capture with no device track (the CPU
backend: its trace has host threads only, ``module_us == 0``) makes
:func:`device_busy_ms` return None — "no device time", which callers
that claim the chip (chip_smoke.py, bench.py) treat as a failure. The
report functions accept whatever subset of trace / HLO inputs exists.
"""

import collections
import glob
import gzip
import json
import re
import shutil
import tempfile

# Published peaks of one chip, keyed by JAX's ``device_kind`` (source:
# Google Cloud documentation, "TPU v5e"). A device that is not here has
# no MFU and no roofline share: a CPU steplog must not report a v5e's.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbps": 819.0},
}

# the HLO cost model's "estimated_cycles" metadata is denominated in
# ~940MHz device cycles (see exp_dump_hlo / round-5 analysis artifacts)
_COST_MODEL_HZ = 940e6


def device_peaks(device_kind=None):
    """The DEVICE_PEAKS row of ``device_kind`` (default: the device JAX
    runs on), or None for a device that is not in the table."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind)


def achieved(flops, ms, device_kind=None):
    """(TFLOP/s, MFU %) for a step of ``flops`` taking ``ms`` on
    ``device_kind`` (default: the device JAX runs on) — the ONE place a
    peak is applied (bench.py, benchmark/run.py and the steplog all
    report these). MFU is None on a device DEVICE_PEAKS does not list."""
    if not flops or not ms or ms != ms:
        return None, None
    tflops = flops / (ms / 1000.0) / 1e12
    peaks = device_peaks(device_kind)
    return tflops, (tflops / peaks["bf16_tflops"] * 100.0 if peaks
                    else None)


class DeviceTrace:
    """Parsed device-side durations from one profiler capture (all trace
    files of the capture merged — multi-host/multi-device captures
    produce several)."""

    def __init__(self, module_us, per_op_us, calls, module_events=None,
                 n_files=1, tracks=()):
        self.module_us = module_us    # total "XLA Modules" span time (us)
        self.per_op_us = per_op_us    # Counter: op name -> total us
        self.calls = calls            # Counter: op name -> #events
        # (ts_us, dur_us) of each "XLA Modules" execution, for gap analysis
        self.module_events = module_events if module_events is not None else []
        self.n_files = n_files        # trace files merged into this view
        # every track name that held a duration event: what to print
        # when the expected device tracks are missing
        self.tracks = sorted(tracks)

    def module_ms_per(self, n):
        return self.module_us / n / 1000.0 if self.module_us else None


def _load_trace_events(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        data = json.load(fh)
    return data.get("traceEvents", [])


def parse_trace_files(files):
    """Merge the device tracks of every trace file into one DeviceTrace.

    pid/tid thread-name metadata is per-file (pids repeat across hosts),
    so each file resolves its own track map before its events merge."""
    module_us = 0.0
    per_op = collections.Counter()
    calls = collections.Counter()
    module_events = []
    seen = set()
    for path in files:
        events = _load_trace_events(path)
        tracks = {}
        for ev in events:
            if ev.get("ph") == "M" and ev.get("name") == "thread_name":
                tracks[(ev["pid"], ev["tid"])] = ev["args"].get("name")
        for ev in events:
            if ev.get("ph") != "X" or "dur" not in ev:
                continue
            tname = tracks.get((ev.get("pid"), ev.get("tid"))) or ""
            seen.add(tname)
            if tname == "XLA Modules":
                module_us += ev["dur"]
                module_events.append((float(ev.get("ts", 0.0)),
                                      float(ev["dur"])))
            elif tname == "XLA Ops":
                per_op[ev["name"]] += ev["dur"]
                calls[ev["name"]] += 1
    return DeviceTrace(module_us, per_op, calls, module_events,
                       n_files=len(files), tracks=seen)


def parse_trace_dir(directory):
    """DeviceTrace from every ``*.trace.json[.gz]`` under ``directory``
    (merged), or None when the capture produced no trace files."""
    files = sorted(
        glob.glob(directory + "/**/*.trace.json.gz", recursive=True)
        + glob.glob(directory + "/**/*.trace.json", recursive=True))
    if not files:
        return None
    return parse_trace_files(files)


def capture(run_fn, sync_fn):
    """Trace ``run_fn()`` (sync with ``sync_fn()`` before/after) and
    return a DeviceTrace over ALL captured trace files, or None if the
    backend produced none."""
    import jax

    sync_fn()
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            run_fn()
            sync_fn()
        finally:
            jax.profiler.stop_trace()
        return parse_trace_dir(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def device_busy_ms(bundle, steps=40):
    """Profiler device-busy ms per step for a StepBundle-like object
    (``.step``/``.carry``/``.fetch``) — the device's own time, where a
    wall-clock slope also counts the host's dispatch. Returns None when
    the trace has no device track (the CPU backend); a capture that
    fails raises."""
    state = {"c": bundle.carry}

    def run():
        for _ in range(steps):
            state["c"] = bundle.step(state["c"])

    try:
        trace = capture(run, lambda: bundle.fetch(state["c"]))
    finally:
        # the donated carry is consumed by the first step: the stale one
        # must never survive this call (deleted-buffer crash downstream)
        bundle.carry = state["c"]
    if trace is None or not trace.module_us:
        return None
    return trace.module_us / steps / 1000.0


def profile_bundle(bundle, steps=10):
    """Trace ``steps`` chained executions of a StepBundle; returns the
    DeviceTrace (or None). The first (compile) step runs before tracing."""
    state = {"carry": bundle.step(bundle.carry)}
    bundle.fetch(state["carry"])  # compile + sync

    def run():
        for _ in range(steps):
            state["carry"] = bundle.step(state["carry"])

    trace = capture(run, lambda: bundle.fetch(state["carry"]))
    bundle.carry = state["carry"]
    return trace


# -- op classification and HLO metadata join --------------------------------

def classify(name):
    """Coarse op-class tag for a device op name."""
    n = name.lower()
    for pat, tag in (
            ("convolution", "conv"), ("conv_general", "conv"),
            ("dot", "dot"), ("select-and-scatter", "pool_bwd"),
            ("reduce-window", "pool"), ("all-reduce", "collective"),
            ("copy", "copy"), ("transpose", "transpose"),
            ("fusion", "fusion"), ("scatter", "scatter"),
            ("dynamic-update", "dus"), ("reduce", "reduce")):
        if pat in n:
            return tag
    return "other"


_DEF_RE = re.compile(r'^\s*%?([\w.\-]+) = .*')
_META_RE = re.compile(r'op_name="([^"]+)"')
_SHAPE_RE = re.compile(r'= \(?([a-z0-9]+)\[([\d,]+)\]')
_CYC_RE = re.compile(r'"estimated_cycles":"(\d+)"')


def load_hlo_defs(hlo_path):
    """Map HLO value name -> (metadata op_name, full def line) from an
    optimized-HLO text dump (exp_dump_hlo / ``--hlo auto``)."""
    defs = {}
    with open(hlo_path) as fh:
        for line in fh:
            m = _DEF_RE.match(line)
            if not m or " = " not in line:
                continue
            om = _META_RE.search(line)
            defs.setdefault(m.group(1), (om.group(1) if om else "?", line))
    return defs


def _cost_model_ms(line):
    cm = _CYC_RE.search(line)
    return int(cm.group(1)) / _COST_MODEL_HZ * 1000.0 if cm else None


def op_report(trace, steps, hlo_defs=None, top=None):
    """Top ops by device time. Returns a list of dicts sorted by total
    device time: name, class, ms_per_step, calls_per_step, pct of op
    total; with ``hlo_defs`` also the jax op_name, output shape, the HLO
    cost model's estimated ms and ``mxu_util_est`` — estimated-optimal /
    measured per-call time, an upper-bound-style utilization estimate for
    the MXU ops the cost model covers (convs/dots/fusions carrying
    estimated_cycles metadata)."""
    total = sum(trace.per_op_us.values()) or 1.0
    rows = []
    for name, dur in trace.per_op_us.most_common(top):
        row = {"name": name, "class": classify(name),
               "ms_per_step": dur / steps / 1000.0,
               "calls_per_step": trace.calls[name] / steps,
               "pct": 100.0 * dur / total}
        if hlo_defs is not None:
            op_name, line = hlo_defs.get(name, ("?", ""))
            row["op_name"] = op_name
            sm = _SHAPE_RE.search(line)
            if sm:
                row["shape"] = "%s[%s]" % sm.groups()
            est = _cost_model_ms(line)
            if est is not None and trace.calls[name]:
                row["est_ms"] = est
                per_call_ms = dur / trace.calls[name] / 1000.0
                if per_call_ms > 0:
                    row["mxu_util_est"] = min(est / per_call_ms, 1.0)
        rows.append(row)
    return rows


def class_report(trace, steps):
    """Device time grouped by op class: list of (class, ms_per_step, pct)."""
    total = sum(trace.per_op_us.values()) or 1.0
    by_class = collections.Counter()
    for name, dur in trace.per_op_us.items():
        by_class[classify(name)] += dur
    return [(tag, dur / steps / 1000.0, 100.0 * dur / total)
            for tag, dur in by_class.most_common()]


def fusion_groups(trace, steps, hlo_defs, top=45):
    """Device time grouped by the tail of the jax op_name path — the
    fusion-source grouping the round-5 analyses used (which model-level
    operation each fused kernel came from)."""
    agg = {}
    for name, dur in trace.per_op_us.most_common():
        op_name = hlo_defs.get(name, ("?", ""))[0]
        tail = "/".join(op_name.split("/")[-2:])
        agg[tail] = agg.get(tail, 0.0) + dur
    return sorted(((tail, dur / steps / 1000.0) for tail, dur in agg.items()),
                  key=lambda kv: -kv[1])[:top]


def conv_detail(trace, steps, hlo_defs, top=32):
    """Per-conv rows: measured ms vs the HLO cost model's estimate."""
    rows = []
    for name, dur in trace.per_op_us.most_common():
        op_name, line = hlo_defs.get(name, ("?", ""))
        if "conv_general_dilated" not in op_name:
            continue
        sm = _SHAPE_RE.search(line)
        est = _cost_model_ms(line)
        rows.append({
            "ms_per_step": dur / steps / 1000.0,
            "est_ms": est if est is not None else float("nan"),
            "kind": "bwd" if "transpose" in op_name else "fwd",
            "shape": ("%s[%s]" % sm.groups()) if sm else "?",
            "name": name})
    rows.sort(key=lambda r: -r["ms_per_step"])
    return rows[:top]


# -- dispatch-gap detector --------------------------------------------------

def dispatch_gap(trace, steps=1, wall_ms_per_step=None,
                 gap_threshold_pct=25.0, min_execs_per_step=4):
    """Compare device-busy time against the trace window (and optionally
    a wall slope) and flag dispatch-bound regions.

    A scan/while-loop dispatch-bound profile — the NMT decoder and CRF
    diagnosis that previously took manual trace reading — shows MANY
    short "XLA Modules" executions per step with idle gaps between them:
    the device finishes each program faster than the host can dispatch
    the next. Detection: gap fraction of the busy window above
    ``gap_threshold_pct`` AND more than ``min_execs_per_step`` device
    executions per step.

    Caveat: the window spans the merged events of all devices in the
    capture; on multi-device captures overlapping executions can push the
    apparent gap to 0 — interpret per-chip.

    Returns a dict (device_busy_ms_per_step, window_ms_per_step,
    gap_ms_per_step, gap_pct, execs_per_step, mean_exec_us,
    dispatch_bound, diagnosis) or None when the trace has no module
    events."""
    events = sorted(trace.module_events)
    if not events:
        return None
    start = events[0][0]
    end = max(ts + dur for ts, dur in events)
    window_us = max(end - start, 1e-9)
    busy_us = sum(dur for _, dur in events)
    gap_us = max(window_us - busy_us, 0.0)
    gap_pct = 100.0 * gap_us / window_us
    execs_per_step = len(events) / steps
    res = {
        "device_busy_ms_per_step": busy_us / steps / 1000.0,
        "window_ms_per_step": window_us / steps / 1000.0,
        "gap_ms_per_step": gap_us / steps / 1000.0,
        "gap_pct": gap_pct,
        "execs_per_step": execs_per_step,
        "mean_exec_us": busy_us / len(events),
    }
    if wall_ms_per_step:
        res["wall_ms_per_step"] = wall_ms_per_step
        res["wall_gap_ms_per_step"] = max(
            wall_ms_per_step - res["device_busy_ms_per_step"], 0.0)
    bound = gap_pct >= gap_threshold_pct and execs_per_step >= min_execs_per_step
    res["dispatch_bound"] = bound
    if bound:
        res["diagnosis"] = (
            "dispatch-bound: %.0f device executions/step averaging %.0fus "
            "with %.1f%% of the window idle — the host dispatch loop "
            "(scan/while-loop per-iteration launches), not device compute, "
            "sets the step time; fuse the loop body into fewer programs"
            % (execs_per_step, res["mean_exec_us"], gap_pct))
    else:
        res["diagnosis"] = (
            "device-bound: %.1f%% of the window idle over %.0f "
            "executions/step — step time tracks device compute"
            % (gap_pct, execs_per_step))
    return res


# -- formatted report -------------------------------------------------------

def report_text(trace, steps, hlo_defs=None, top=40, flops_per_step=None,
                wall_ms_per_step=None):
    """The full per-op attribution report as printable text — the format
    of benchmark/artifacts/*_analysis.md's measured sections."""
    lines = []
    total_ops = sum(trace.per_op_us.values())
    lines.append(
        "module total: %.3f ms/step | op total: %.3f ms/step  "
        "(%d steps, %d trace file%s)"
        % (trace.module_us / steps / 1000.0, total_ops / steps / 1000.0,
           steps, trace.n_files, "" if trace.n_files == 1 else "s"))
    if flops_per_step and trace.module_us:
        tflops, mfu = achieved(flops_per_step,
                               trace.module_us / steps / 1000.0)
        lines.append("achieved: %.1f TFLOP/s = %s MFU "
                     "(static step FLOPs / device-busy time)"
                     % (tflops, "no peak for this device" if mfu is None
                        else "%.1f%%" % mfu))
    gap = dispatch_gap(trace, steps, wall_ms_per_step=wall_ms_per_step)
    if gap is not None:
        lines.append("dispatch gap: busy %.3f / window %.3f ms/step "
                     "(%.1f%% idle, %.0f execs/step) -> %s"
                     % (gap["device_busy_ms_per_step"],
                        gap["window_ms_per_step"], gap["gap_pct"],
                        gap["execs_per_step"], gap["diagnosis"]))
    lines.append("")
    lines.append("by class (ms/step):")
    for tag, ms, pct in class_report(trace, steps):
        lines.append("  %-12s %8.3f  (%4.1f%%)" % (tag, ms, pct))
    lines.append("")
    lines.append("top ops (ms/step, calls/step):")
    for row in op_report(trace, steps, hlo_defs=hlo_defs, top=top):
        extra = ""
        if "mxu_util_est" in row:
            extra = "  mxu~%.0f%%" % (row["mxu_util_est"] * 100.0)
        lines.append("  %8.3f  x%-4d %s%s"
                     % (row["ms_per_step"], int(row["calls_per_step"]),
                        row["name"][:110], extra))
    if hlo_defs:
        lines.append("")
        lines.append("top ops with HLO attribution (ms/step):")
        for tail, ms in fusion_groups(trace, steps, hlo_defs):
            lines.append("  %8.3f  %s" % (ms, tail[:120]))
        rows = conv_detail(trace, steps, hlo_defs)
        if rows:
            lines.append("")
            lines.append("conv detail (measured ms | cost-model ms | kind "
                         "| out shape):")
            for r in rows:
                lines.append("  %7.3f | %7.3f | %s | %-28s %s"
                             % (r["ms_per_step"], r["est_ms"], r["kind"],
                                r["shape"], r["name"][:40]))
    return "\n".join(lines)
