"""From a profiler trace to numbers: device busy time as a union of
intervals per device, idle gaps, kernel and collective time, the top
operations. Copied in idea from `paddle_tpu/observe/attribution.py` and
corrected: that one sums "XLA Modules" durations (so overlapping events
count twice) and merges every device into one total.

A trace is a plain dict, `{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, dur_ns], ...]}]}]}`, read from the profiler's
`.xplane.pb` (`load`) or from a `.json` file of that shape (the recorded
trace the tests use). Times are nanoseconds on the profiler's one clock.
"""

import glob
import json
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
HOST_MARK = "chipbench."
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def short_name(text):
    """An operation's own name out of the event's text. The TPU writes an
    "XLA Ops" event as the instruction's whole HLO line, `%fusion.55 =
    (f32[256]...) fusion(...), kind=...`: keep `fusion.55`, and say where
    the instruction is a Pallas kernel (the text carries the custom call's
    target, not the kernel's name)."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return head + " [pallas]" if PALLAS_TARGET in text else head


def find_xplane(log_dir):
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    return paths[-1]


def load(path):
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace):
    """{device plane name: plane} of the chips' own planes (a chip's
    further planes, such as its SparseCores, have longer names)."""
    out = {}
    for plane in trace["planes"]:
        name = plane["name"]
        if name.startswith(DEVICE_PREFIX) \
                and name[len(DEVICE_PREFIX):].isdigit():
            out[name] = plane
    return out


def line_events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def op_events(plane):
    """The events that say an operation ran: the "XLA Ops" line, or the
    "XLA Modules" line where a backend writes no per-operation line."""
    return line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def total(merged):
    return sum(end - start for start, end in merged)


def overlap(a, b):
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _spans(events):
    return [(s, s + d) for _, s, d in events if d > 0]


def is_collective(text):
    return short_name(text).startswith(COLLECTIVES)


def traced_window(trace):
    """(start, end) from the first to the last device event of any chip."""
    starts, ends = [], []
    for plane in device_planes(trace).values():
        for _, s, d in op_events(plane):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no device event")
    return min(starts), max(ends)


def reduce(trace, kernel_marks=()):
    """Every number the per-layer metrics read, in seconds:

    `window_s`; per device `busy_s` (union of operation intervals);
    `busy_s` (mean over devices) and `busiest` (that device's name);
    `kernel_s[mark]` and `kernel_calls[mark]` on the busiest device for
    each substring in `kernel_marks`, looked for in the event's whole text; `collective_s` and
    `collective_exposed_s` on the busiest device (collective intervals,
    and the part of them in which no other operation ran there);
    `device_ops` (top 10 by summed time on the busiest device) and
    `idle_gaps` (its 10 longest gaps, named by the benchmark's host
    annotation that covers most of each)."""
    lo, hi = traced_window(trace)
    per_device = {}
    for name, plane in device_planes(trace).items():
        per_device[name] = total(union(_spans(op_events(plane))))
    busiest = max(per_device, key=per_device.get)
    events = op_events(device_planes(trace)[busiest])

    kernel_s = {m: 0.0 for m in kernel_marks}
    kernel_calls = {m: 0 for m in kernel_marks}
    by_name = {}
    coll, rest = [], []
    for text, start, dur in events:
        name = short_name(text)
        by_name[name] = by_name.get(name, 0.0) + dur
        for mark in kernel_marks:
            if mark in text:
                kernel_s[mark] += dur * 1e-9
                kernel_calls[mark] += 1
        (coll if is_collective(text) else rest).append((start, start + dur))
    coll_u, rest_u = union(coll), union(rest)

    busy_u = union(_spans(events))
    gaps = [(busy_u[i + 1][0] - busy_u[i][1], busy_u[i][1], busy_u[i + 1][0])
            for i in range(len(busy_u) - 1)]
    gaps.sort(reverse=True)
    marks = host_marks(trace)
    idle = [[_covering(marks, s, e), length * 1e-9]
            for length, s, e in gaps[:10]]
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "per_device_busy_s": {k: v * 1e-9 for k, v in per_device.items()},
        "busy_s": sum(per_device.values()) / len(per_device) * 1e-9,
        "busiest": busiest,
        "busiest_busy_s": per_device[busiest] * 1e-9,
        "kernel_s": kernel_s,
        "kernel_calls": kernel_calls,
        "collective_s": total(coll_u) * 1e-9,
        "collective_exposed_s": (total(coll_u) - overlap(coll_u, rest_u))
        * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in top],
        "idle_gaps": idle,
    }


def host_marks(trace):
    """[(name, start, end)] of the benchmark's own host annotations."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(HOST_MARK):
                    out.append((name, start, start + dur))
    return out


def _covering(marks, start, end):
    """The host annotation that covers most of [start, end) and at least
    half of it, or what is left when none does: the inside of `SGD.train`,
    one span until the program writes annotations of its own."""
    best, best_len = "SGD.train", 0.5 * (end - start)
    for name, s, e in marks:
        length = min(e, end) - max(s, start)
        if length > best_len:
            best, best_len = name, length
    return best
