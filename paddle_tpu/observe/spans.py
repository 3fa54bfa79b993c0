"""Nested named spans: one ``span()`` call, every sink.

Host-side wall-time spans (reference: REGISTER_TIMER scopes,
paddle/utils/Stat.h:230-233), kept deliberately cheap: a span is one
``perf_counter`` pair, a push and a pop of its thread's stack and an
appended tuple, so the trainer can wrap every batch phase without
measurable overhead. Each closed span feeds the
:data:`paddle_tpu.utils.stat.global_stats` StatSet under the span name,
so ``PADDLE_TPU_STATS=1`` per-pass dumps and the exported trace can
never disagree about what was measured.

Every span also holds a ``jax.profiler.TraceAnnotation("paddle_tpu." +
name)`` open for exactly its lifetime, so whenever anyone traces
(``paddle_tpu.utils.stat.profiler_trace(dir)``, the chip benchmark's
traced run) the span lies on the profiler's clock, on its own thread's
line of the ``/host:CPU`` plane, beside the device's "XLA Ops": an idle
gap of the device can be put down to the span that covers it. With no
profiler session the annotation costs a third of a microsecond. Span
names are a closed vocabulary (docs/observability.md); a number that
varies goes into ``args``, which the profiler stores as the event's
stats, never into the name.

A span knows its parent: each thread keeps a stack of its open spans, a
closed span adds its duration to its parent's ``child_dur``, and
``scope.self_dur`` is the span's own time, its duration less what its
children covered.

Export is the Chrome trace-event JSON format ("X" complete events, µs
timestamps) — the file loads directly in Perfetto (ui.perfetto.dev) or
chrome://tracing. Spans opened on different threads land on different
trace rows; a span's ``args`` carry its ``parent``'s name.

An optional ``sync`` pytree is blocked on (``jax.block_until_ready``)
before the span closes, so spans timing device work record real wall
time, not dispatch time.

**Request-scoped tracing** (docs/observability.md "Request tracing &
tail attribution"): a span may carry a
:class:`~paddle_tpu.observe.tracing.TraceContext` (``trace=ctx``) —
the context's trace/span/parent ids land in the span's args, and the
exporter links every span of one trace into a single flow-arrowed lane
("s"/"t"/"f" events) across threads, so one request's journey through
the HTTP thread, the dispatch loop and the spill writer renders as ONE
connected lane in Perfetto. :meth:`SpanTracer.add_event` records a span
retrospectively from stamped timestamps — the serving workers measure
phases as plain perf_counter pairs on the hot path and emit the spans
once, at request completion. Such a span is over when it is recorded, so
it reaches the StatSet and the Chrome export but not the profiler's
trace.
"""

import json
import os
import sys
import threading
import time
from contextlib import contextmanager

from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.utils.stat import global_stats

# What a process does before its first step and round each `train` call
# (docs/observability.md "Set-up spans"): span name -> (always-on registry
# histogram, help). One observation an occurrence, in ms, made by
# :func:`phase`.
PHASE_HISTOGRAMS = {
    "import": ("paddle_tpu_setup_import_ms",
               "one first use of a paddle.<submodule>: its import"),
    "init": ("paddle_tpu_setup_init_ms",
             "one paddle.init: the compile cache placed, the backend "
             "opened where the caller has not"),
    "params_create": ("paddle_tpu_setup_params_create_ms",
                      "one Parameters.create: every leaf initialised"),
    "params_update": ("paddle_tpu_setup_params_update_ms",
                      "one Parameters.update_from called by a user (not "
                      "the one inside sync_back)"),
    "trainer_prepare": ("paddle_tpu_setup_trainer_prepare_ms",
                        "one SGD.__init__: topology, step functions, "
                        "masters, replica and optimizer slots handed to "
                        "the device"),
    "train_enter": ("paddle_tpu_train_enter_ms",
                    "one train call from its entry to its feeder built"),
    "train_exit": ("paddle_tpu_train_exit_ms",
                   "one train call from its last step read back to its "
                   "return, sync_back included"),
    "sync_back": ("paddle_tpu_train_sync_back_ms",
                  "one read of every parameter back into Parameters"),
}


def _annotation(name, args):
    """An entered profiler annotation for a span, or None in a process
    that has not imported jax: nobody can be tracing there, and a span
    must not be what pulls jax in."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    note = profiler.TraceAnnotation("paddle_tpu." + name, **(args or {}))
    note.__enter__()
    return note


class _Scope:
    """Handle yielded by :meth:`SpanTracer.span`; ``dur`` (seconds) is set
    when the span closes, so callers timing a window can reuse the span's
    own measurement instead of keeping a second clock. ``parent`` is the
    name of the span that was open on this thread when this one opened,
    ``child_dur`` the summed durations of the spans that closed inside
    it."""

    __slots__ = ("name", "dur", "parent", "child_dur")

    def __init__(self, name, parent=None):
        self.name = name
        self.dur = None
        self.parent = parent
        self.child_dur = 0.0

    @property
    def self_dur(self):
        """The span's own time: ``dur`` less what its children covered."""
        return self.dur - self.child_dur


class SpanTracer:
    """Thread-safe span recorder. One process-global instance
    (:func:`get_tracer`) is shared by the trainer, the benchmark harness,
    and user code; sub-tracers are only needed for isolated tests."""

    MAX_EVENTS = 200_000  # hard cap; excess spans still feed stats

    def __init__(self, name="paddle_tpu", stats=global_stats,
                 record_events=True):
        self.name = name
        self.enabled = True
        # record_events: True/False, or None = auto — record only while
        # PADDLE_TPU_TELEMETRY is set (the process-global tracer uses
        # auto so a run with no possible trace consumer doesn't retain up
        # to MAX_EVENTS tuples in memory; consumers that WILL export —
        # the trainer/run.py telemetry paths — flip it to True)
        self.record_events = record_events
        self._lock = threading.Lock()
        # (name, t_start_s, dur_s, thread_ident, args, trace, parent) —
        # trace is (trace_id, span_id, parent_id) or None, parent the
        # enclosing span's name or None
        self._events = []
        self._open = threading.local()  # .stack: this thread's open spans
        self._dropped = 0
        self._stats = stats
        self._t0 = time.perf_counter()

    def _recording(self):
        if self.record_events is None:
            return bool(os.environ.get("PADDLE_TPU_TELEMETRY"))
        return self.record_events

    @contextmanager
    def span(self, name, sync=None, args=None, trace=None):
        """Time a scope. ``sync`` is an optional array/pytree blocked on
        before the span closes; ``args`` is a small dict of scalars shown
        in the trace viewer and stored as the profiler event's stats;
        ``trace`` is an optional sampled
        :class:`~paddle_tpu.observe.tracing.TraceContext` linking this
        span into its request's cross-thread flow lane."""
        try:
            stack = self._open.stack
        except AttributeError:
            stack = self._open.stack = []
        parent = stack[-1] if stack else None
        scope = _Scope(name, parent.name if parent is not None else None)
        stack.append(scope)
        note = _annotation(name, args)
        start = time.perf_counter()
        try:
            yield scope
        finally:
            if sync is not None:
                try:
                    import jax

                    jax.block_until_ready(sync)
                except Exception:
                    pass
            end = time.perf_counter()
            if note is not None:
                note.__exit__(None, None, None)
            # a span left open across a generator's yield can close out
            # of order: take this one off the stack wherever it sits
            if stack[-1] is scope:
                stack.pop()
            else:
                stack.remove(scope)
            # a disabled tracer still stamps dur (callers like the trainer
            # and harness consume scope.dur arithmetically) — it only stops
            # recording events and feeding stats
            scope.dur = end - start
            if parent is not None:
                parent.child_dur += scope.dur
            if self.enabled:
                if self._stats is not None:
                    self._stats.get(name).add(scope.dur)
                if self._recording():
                    self._record(name, start, scope.dur,
                                 threading.get_ident(), args, trace,
                                 scope.parent)

    def _record(self, name, t_start, dur, ident, args, trace, parent=None):
        """Append one event; ``t_start`` is absolute perf_counter time
        (made clock-relative under the lock, next to the ``_t0`` that
        reset() rewrites)."""
        tup = (None if trace is None or not trace.sampled
               else (trace.trace_id, trace.span_id, trace.parent_id))
        with self._lock:
            if len(self._events) < self.MAX_EVENTS:
                self._events.append((name, t_start - self._t0, dur,
                                     ident, args, tup, parent))
            else:
                self._dropped += 1

    def add_event(self, name, t_start, dur, args=None, trace=None,
                  ident=None):
        """Record a span retrospectively from stamped timestamps
        (``t_start`` is an absolute ``time.perf_counter()`` value,
        ``dur`` seconds). The serving workers time request phases as
        plain perf_counter pairs on the hot path and emit the spans
        once, at completion — same stats feed, same export, no
        contextmanager overhead per phase. The span is over by now, so no
        profiler annotation can cover it: it stays off the profiler's
        clock, and has no parent."""
        dur = max(float(dur), 0.0)
        if not self.enabled:
            return
        if self._stats is not None:
            self._stats.get(name).add(dur)
        if self._recording():
            self._record(name, t_start, dur,
                         ident if ident is not None
                         else threading.get_ident(), args, trace)

    def events(self):
        with self._lock:
            return list(self._events)

    def reset(self, at=None):
        """Drop recorded spans and restart the trace clock, now or at the
        earlier ``perf_counter`` reading ``at`` (where a span that is
        still open began); the StatSet aggregates are owned by the
        StatSet and are NOT reset here."""
        with self._lock:
            self._events = []
            self._dropped = 0
            self._t0 = time.perf_counter() if at is None else at

    def to_chrome_trace(self):
        """Chrome trace-event dict: ``{"traceEvents": [...]}`` with "X"
        complete events (ts/dur in µs) plus process/thread metadata.
        Trace-tagged spans additionally carry their trace/span/parent
        ids in args and are chained per trace_id with flow events
        ("s" start / "t" step / "f" finish, one flow id per trace) —
        Perfetto renders each request as one arrow-connected lane no
        matter how many threads it crossed."""
        pid = os.getpid()
        with self._lock:
            snapshot = list(self._events)
            dropped = self._dropped
        out = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": self.name}}]
        tids = {}
        flows = {}  # trace_id -> [(ts_s, tid)]
        for name, ts, dur, ident, args, trace, parent in snapshot:
            tid = tids.setdefault(ident, len(tids))
            ev = {"ph": "X", "name": name, "pid": pid, "tid": tid,
                  "ts": round(ts * 1e6, 3), "dur": round(dur * 1e6, 3)}
            if args or trace or parent:
                ev["args"] = dict(args or {})
            if parent:
                ev["args"]["parent"] = parent
            if trace:
                trace_id, span_id, parent_id = trace
                ev["args"]["trace_id"] = trace_id
                ev["args"]["span_id"] = span_id
                if parent_id:
                    ev["args"]["parent_id"] = parent_id
                flows.setdefault(trace_id, []).append((ts, tid))
            out.append(ev)
        for trace_id, points in flows.items():
            if len(points) < 2:
                continue  # a single span needs no arrow
            points.sort()
            flow_id = int(trace_id[:15], 16)
            last = len(points) - 1
            for i, (ts, tid) in enumerate(points):
                ev = {"ph": "s" if i == 0 else ("f" if i == last
                                                else "t"),
                      "name": "serve_trace", "cat": "serve_trace",
                      "id": flow_id, "pid": pid, "tid": tid,
                      "ts": round(ts * 1e6, 3)}
                if ev["ph"] == "f":
                    ev["bp"] = "e"  # bind to the enclosing slice
                out.append(ev)
        for ident, tid in tids.items():
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid,
                        "args": {"name": "host thread %d" % tid}})
        trace = {"traceEvents": out, "displayTimeUnit": "ms"}
        if dropped:
            trace["metadata"] = {"dropped_spans": dropped}
        return trace

    def export(self, path):
        """Write the Chrome-trace JSON (gzipped when ``path`` ends in
        .gz); returns ``path``. Open the file in Perfetto or
        chrome://tracing."""
        data = self.to_chrome_trace()
        if path.endswith(".gz"):
            import gzip

            with gzip.open(path, "wt") as fh:
                json.dump(data, fh)
        else:
            with open(path, "w") as fh:
                json.dump(data, fh)
        return path


_global_tracer = SpanTracer(record_events=None)


def get_tracer():
    """The process-global tracer every subsystem shares."""
    return _global_tracer


def span(name, sync=None, args=None, trace=None):
    """Module-level shortcut: ``with observe.span("feed"): ...``."""
    return _global_tracer.span(name, sync=sync, args=args, trace=trace)


@contextmanager
def phase(name, args=None, labels=None, unless_in=None):
    """A span of :data:`PHASE_HISTOGRAMS` on the process-global tracer
    that also observes its histogram once, in ms, when it closes (also
    where its body raised: the time was spent). Opened inside a span
    named ``unless_in`` it is that span's child and observes nothing:
    its time is already its parent's."""
    scope = None
    try:
        with _global_tracer.span(name, args=args) as scope:
            yield scope
    finally:
        if scope is not None and (unless_in is None
                                  or scope.parent != unless_in):
            hist, help = PHASE_HISTOGRAMS[name]
            # looked up an occurrence, not held: a handful a process
            observe_metrics.get_registry().histogram(
                hist, help=help, labels=labels).observe(scope.dur * 1e3)


def export(path):
    return _global_tracer.export(path)
