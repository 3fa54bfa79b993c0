"""The persistent XLA compilation cache, placed once for every entry point,
and the process's one set of ``jax.monitoring`` listeners.

``paddle.init``, ``cli.main``, ``bench.py``, ``chip_smoke.py`` and the
``serve/workers.py`` children all call :func:`enable` before their first
compile (JAX decides whether the cache is in use at the first compile of
the process, so a later call is too late for that process).

**Where it lives** is the user's choice: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and this module
places nothing. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the path is part of the cache's key, so one
built from a temp dir, a pid or the time would never hit.

**What it keeps** is the framework's, wherever it lives: every program a
process compiles (:data:`KEEP_FROM_SECS`), not only those that took JAX's
default of a second. A process's set-up is a hundred small programs (one
``fold_in`` and one initialiser a leaf in ``Parameters.create``, the
eager ``jnp`` calls of ``SGD.__init__``, the programs round the first
steps), each quick and together 14-20 s of every warm set-up on the chip
(PERF.md §5) while JAX wrote none of them: a read looks the key up
whatever the threshold, only the write is gated. So the first run on a
machine writes them (a few KB each on the CPU, more on the TPU: sizes in
docs/observability.md "Set-up spans") and every later process reads
them. An explicit ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` is the
user's word and stands. A cold run is a deleted directory: nothing here
or in JAX evicts an entry.

**How often it engages**: ``paddle_tpu_compile_backend_ms``'s count is
the programs the process asked the backend for,
``paddle_tpu_compile_cache_retrieval_ms``'s count and sum are those of
them that were read and what the reads took; :func:`stats` holds the
same as ``requests`` and ``hits``, and the entries on disk.

**Compile phases** (docs/observability.md "Set-up spans"): JAX times its
own tracing, lowering and backend compile and reports each as a
``jax.monitoring`` duration event. :func:`listen` registers one listener
of each kind, once a process, and keeps the times in four always-on
registry histograms (:data:`PHASE_HISTOGRAMS`, ms). Tracing a step fires
one trace event for every jitted function it calls, each inside the
outer one's duration; an event therefore observes its *self* time, its
duration less that of the events that began and ended inside it on the
same thread, whatever their kind. So the sums of the three phases
together never exceed the wall time of the thread that compiled.
:func:`subscribe` hands every duration event on (``observe/steplog.py``:
the steplog's ``event`` records, ``watch_compiles``), so the process has
one registration and what is counted is what is timed.
"""

import os
import threading

from paddle_tpu.observe import metrics as observe_metrics

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")
# Seconds of compiling from which a program is written to the cache. JAX's
# own 1.0 keeps the step and drops every small program of set-up, which a
# warm process then compiles again, 0.1-0.15 s each on the chip (PERF.md §5).
KEEP_FROM_SECS = 0.0

# JAX's event -> (histogram, help). The first three nest in one another
# (jax/_src/dispatch.py log_elapsed_time: a scalar event at the start, a
# duration event at the end); the retrieval lies inside a backend compile
# and has a histogram of its own, part of no sum.
PHASE_HISTOGRAMS = {
    "/jax/core/compile/jaxpr_trace_duration": (
        "paddle_tpu_compile_trace_ms",
        "self time of one jaxpr trace (inner traces counted once)"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (
        "paddle_tpu_compile_lower_ms",
        "self time of one lowering of a jaxpr to an MLIR module"),
    "/jax/core/compile/backend_compile_duration": (
        "paddle_tpu_compile_backend_ms",
        "self time of one backend compile: XLA's compile, or on a "
        "cache hit the retrieval and load"),
}
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
RETRIEVAL_HISTOGRAM = (
    "paddle_tpu_compile_cache_retrieval_ms",
    "one read of the persistent compile cache that hit")

_lock = threading.Lock()
_counts = {"requests": 0, "hits": 0}
_listening = False
_subscribers = ()  # replaced whole under _lock, read without it
# .stack: seconds of the ended events inside each phase still open on
# this thread, outermost first
_open = threading.local()


def _on_event(event, **kw):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        key = "requests"
    elif event == "/jax/compilation_cache/cache_hits":
        key = "hits"
    else:
        return
    with _lock:
        _counts[key] += 1


def _on_start(event, value, **kw):
    if event in PHASE_HISTOGRAMS:
        try:
            _open.stack.append(0.0)
        except AttributeError:
            _open.stack = [0.0]


def _on_duration(event, secs, **kw):
    entry, own = PHASE_HISTOGRAMS.get(event), secs
    if entry is not None:
        stack = getattr(_open, "stack", None)
        if stack:
            own = max(secs - stack.pop(), 0.0)
        if stack:
            stack[-1] += secs
    elif event == RETRIEVAL_EVENT:
        entry = RETRIEVAL_HISTOGRAM
    if entry is not None:
        # looked up an event, not held: a registry that was reset (tests)
        # would otherwise keep a detached histogram
        observe_metrics.get_registry().histogram(
            entry[0], help=entry[1]).observe(own * 1e3)
    for callback in _subscribers:
        callback(event, secs)


def listen():
    """Register this module's listeners, once a process: JAX keeps a
    listener for the process's life, so nothing else registers one."""
    global _listening
    from jax import monitoring

    with _lock:
        if _listening:
            return
        monitoring.register_event_listener(_on_event)
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def subscribe(callback):
    """Have ``callback(event, secs)`` called for every ``jax.monitoring``
    duration event of the process from now on, on the thread that emits
    it, with the event's whole duration."""
    global _subscribers
    listen()
    with _lock:
        if callback not in _subscribers:
            _subscribers = _subscribers + (callback,)


def enable():
    """Place the cache, have it keep quick programs too, and start
    counting its hits and timing the compile phases. Returns the directory
    in use. A directory that cannot be created is an error."""
    import jax

    listen()
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          KEEP_FROM_SECS)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def stats():
    """``{"dir", "entries", "requests", "hits"}``: entries on disk now, and
    this process's compile requests that consulted the cache and how many
    of them it answered (requests - hits = programs really compiled)."""
    import jax

    directory = jax.config.jax_compilation_cache_dir
    entries = 0
    if directory and os.path.isdir(directory):
        entries = sum(1 for n in os.listdir(directory)
                      if n.endswith("-cache"))
    with _lock:
        return {"dir": directory, "entries": entries, **_counts}
