"""The persistent XLA compilation cache, placed once for every entry point.

``paddle.init``, ``cli.main``, ``bench.py``, ``chip_smoke.py`` and the
``serve/workers.py`` children all call :func:`enable` before their first
compile (JAX decides whether the cache is in use at the first compile of
the process, so a later call is too late for that process).

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and this
module sets nothing. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the path is part of the cache's key, so one
built from a temp dir, a pid or the time would never hit.
"""

import os
import threading

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")

_lock = threading.Lock()
_counts = {"requests": 0, "hits": 0}
_listening = False


def _on_event(event, **kw):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        key = "requests"
    elif event == "/jax/compilation_cache/cache_hits":
        key = "hits"
    else:
        return
    with _lock:
        _counts[key] += 1


def enable():
    """Place the cache and start counting its hits. Returns the directory
    in use. A directory that cannot be created is an error."""
    global _listening
    import jax

    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
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
