"""Aggregate timers.

Equivalent of REGISTER_TIMER / StatSet (reference: paddle/utils/Stat.h:63,114,
230-233; per-layer timers at gserver NeuralNetwork.cpp:248). On TPU the inner
compute is one fused XLA program, so timers wrap host-visible phases (trace,
compile, device step, data feed) plus any user scopes. The one scope timer
is :func:`paddle_tpu.observe.spans.span`, which feeds a StatSet under the
span's name (``sync=`` blocks on device work so wall time is real, not
dispatch time).
"""

import threading
from contextlib import contextmanager


class StatInfo:
    __slots__ = ("name", "total", "count", "max", "min")

    def __init__(self, name):
        self.name = name
        self.total = 0.0
        self.count = 0
        self.max = 0.0
        self.min = float("inf")

    def add(self, seconds):
        self.total += seconds
        self.count += 1
        self.max = max(self.max, seconds)
        self.min = min(self.min, seconds)

    @property
    def avg(self):
        return self.total / self.count if self.count else 0.0

    def __repr__(self):
        return "Stat(%s: total=%.4fs count=%d avg=%.4fs max=%.4fs min=%.4fs)" % (
            self.name, self.total, self.count, self.avg, self.max,
            0.0 if self.min == float("inf") else self.min,
        )


class StatSet:
    def __init__(self, name="global"):
        self.name = name
        self._lock = threading.Lock()
        self._stats = {}

    def get(self, name):
        with self._lock:
            stat = self._stats.get(name)
            if stat is None:
                stat = self._stats[name] = StatInfo(name)
            return stat

    def print_all(self, log=None):
        if log is None:
            from paddle_tpu.utils.logger import logger as log_mod

            log = log_mod.info
        with self._lock:
            stats = sorted(self._stats.values(), key=lambda s: -s.total)
        log("======= StatSet: [%s] =======", self.name)
        for stat in stats:
            log("  %r", stat)

    def reset(self):
        with self._lock:
            self._stats.clear()

    def as_dict(self):
        with self._lock:
            return {
                k: {"total": v.total, "count": v.count, "avg": v.avg}
                for k, v in self._stats.items()
            }


global_stats = StatSet("global")


@contextmanager
def profiler_trace(logdir="/tmp/paddle_tpu_trace"):
    """Capture an xprof/TensorBoard device trace for the enclosed region
    (reference: hl_profiler_start/hl_profiler_end, hl_cuda.h — the CUDA
    profiler window; here jax.profiler, viewable with xprof/TensorBoard)."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
