"""paddle_tpu.observe — spans, device attribution, step telemetry.

The observability subsystem the rest of the stack instruments against
(reference: paddle/utils/Stat.h REGISTER_TIMER registry, per-layer timers
in gserver/NeuralNetwork.cpp:248, and the hl_profiler_start/end CUDA
profiler window). Three pieces behind one package:

* :mod:`paddle_tpu.observe.spans` — nested named spans with optional
  device sync, thread-safe, each knowing its parent and its self time:
  one ``span()`` call feeds the :class:`paddle_tpu.utils.stat.StatSet`
  aggregates, the Chrome-trace/Perfetto JSON export, and a
  ``jax.profiler.TraceAnnotation`` on the profiler's own clock.
* :mod:`paddle_tpu.observe.attribution` — device-trace attribution
  (promoted from benchmark/traceutil.py): per-op device time, fusion
  grouping, MXU-utilization estimates, and the dispatch-gap detector that
  flags scan/while-loop dispatch-bound regions.
* :mod:`paddle_tpu.observe.steplog` — per-step JSONL telemetry sink with
  a stable documented schema (docs/observability.md), activated by
  ``PADDLE_TPU_TELEMETRY=<dir>``.
* :mod:`paddle_tpu.observe.metrics` — process-wide registry of counters,
  gauges and fixed-bucket latency histograms (exact p50/p95/p99 readout),
  rendered as Prometheus text exposition (``GET /metrics`` on the serve
  front end) and as a JSON snapshot.
* :mod:`paddle_tpu.observe.sentinel` — training flight recorder (ring of
  the last N step records, dumped as a ``crash_report`` on exception or
  trip) plus the NaN/Inf-loss and loss-divergence sentinel
  (``PADDLE_TPU_SENTINEL``: warn by default, ``halt`` raises).
* :mod:`paddle_tpu.observe.regress` — spread-aware bench regression gate
  against the audited ``BENCH_*.json``/``BASELINE.json`` record
  (``PADDLE_TPU_BENCH_GATE=hard`` fails a regressed bench run).
* :mod:`paddle_tpu.observe.tracing` — request-scoped distributed
  tracing for the serving tier: W3C-traceparent-shaped
  :class:`~paddle_tpu.observe.tracing.TraceContext` propagated by value
  through every thread hop, ``PADDLE_TPU_TRACE_SAMPLE`` sampling, the
  always-on slowest-N exemplar reservoir (``GET /debug/traces``) and
  the tail-attribution report (``cli observe``).

Everything degrades to a no-op when profiling is unavailable: spans always
work (their profiler annotation is inert while nobody traces),
attribution returns None without a usable
profiler backend, and the steplog is simply not created without the env
flag.
"""

from paddle_tpu.observe import (attribution, metrics, regress,  # noqa: F401
                                sentinel, spans, steplog, tracing)
from paddle_tpu.observe.tracing import TraceContext  # noqa: F401
from paddle_tpu.observe.metrics import get_registry  # noqa: F401
from paddle_tpu.observe.spans import get_tracer, span  # noqa: F401
from paddle_tpu.observe.steplog import StepLog, from_env, telemetry_dir  # noqa: F401
