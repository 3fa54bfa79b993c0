"""The train driver: one cell, one run, through `paddle.trainer.SGD.train`.

Set-up builds one trainer from the seed, drives it through its first three
steps by the same call and feed as the window (one `train` call of one
batch, one of two, so the parameters can be read after step one and after
step three), then opens the window on that same trainer. Once the window
has closed and the peak memory has been read, the trainer is dropped and
the plain reference follows the same three batches.
"""

import gc
import importlib
import math
import os
import shutil
import threading
import time

import numpy as np

from chipbench import check, trace as trace_mod, traffic, window

# What every train cell runs: the v2 call a user makes, its feed pipeline
# on, Momentum as the 2017 benchmark configurations set it. The window opens
# at the RAMP_STEPS-th step of its `train` call, once the feeder's queue is
# full.
LEARNING_RATE = 0.01
MOMENTUM = 0.9
RAMP_STEPS = 4


def _module(kind, name):
    return importlib.import_module("chipbench.%s.%s" % (kind, name))


class Handler:
    """Stamps every `EndIteration` on the host clock, opens the window after
    `ramp` steps of the call, closes it at the first step that ends
    `seconds` later, and tells the reader to end the pass. In a traced run
    it also starts and stops the profiler round a stretch of steps inside
    the window."""

    def __init__(self, seconds, ramp, on_open, on_close, trace_plan=None):
        self.seconds = seconds
        self.ramp = ramp
        self.on_open = on_open
        self.on_close = on_close
        self.trace_plan = trace_plan
        self.stop = threading.Event()
        self.stamps = []      # of the window: first stamp opens it
        self.costs = []       # of the window's steps
        self.seen = 0
        self.trace_stamps = None
        self.traced = False

    def __call__(self, event):
        import jax

        from paddle_tpu import event as v2_event

        if not isinstance(event, v2_event.EndIteration):
            return
        with jax.profiler.TraceAnnotation("chipbench.handler"):
            now = time.perf_counter()
            self.seen += 1
            if self.stop.is_set() or self.seen < self.ramp:
                return
            if not self.stamps:
                self.on_open()
                now = time.perf_counter()
                self.stamps.append(now)
                return
            self.stamps.append(now)
            self.costs.append(event.cost)
            elapsed = now - self.stamps[0]
            if self.trace_plan is not None:
                self._trace(elapsed, now)
            if elapsed >= self.seconds:
                self.on_close()
                self.stop.set()

    def _trace(self, elapsed, now):
        import jax

        plan = self.trace_plan
        if self.trace_stamps is None:
            if elapsed >= plan["after_s"]:
                # the device's and the host tracer's events are all the
                # reduction reads; Python's own tracer slows the host
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(plan["dir"],
                                         profiler_options=options)
                self.trace_stamps = [time.perf_counter()]
        elif not self.traced:
            self.trace_stamps.append(now)
            if len(self.trace_stamps) > plan["steps"]:
                jax.profiler.stop_trace()
                self.traced = True


def _reader_of(batches):
    return lambda: iter(batches)


def _cycling_reader(pool, stop):
    def reader():
        import jax

        i = 0
        while not stop.is_set():
            with jax.profiler.TraceAnnotation("chipbench.reader"):
                batch = pool[i % len(pool)]
            yield batch
            i += 1
    return reader


def _collect(costs):
    def handler(event):
        from paddle_tpu import event as v2_event

        if isinstance(event, v2_event.EndIteration):
            costs.append(event.cost)
    return handler


def _snapshot(params, names):
    """{reference name: host array} of the trainer's synced parameters."""
    return {ref: np.array(params.get(prog), copy=True)
            for ref, prog in names.items()}


def run(cell, cfg, args, started, rehearsal=False):
    """Runs the cell once. Returns the result line's dict."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.observe import metrics as observe_metrics
    from paddle_tpu.parallel.mesh import DataParallel, build_mesh
    from paddle_tpu.utils import compile_cache

    chips = int(cell["chips"])
    devices = jax.devices()
    if not rehearsal:
        if devices[0].platform != "tpu" or len(devices) < chips:
            raise SystemExit(
                "chipbench: cell %s needs %d TPU chip(s), jax.devices() is %r"
                % (cell["name"], chips, devices))
    elif len(devices) < chips:
        raise SystemExit("chipbench: rehearsal of %s needs %d devices, got %r"
                         % (cell["name"], chips, devices))
    devices = devices[:chips]

    precision = cfg["precision"]
    paddle.init(use_tpu=not rehearsal, seed=int(args.seed) % (2 ** 31),
                compute_dtype=precision["compute_dtype"],
                matmul_precision=precision["matmul_precision"])
    model = _module("models", cfg["model"])
    ref = _module("reference", cfg["reference"])
    flops = _module("flops", cfg["flops"])

    cost = model.build(cfg)
    names = model.program_names(cfg)
    weights, state = ref.init_weights(args.seed, cfg)
    params = paddle.parameters.create(cost)
    given = {names[k]: v for k, v in {**weights, **state}.items()}
    missing = sorted(set(params.names()) ^ set(given))
    if missing:
        raise SystemExit("chipbench: reference and program disagree on "
                         "parameters: %s" % missing[:8])
    for name, value in given.items():
        if tuple(value.shape) != params.get_shape(name):
            raise SystemExit("chipbench: %s is %s in the reference, %s in "
                             "the program" % (name, value.shape,
                                              params.get_shape(name)))
    params.update_from(given)
    del weights, state, given

    lr, mu = LEARNING_RATE, MOMENTUM
    parallelism = None
    if cell.get("parallelism") == "data":
        parallelism = DataParallel(build_mesh({"data": chips},
                                              devices=devices))
    trainer = paddle.trainer.SGD(
        cost, params, paddle.optimizer.Momentum(learning_rate=lr,
                                                momentum=mu),
        parallelism=parallelism)
    pool = traffic.make_pool(cfg["inputs"], cell, args.seed)

    # the first three steps, through the window's own call and feed
    first = [pool[i % len(pool)] for i in range(3)]
    costs = []
    trainer.train(_reader_of(first[:1]), event_handler=_collect(costs),
                  feed_pipeline=True)
    after1 = _snapshot(trainer.parameters, names)
    trainer.train(_reader_of(first[1:]), event_handler=_collect(costs),
                  feed_pipeline=True)
    after3 = _snapshot(trainer.parameters, names)

    registry = observe_metrics.get_registry()
    marks = {}

    def on_open():
        marks["registry_open"] = registry.snapshot()["histograms"]
        marks["compiles_open"] = compile_cache.stats()
        marks["setup_s"] = time.perf_counter() - started

    def on_close():
        marks["registry_close"] = registry.snapshot()["histograms"]
        marks["compiles_close"] = compile_cache.stats()

    trace_plan = None
    if args.trace:
        trace_dir = os.path.join(args.work_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        plan = cell["trace"]
        trace_plan = {"dir": trace_dir, "steps": int(plan["steps"]),
                      "after_s": min(float(plan["after_s"]),
                                     0.3 * args.seconds)}
    handler = Handler(args.seconds, RAMP_STEPS, on_open, on_close,
                      trace_plan)
    trainer.train(_cycling_reader(pool, handler.stop), event_handler=handler,
                  feed_pipeline=True)
    if trace_plan is not None and handler.trace_stamps is not None \
            and not handler.traced:
        jax.profiler.stop_trace()
        handler.traced = len(handler.trace_stamps) > 1
    if len(handler.stamps) < 3:
        raise SystemExit("chipbench: the window closed after %d steps"
                         % max(len(handler.stamps) - 1, 0))

    # On this runtime a program's temporaries are reserved, not "in use":
    # the two pools add up to the HBM that is held (largest_free_block
    # confirms it), so the peak is the sum of their peaks.
    memory = [d.memory_stats() or {} for d in devices]
    peak = max(m.get("peak_bytes_in_use", 0)
               + m.get("peak_bytes_reserved", 0) for m in memory)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": chips, "memory_peak_bytes": int(peak)}

    ctx = {
        "cell": cell, "cfg": cfg, "chips": chips, "flops": flops,
        "stamps": handler.stamps, "samples_per_step": int(cell["batch"]),
        "setup_s": marks["setup_s"], "peak_bytes": int(peak),
        "registry_open": marks["registry_open"],
        "registry_close": marks["registry_close"],
        "compiles_open": marks["compiles_open"],
        "compiles_close": marks["compiles_close"],
        "device_kind": kind, "trace": None, "traced_steps": 0,
        "memory_stats": memory[0],
    }
    if trace_plan is not None and handler.traced:
        reduced = trace_mod.reduce(
            trace_mod.load(trace_mod.find_xplane(trace_plan["dir"])),
            kernel_marks=tuple(cell["trace"].get("kernel_marks", ())))
        ctx["trace"] = reduced
        ctx["traced_steps"] = len(handler.trace_stamps) - 1
        shutil.rmtree(trace_plan["dir"], ignore_errors=True)

    # the program's readings, then its state goes before the reference runs
    lost = sum(1 for c in costs + handler.costs if not math.isfinite(c))
    w0, s0 = ref.init_weights(args.seed, cfg)
    w0 = {k: np.asarray(v) for k, v in w0.items()}
    s0 = {k: np.asarray(v) for k, v in s0.items()}
    program = {
        "losses": costs,
        "grad1": {k: (w0[k] - after1[k]) / lr for k in w0},
        "delta3": {k: after3[k] - w0[k] for k in w0},
        "state3": {k: after3[k] - s0[k] for k in s0},
    }
    del trainer, params, parallelism, after1, after3, w0, s0
    gc.collect()

    from chipbench.reference import common

    t_ref = time.perf_counter()
    reference = common.train3(
        ref, cfg, args.seed, [ref.batch_arrays(b, cfg) for b in first],
        lr, mu, devices=devices)
    ctx["reference_s"] = time.perf_counter() - t_ref
    numbers = check.readings(program, reference)
    compared, correct = check.decide(numbers, cell["limits"])
    correct = correct and lost == 0
    return {"correct": bool(correct),
            "attempted": len(handler.stamps) - 1 + len(costs),
            "failed": lost, "device": device, "ctx": ctx,
            "numbers": numbers, "compared": compared}
