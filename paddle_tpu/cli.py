"""Command-line launcher.

Parity with the reference's CLI surface (paddle/trainer/TrainerMain.cpp —
jobs train/test/time; paddle/scripts `paddle train --config=...`;
MergeModel.cpp). The config is a Python module defining the topology
(the reference also executed Python for configs — config_parser.py via the
embedded interpreter — so a Python config file is the faithful shape):

    python -m paddle_tpu.cli train --config my_config.py --num-passes 5
    python -m paddle_tpu.cli time  --config my_config.py --iters 50
    python -m paddle_tpu.cli test  --config my_config.py --params ckpt.tar
    python -m paddle_tpu.cli merge_model --config c.py --params p.tar -o m.tar

The config module must define ``cost()`` returning the cost layer (and may
define ``optimizer()``, ``train_reader()``, ``test_reader()``,
``batch_size``). A checkgrad job mirrors --job=checkgrad
(Trainer::checkGradient, Trainer.cpp:299) using the float64 harness.
"""

import argparse
import importlib.util
import json
import os
import sys
import time


def _load_config(path, config_args=""):
    from paddle_tpu import config as cfgmod

    cfgmod.reset()
    cfgmod.set_config_args(config_args)
    # Reference configs import `paddle.trainer_config_helpers` and sibling
    # data-provider modules; expose the compat package and the config's own
    # directory for the duration of the exec only (a config dir's helper
    # named like a real module must not shadow imports process-wide), like
    # the reference CLI's embedded config_parser did.
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    added = []
    for extra in (os.path.join(repo_root, "compat"),
                  os.path.dirname(os.path.abspath(path))):
        if os.path.isdir(extra) and extra not in sys.path:
            sys.path.insert(0, extra)
            added.append(extra)
    try:
        spec = importlib.util.spec_from_file_location(
            "paddle_tpu_user_config", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["paddle_tpu_user_config"] = mod
        # py2-era configs (the reference is a 2017 codebase) may use xrange
        mod.xrange = range
        spec.loader.exec_module(mod)
    finally:
        for extra in added:
            try:
                sys.path.remove(extra)
            except ValueError:
                pass
    # v1-DSL configs (settings()/outputs()/define_py_data_sources2) leave
    # their declarations in the config registry; adapt them onto the
    # cost()/optimizer()/train_reader() surface the commands consume
    st = cfgmod.pop_config()
    if st is not None:
        outputs = st["outputs"]
        if outputs and not hasattr(mod, "cost"):
            mod.cost = (lambda: outputs[0] if len(outputs) == 1
                        else outputs)
        if not hasattr(mod, "optimizer") and st["settings"].get("optimizer"):
            mod.optimizer = lambda: st["settings"]["optimizer"]
        if st["settings"].get("batch_size") and not hasattr(mod,
                                                            "batch_size"):
            mod.batch_size = st["settings"]["batch_size"]
        ds = st["data_sources"]
        if "train" in ds and not hasattr(mod, "train_reader"):
            mod.train_reader = ds["train"]
        if "test" in ds and not hasattr(mod, "test_reader"):
            mod.test_reader = ds["test"]
    return mod


def _build(cfg, parallelism=None):
    import paddle_tpu as paddle
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.utils import flags

    cost = cfg.cost()
    params = Parameters.create(cost)
    if hasattr(cfg, "optimizer"):
        optimizer = cfg.optimizer()
    else:
        from paddle_tpu import optimizer as opt

        optimizer = opt.Momentum(learning_rate=0.01, momentum=0.9)
    extra = list(cfg.evaluators()) if hasattr(cfg, "evaluators") else None
    # --trainer-count N (reference: --trainer_count spun N worker threads,
    # MultiGradientMachine): here it builds an N-device data-parallel mesh
    # and pjits the train step over it — XLA inserts the gradient psum
    tc = flags.get_flag("trainer_count") or 1
    if parallelism is None and tc > 1:
        import jax

        from paddle_tpu.parallel.mesh import DataParallel, build_mesh

        n_dev = len(jax.devices())
        if tc > n_dev:
            raise SystemExit(
                "--trainer-count %d exceeds the %d visible devices "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "for a virtual CPU mesh)" % (tc, n_dev))
        parallelism = DataParallel(
            build_mesh({"data": tc}, devices=jax.devices()[:tc]))
    trainer = paddle.trainer.SGD(cost, params, optimizer, extra_layers=extra,
                                 parallelism=parallelism)
    return cost, params, trainer


def cmd_train(args):
    import paddle_tpu as paddle
    from paddle_tpu import minibatch

    cfg = _load_config(args.config,
                       getattr(args, "config_args", ""))
    cost, params, trainer = _build(cfg)
    batch_size = getattr(cfg, "batch_size", args.batch_size)
    reader = minibatch.batch(cfg.train_reader(), batch_size)
    if args.init_model:
        trainer.restore_checkpoint(args.init_model)

    save_dir = args.save_dir

    def handler(event):
        import paddle_tpu.event as ev

        if isinstance(event, ev.EndPass) and save_dir:
            trainer.save_checkpoint(save_dir, pass_id=event.pass_id)

    trainer.train(reader, num_passes=args.num_passes, event_handler=handler,
                  feed_pipeline=getattr(args, "feed_pipeline", 0) or False,
                  steps_per_call=getattr(args, "steps_per_call", 0) or None,
                  checkpoint_dir=getattr(args, "checkpoint_dir", "") or None,
                  checkpoint_every=getattr(args, "checkpoint_every", 0),
                  checkpoint_keep=getattr(args, "checkpoint_keep", 3),
                  checkpoint_sync=getattr(args, "checkpoint_sync", False),
                  resume=getattr(args, "resume", False))
    if hasattr(cfg, "test_reader"):
        result = trainer.test(minibatch.batch(cfg.test_reader(), batch_size))
        print("test cost=%.6f metrics=%s" % (result.cost, result.metrics))
    return 0


def cmd_test(args):
    import paddle_tpu as paddle
    from paddle_tpu import minibatch

    cfg = _load_config(args.config,
                       getattr(args, "config_args", ""))
    cost, params, trainer = _build(cfg)
    if args.params:
        with open(args.params, "rb") as f:
            params.init_from_tar(f)
        trainer.__prepare__()
    result = trainer.test(
        minibatch.batch(cfg.test_reader(), getattr(cfg, "batch_size",
                                                   args.batch_size)))
    print("test cost=%.6f metrics=%s" % (result.cost, result.metrics))
    return 0


def cmd_time(args):
    """--job=time parity (TrainerBenchmark.cpp): steady-state ms/batch."""
    import jax

    from paddle_tpu import minibatch

    cfg = _load_config(args.config,
                       getattr(args, "config_args", ""))
    cost, params, trainer = _build(cfg)
    batch_size = getattr(cfg, "batch_size", args.batch_size)
    batches = list(minibatch.batch(cfg.train_reader(), batch_size)())
    if not batches:
        print("no data")
        return 1
    feed_batches = batches[: max(args.iters, 1)]
    # warmup (compile)
    trainer.train(lambda: iter(feed_batches[:1]), num_passes=1)
    start = time.perf_counter()
    count = 0
    for batch in feed_batches:
        trainer.train(lambda b=batch: iter([b]), num_passes=1,
                      sync_params=False)
        count += 1
    jax.block_until_ready(trainer._trainable)
    elapsed = (time.perf_counter() - start) / count * 1000.0
    print(json.dumps({"ms_per_batch": round(elapsed, 3),
                      "batch_size": batch_size, "batches": count}))
    return 0


def cmd_checkgrad(args):
    """--job=checkgrad parity: numeric-vs-analytic on the user's config."""
    from paddle_tpu.checkgrad import check_layer_grad  # float64 harness
    from paddle_tpu import minibatch
    from paddle_tpu.topology import Topology, convert_feed

    cfg = _load_config(args.config,
                       getattr(args, "config_args", ""))
    cost = cfg.cost()
    topo = Topology(cost)
    batch = next(iter(minibatch.batch(cfg.train_reader(),
                                      getattr(cfg, "batch_size", 8))()))
    feed = convert_feed(topo, batch)
    check_layer_grad(cost, feed, check_inputs=False)
    print("checkgrad PASSED")
    return 0


def cmd_cluster_train(args):
    """Cluster launcher job (reference: scripts/cluster_train/paddle.py —
    started pservers+trainers across hosts; pserver-free here, see
    distributed/launcher.py)."""
    from paddle_tpu.distributed.launcher import launch_local_cluster
    from paddle_tpu.utils import flags

    if (flags.get_flag("trainer_count") or 1) > 1:
        raise SystemExit(
            "--trainer-count does not apply to cluster_train: every worker "
            "spans the GLOBAL mesh; use --num-processes (and per-host "
            "device visibility) to set the parallel width")
    results = launch_local_cluster(
        args.config, args.num_processes, num_passes=args.num_passes,
        batch_size=args.batch_size, config_args=args.config_args,
        devices_per_process=args.devices_per_process,
        use_tpu=args.use_tpu)
    for r in results:
        print(json.dumps(r))
    return 0


def cmd_merge_model(args):
    """MergeModel.cpp parity: fuse the model topology (a serialized
    ModelConfig proto, built by re-invoking the builder/config) + params
    into ONE tar that capi loads without executing any user Python
    (reference: paddle/trainer/MergeModel.cpp; consumed by
    paddle_gradient_machine_create_for_inference, capi/gradient_machine.h:36).
    Layers whose constructor args aren't serializable are recorded opaque;
    such models keep needing the builder escape hatch (interchange.py)."""
    import tarfile
    import io

    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.topology import Topology
    from paddle_tpu.proto.interchange import opaque_layer_names

    reset_name_counters()
    if args.builder:
        from paddle_tpu.capi.bridge import _run_builder

        outputs = _run_builder(args.builder)
    elif args.config:
        cfg = _load_config(args.config, getattr(args, "config_args", ""))
        fn = getattr(cfg, "infer_outputs", None) or cfg.cost
        outputs = fn()
    else:
        print("merge_model needs --builder or --config", file=sys.stderr)
        return 2
    msg = Topology(outputs).to_proto()
    opaque = opaque_layer_names(msg)
    proto_bytes = msg.SerializeToString()

    with open(args.params, "rb") as f:
        payload = f.read()
    manifest = json.dumps({
        "format": "paddle_tpu-merged-model-v1",
        "builder": args.builder or "",
        "config_file": os.path.basename(args.config or ""),
        "opaque_layers": opaque,
    }).encode()
    with tarfile.open(args.output, "w") as tar:
        info = tarfile.TarInfo("merged_manifest.json")
        info.size = len(manifest)
        tar.addfile(info, io.BytesIO(manifest))
        info = tarfile.TarInfo("model.pb")
        info.size = len(proto_bytes)
        tar.addfile(info, io.BytesIO(proto_bytes))
        info = tarfile.TarInfo("parameters.tar")
        info.size = len(payload)
        tar.addfile(info, io.BytesIO(payload))
        if args.config:
            tar.add(args.config, arcname=os.path.basename(args.config))
    if opaque:
        print("note: opaque layers (builder required at load): %s"
              % ",".join(opaque))
    print("merged model written to", args.output)
    return 0


def cmd_export(args):
    """AOT-export an inference bundle (docs/serving.md): lower the
    forward per batch bucket with jax.export and write manifest + packed
    params + serialized artifacts. The bundle reloads in a fresh process
    WITHOUT re-running any model-config code (contrast merge_model, which
    still rebuilds the topology from its proto at load time)."""
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.export import export_bundle, verify_bundle

    reset_name_counters()
    if args.builder:
        from paddle_tpu.capi.bridge import _run_builder

        outputs = _run_builder(args.builder)
    elif args.config:
        cfg = _load_config(args.config, getattr(args, "config_args", ""))
        fn = getattr(cfg, "infer_outputs", None) or cfg.cost
        outputs = fn()
    else:
        print("export needs --builder or --config", file=sys.stderr)
        return 2
    with open(args.params, "rb") as f:
        params = Parameters.from_tar(f)
    batch_sizes = tuple(int(b) for b in args.batch_sizes.split(",") if b)
    decode_slots = tuple(int(s) for s in
                         getattr(args, "decode_slots", "").split(",")
                         if s) or None
    manifest = export_bundle(outputs, params, args.output,
                             batch_sizes=batch_sizes,
                             seq_len=args.seq_len, name=args.name or None,
                             platforms=(args.platforms.split(",")
                                        if args.platforms else None),
                             decode_slots=decode_slots,
                             decode_window=getattr(args, "decode_window",
                                                   None),
                             quantize=getattr(args, "quantize", "") or None)
    import jax

    if jax.default_backend() in manifest["platforms"]:
        # export-time smoke: the written artifacts must deserialize and
        # run HERE (cross-platform exports can only be checked on their
        # target backend — `cli serve --selfcheck` there)
        verify_bundle(args.output)
    summary = {"bundle": args.output,
               "name": manifest["name"],
               "buckets": [b["batch"] for b in manifest["buckets"]],
               "inputs": [i["name"] for i in manifest["inputs"]],
               "platforms": manifest["platforms"],
               "hbm_estimate_bytes": manifest["hbm_estimate_bytes"]}
    if manifest.get("quantization"):
        summary["quantization"] = manifest["quantization"]["scheme"]
    if manifest.get("decode"):
        summary["decode_slots"] = [b["slots"] for b in
                                   manifest["decode"]["slots"]]
        summary["decode_window"] = manifest["decode"]["window"]
    print(json.dumps(summary))
    return 0


def _session_kwargs(args):
    """Session-tier knobs of ``cli serve --continuous``
    (docs/serving.md "Session tier & paging" knob table)."""
    kw = {
        "session_capacity": getattr(args, "session_store", 4096),
        "idle_spill_ms": getattr(args, "idle_spill_ms", None),
        "session_slo_grace_ms": getattr(args, "session_slo_ms", None),
        "session_ttl_ms": getattr(args, "session_ttl_ms", None),
    }
    addr = getattr(args, "session_store_addr", "") or ""
    if addr:
        # multi-host session tier: every scheduler on this host pages
        # against the SHARED store process instead of a private dict —
        # committed sessions then survive this host (serve/remote_store)
        from paddle_tpu.serve.remote_store import RemoteSessionStore

        kw["session_store"] = RemoteSessionStore(addr)
    return kw


def _make_engine(bundle, args, reg, model=None, warmup="async",
                 budget_share=None, steplog=None):
    from paddle_tpu.serve import ContinuousScheduler, InferenceEngine

    if args.continuous and not bundle.has_decoder():
        # refuse loudly: silently falling back to the padding
        # engine would leave the operator believing continuous
        # batching is active
        print("--continuous: bundle %r has no decode artifacts; "
              "re-export with --decode-slots" % bundle.name,
              file=sys.stderr)
        raise SystemExit(2)
    replicas = getattr(args, "replicas", "") or ""
    workers = getattr(args, "workers", "") or ""
    if workers and replicas:
        print("--workers (worker processes) and --replicas (in-process "
              "threads) are mutually exclusive: pick one data plane",
              file=sys.stderr)
        raise SystemExit(2)
    if workers:
        # multi-process data plane (docs/serving.md "Worker
        # processes"): each replica as its own OS worker process behind
        # the same duck-typed fleet front — the GIL-free path
        from paddle_tpu.core.place import host_tpu_chips
        from paddle_tpu.serve import WorkerSet
        from paddle_tpu.serve.fleet import auto_replicas

        # "auto" sizes like --replicas auto (one per device, or the
        # PADDLE_TPU_HBM_BUDGET fit), capped at the core count: worker
        # PROCESSES beyond the cores only add context-switch overhead.
        # On a TPU host it must size WITHOUT opening a device: this
        # parent only routes, and a parent that has touched JAX holds
        # the chips its workers need. There it is one worker, the only
        # width that can open the chips (WorkerSet refuses more)
        if workers != "auto":
            n = int(workers)
        elif host_tpu_chips():
            n = 1
        else:
            n = min(auto_replicas(bundle, budget=budget_share),
                    os.cpu_count() or 1)
        kwargs = (dict({"max_queue": args.max_queue_rows},
                       **_session_kwargs(args)) if args.continuous
                  else {"max_batch_size": args.max_batch_size,
                        "max_latency_ms": args.max_latency_ms,
                        "max_queue_rows": args.max_queue_rows})
        if kwargs.get("session_store") is not None:
            # a store CLIENT holds a live socket — it cannot cross the
            # worker-process spawn boundary; each worker would need its
            # own dial-up, which the worker protocol does not carry
            print("--session-store-addr cannot combine with --workers: "
                  "use --replicas or a single engine per host",
                  file=sys.stderr)
            raise SystemExit(2)
        return WorkerSet(bundle, workers=max(n, 1),
                         continuous=args.continuous,
                         engine_kwargs=kwargs, metrics_registry=reg,
                         model=model, respawn=args.respawn_workers)
    if replicas:
        # replica scaling (docs/serving.md "Replica scaling"): ONE
        # bundle onto N devices as N shared-nothing engines behind a
        # least-queued dispatch front, duck-typed like a single engine
        from paddle_tpu.serve import ReplicaSet
        from paddle_tpu.serve.fleet import auto_replicas

        # "auto" sizes the fleet from the HARDWARE (one per device) or,
        # under PADDLE_TPU_HBM_BUDGET, from the bundle's manifest HBM
        # estimate — a quantized bundle's smaller estimate admits more
        # replicas for the same budget (serve/fleet.py). A multi-model
        # host passes each model its SHARE of the budget so N auto
        # fleets cannot jointly overcommit the chip.
        n = (auto_replicas(bundle, budget=budget_share)
             if replicas == "auto" else int(replicas))
        kwargs = (dict({"max_queue": args.max_queue_rows},
                       **_session_kwargs(args)) if args.continuous
                  else {"max_batch_size": args.max_batch_size,
                        "max_latency_ms": args.max_latency_ms,
                        "max_queue_rows": args.max_queue_rows})
        return ReplicaSet(bundle, replicas=n,
                          continuous=args.continuous,
                          engine_kwargs=kwargs, metrics_registry=reg,
                          model=model, warmup=warmup)
    if args.continuous:
        return ContinuousScheduler(
            bundle, warmup=warmup, metrics_registry=reg, model=model,
            max_queue=args.max_queue_rows, steplog=steplog,
            **_session_kwargs(args))
    return InferenceEngine(
        bundle, max_batch_size=args.max_batch_size,
        max_latency_ms=args.max_latency_ms, warmup=warmup,
        metrics_registry=reg, model=model, steplog=steplog,
        max_queue_rows=args.max_queue_rows)


def _make_slo(fronts, args, model=None):
    """Burn-rate SLO monitor over the serving fronts
    (observe/health.py): always built so ``GET /debug/slo`` answers;
    the periodic evaluation thread (and its ``slo_status`` steplog
    stream) only starts when an objective was actually declared via
    ``--slo-p99-ms`` / ``--slo-availability``."""
    from paddle_tpu.observe import health as observe_health
    from paddle_tpu.observe import metrics as observe_metrics
    from paddle_tpu.observe import steplog

    slo = observe_health.SloMonitor(
        fronts, p99_ms=args.slo_p99_ms,
        availability=args.slo_availability,
        registry=observe_metrics.get_registry(),
        slog=steplog.from_env("slo", meta={"phase": "slo"}),
        model=model)
    if slo.active:
        slo.start()
    return slo


def _make_controller(slo, fronts, args, model=None):
    """The actuation half of the SLO loop (docs/control.md): with
    ``--autotune``, collect every knob the serving fronts register —
    the router's shed ceilings, the fleet/worker-set width and its
    members' broadcast knobs, a single engine's deadline and queue
    bound — and start the named controller thread over them. Needs a
    declared objective: a controller with nothing to steer toward
    would never act, so silently 'enabling' it would be a lie."""
    if not getattr(args, "autotune", False):
        return None
    if not slo.active:
        print("--autotune needs a declared objective: add --slo-p99-ms "
              "(and optionally --slo-availability)", file=sys.stderr)
        raise SystemExit(2)
    from paddle_tpu.control import Controller, KnobRegistry
    from paddle_tpu.observe import metrics as observe_metrics
    from paddle_tpu.observe import steplog

    knobs = KnobRegistry()
    for front in fronts:
        if not hasattr(front, "register_knobs"):
            continue
        try:
            front.register_knobs(knobs)
        except ValueError:
            # multi-model routers host N engines that would all claim
            # engine.*: the first registrant keeps the name, later
            # models stay hand-tuned (name a dedicated deployment to
            # autotune a specific model)
            pass
    controller = Controller(
        slo, knobs, registry=observe_metrics.get_registry(),
        slog=steplog.from_env("control", meta={"phase": "control"}),
        model=model)
    controller.start()
    return controller


def cmd_serve(args):
    """Serve exported bundles behind the serving tier. Single-model:
    ``cli serve <bundle>`` (the PR 3 surface, plus ``--continuous`` for
    decode-capable bundles). Multi-model: repeat ``--model
    NAME=DIR[:PRIORITY]`` to host N bundles behind the router —
    per-model queues, priority admission control, 429 load shedding,
    per-model ``/readyz``. ``--selfcheck`` loads the bundle, warms
    every bucket, pushes one batch through the engine and exits — the
    deployment smoke gate (tests/test_serve.py uses it the same way CI
    would)."""
    from paddle_tpu.observe import metrics as observe_metrics
    from paddle_tpu.serve import Router, load_bundle

    # SIGTERM (the production stop signal: kubernetes, systemd, a plain
    # `kill`) must take the SAME graceful path as Ctrl-C: the finally
    # blocks below stop the engines, which flush/close their steplogs —
    # without this, a terminated server silently drops up to
    # flush_every-1 batched serving records (the default handler exits
    # without running finally OR atexit)
    import signal

    def _graceful_term(signum, frame):
        # one-shot: a SECOND SIGTERM during the (possibly slow) drain
        # must not raise inside the finally block and abort the very
        # flush this handler exists to guarantee (force-kill remains
        # available via SIGKILL)
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        raise KeyboardInterrupt

    try:
        signal.signal(signal.SIGTERM, _graceful_term)
    except ValueError:
        pass  # not the main thread (embedded callers): keep the default

    join = getattr(args, "join", "") or ""
    if getattr(args, "front", False):
        # fleet-of-fleets front (docs/serving.md "Multi-host serving"):
        # no bundle, no device — membership from the coordinator's TTL
        # leases, a consistent-hash ring over the live hosts, session
        # affinity with rehome-on-lease-lapse
        if args.bundle or args.model or args.selfcheck:
            print("--front holds no engine: drop the positional "
                  "bundle / --model / --selfcheck", file=sys.stderr)
            return 2
        if not join:
            print("--front needs --join COORD:PORT to discover hosts",
                  file=sys.stderr)
            return 2
        from paddle_tpu.observe import steplog as observe_steplog
        from paddle_tpu.serve.cluster import (ClusterFront,
                                              make_front_server)

        slog = observe_steplog.from_env(
            "serve-front", meta={"phase": "serve_front"})
        front = ClusterFront(endpoint=join, steplog=slog,
                             rehome_retries=args.rehome_retries)
        server = make_front_server(front, host=args.host,
                                   port=args.port)
        print("serving front on http://%s:%d over coordinator %s "
              "(POST /infer; GET /healthz /readyz /hosts /stats "
              "/metrics)" % (*server.server_address, join))
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            server.shutdown()
            front.stop()
            if slog is not None:
                slog.close()
        return 0
    if join and args.model:
        print("--join serves ONE bundle per host: the cluster front "
              "routes bare POST /infer, not per-model paths",
              file=sys.stderr)
        return 2

    if args.model:
        if args.bundle or args.selfcheck:
            print("--model is multi-model mode: drop the positional "
                  "bundle / --selfcheck", file=sys.stderr)
            return 2
        from paddle_tpu.serve.server import make_router_server

        reg = observe_metrics.get_registry()
        router = Router(metrics_registry=reg)
        # N hosted models split one device-memory budget: each auto
        # fleet sizes against its share, not the whole budget
        budget_share = None
        if args.replicas == "auto" and len(args.model) > 1:
            from paddle_tpu.analyze.topology_check import hbm_budget_bytes

            budget = hbm_budget_bytes()
            if budget is not None:
                budget_share = budget // len(args.model)
        for spec in args.model:
            name, _, rest = spec.partition("=")
            if not rest:
                print("--model wants NAME=DIR[:PRIORITY], got %r" % spec,
                      file=sys.stderr)
                return 2
            directory, _, priority = rest.rpartition(":")
            if not directory:  # no priority suffix
                directory, priority = rest, "normal"
            bundle = load_bundle(directory)
            router.add_model(name, bundle,
                             _make_engine(bundle, args, reg, model=name,
                                          budget_share=budget_share),
                             priority=priority or "normal")
        slo = _make_slo([router.model(n).engine
                         for n in router.models()], args)
        controller = _make_controller(
            slo, [router] + [router.model(n).engine
                             for n in router.models()], args)
        server = make_router_server(router, host=args.host,
                                    port=args.port, slo=slo,
                                    controller=controller)
        print("serving %s on http://%s:%d (POST /infer/<model>; GET "
              "/healthz /readyz /metrics /stats /debug/slo%s "
              "/manifest/<model>)"
              % (sorted(router.models()), *server.server_address,
                 " /debug/control" if controller else ""))
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            server.shutdown()
            if controller is not None:
                controller.stop()
            slo.stop(close_slog=True)
            router.stop()
        return 0
    if not args.bundle:
        print("serve needs a bundle directory or --model entries",
              file=sys.stderr)
        return 2
    bundle = load_bundle(args.bundle)
    host_slog = None
    host_id = ""
    if join and not args.selfcheck:
        import socket as _socket

        from paddle_tpu.observe import steplog as observe_steplog

        # one steplog per HOST, run-named "<run>@<host_id>" with the
        # host in the meta line: cli observe merges the per-host files
        # back into one cross-host timeline keyed on that suffix
        host_id = (getattr(args, "host_id", "") or
                   "%s-%d" % (_socket.gethostname(), os.getpid()))
        host_slog = observe_steplog.from_env(
            "serve@%s" % host_id,
            meta={"phase": "serve", "host": host_id})
    # serving path: warm asynchronously so the HTTP endpoints bind
    # immediately and the readiness probe (/healthz, /readyz) honestly
    # reports ready=false until every bucket is warm; selfcheck warms
    # synchronously — it IS the warmth gate
    engine = _make_engine(bundle, args, observe_metrics.get_registry(),
                          warmup=(True if args.selfcheck else "async"),
                          steplog=host_slog)
    if args.selfcheck:
        try:
            if hasattr(engine, "wait_ready"):
                # worker fleet: warmup runs inside the child processes;
                # the smoke gate waits for every worker to report warm
                engine.wait_ready(timeout=300.0)
            out = engine.infer(bundle.dummy_inputs(rows=1), timeout=300.0)
            print(json.dumps({
                "ok": True, "bundle": bundle.name,
                "buckets": bundle.batch_sizes(),
                "outputs": {k: list(v.shape) for k, v in out.items()},
                "stats": {k: v for k, v in engine.stats().items()
                          if isinstance(v, int)}}))
            return 0
        finally:
            engine.stop()
    import contextlib

    from paddle_tpu.serve.server import make_server

    slo = _make_slo([engine], args, model=bundle.name)
    controller = _make_controller(slo, [engine], args, model=bundle.name)
    heartbeat = None
    with contextlib.ExitStack() as stack:
        # process-wide compile counter behind GET /debug/compiles: a
        # client diffs it across a window to prove serving compiled
        # nothing after warm-up (chip_smoke.py; the hosts-ab bench does
        # the same across its chaos window)
        from paddle_tpu.observe import steplog as observe_steplog

        watcher = stack.enter_context(observe_steplog.watch_compiles())
        server = make_server(bundle, engine, host=args.host,
                             port=args.port, slo=slo,
                             controller=controller,
                             compiles_fn=lambda: watcher.compiles)
        if join:
            from paddle_tpu.distributed.client import encode_host_meta
            from paddle_tpu.distributed.elastic import HeartbeatThread

            # start the lease only AFTER the server bound: the address
            # announced through the lease meta must already answer —
            # the front dials it the moment the host appears
            heartbeat = HeartbeatThread(
                join, worker_id=host_id, ttl=args.lease_ttl,
                steplog=host_slog,
                meta=encode_host_meta(
                    kind="serve",
                    addr="%s:%d" % server.server_address))
            heartbeat.start()
        print("serving %r on http://%s:%d (POST /infer; GET /healthz "
              "/readyz /metrics /stats /debug/slo%s /manifest)%s"
              % (bundle.name, *server.server_address,
                 " /debug/control" if controller else "",
                 (" joined %s as %r" % (join, host_id)) if join else ""))
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            server.shutdown()
            if heartbeat is not None:
                heartbeat.stop()
            if controller is not None:
                controller.stop()
            slo.stop(close_slog=True)
            engine.stop()
            if host_slog is not None:
                host_slog.close()
    return 0


def cmd_generate(args):
    """Streaming generation over a decode-capable bundle
    (docs/serving.md "Streaming generation"): loop the exported decode
    step host-side, feed each sampled y_t back as x_{t+1}. Greedy at
    --temperature 0 (default), seeded sampling otherwise."""
    from paddle_tpu.serve import load_bundle
    from paddle_tpu.serve.generate import generate

    bundle = load_bundle(args.bundle)
    prime = [int(t) for t in args.prime.split(",") if t.strip()]
    out = generate(bundle, prime, args.steps,
                   temperature=args.temperature, seed=args.seed,
                   slots=args.slots)
    print(json.dumps(out))
    return 0


def cmd_observe(args):
    """Summarize a PADDLE_TPU_TELEMETRY directory: per-run step counts,
    steady-state wall-time p50/p95/p99, compile-event totals, and the
    trace files to open in Perfetto (docs/observability.md). With
    ``--regress <baseline.json>`` the ``bench_row`` records mirrored
    into the directory are gated against the audited baseline
    (observe/regress.py) and a gated regression exits non-zero — the CI
    one-liner."""
    from paddle_tpu.observe import steplog

    summary = steplog.summarize_dir(args.directory)
    rc = 0
    regress_results = None
    if args.regress:
        import glob as _glob

        from paddle_tpu.observe import regress as observe_regress

        rows = []
        for path in sorted(_glob.glob(
                os.path.join(args.directory, "*.steps.jsonl"))):
            rows.extend(r for r in steplog.read_jsonl(path)
                        if r.get("type") == "bench_row")
        results, regressions = observe_regress.gate_rows(
            rows, baseline_paths=[args.regress],
            base_tol_pct=args.regress_tol)
        regress_results = results
        if regressions:
            rc = 1
    if getattr(args, "fleet_stats", None):
        # live membership next to the post-hoc file view: the
        # coordinator's fleet_stats verb answers "who is alive RIGHT
        # NOW and how stale is each lease" (short retry window — an
        # observability query must not hang behind a dead coordinator)
        from paddle_tpu.distributed.client import CoordinatorClient

        client = CoordinatorClient(args.fleet_stats, worker_id="observe",
                                   retry_timeout=5.0)
        try:
            summary["fleet_stats"] = client.fleet_stats()
            # serving hosts (workers registered WITH lease meta) next
            # to the trainer leases: the same coordinator carries both
            summary["serve_hosts_live"] = client.serve_hosts()
        finally:
            client.close()
    if args.json:
        if regress_results is not None:
            summary["regress"] = regress_results
        print(json.dumps(summary, indent=2))
        return rc
    print("telemetry dir: %s" % summary["directory"])
    for run in summary["runs"]:
        print("  run %-12s schema=%s backend=%-5s steps=%-5d "
              "compile_events=%d (%.2fs)"
              % (run.get("run"), run.get("schema"), run.get("backend"),
                 run["steps"], run["compile_events"],
                 run["event_secs_total"]))
        if "wall_ms_steady_mean" in run:
            print("    wall ms/step: steady p50 %.3f  p95 %.3f  "
                  "p99 %.3f  mean %.3f  min %.3f  "
                  "(first-step mean incl. compile %.3f)"
                  % (run["wall_ms_p50"], run["wall_ms_p95"],
                     run["wall_ms_p99"], run["wall_ms_steady_mean"],
                     run["wall_ms_min"], run["wall_ms_mean"]))
        if "feed_stall_ms_p50" in run:
            waste = ("  padding waste %.1f%%"
                     % run["feed_padding_waste_pct"]
                     if "feed_padding_waste_pct" in run else "")
            print("    feed stall ms: p50 %.3f  p95 %.3f  "
                  "(%d pipelined batches)%s"
                  % (run["feed_stall_ms_p50"], run["feed_stall_ms_p95"],
                     run["feed_batches"], waste))
        if "checkpoints" in run:
            thread = (", step-thread p95 %.3f ms"
                      % run["checkpoint_step_thread_ms_p95"]
                      if "checkpoint_step_thread_ms_p95" in run else "")
            print("    checkpoints: %d  save p95 %.3f ms  %.1f KB total%s"
                  % (run["checkpoints"], run["checkpoint_ms_p95"],
                     run["checkpoint_bytes_total"] / 1024.0, thread))
        if "examples_per_sec_best" in run:
            print("    examples/sec best: %.1f"
                  % run["examples_per_sec_best"])
        if "cost_last" in run:
            print("    cost: first %.6f -> last %.6f"
                  % (run["cost_first"], run["cost_last"]))
        # a WorkerSet's per-worker steplog file carries the worker
        # index in its meta: label its lines "worker" so per-worker
        # qps/occupancy reads next to the in-process per-replica lines
        member = ("worker" if run.get("serve_worker") is not None
                  else "replica")
        for rep, s in sorted(run.get("serve_replicas", {}).items()):
            print("    serve %s %-4s dispatches %-6d "
                  "completed %-6d%s%s"
                  % (member, rep, s["dispatches"], s["completed"],
                     ("  qps %.1f" % s["qps"]) if "qps" in s else "",
                     ("  occupancy %.2f" % s["occupancy_mean"])
                     if "occupancy_mean" in s else ""))
            if "spills" in s or "resident_sessions" in s:
                # session tier: paging activity + where the sessions sit
                swaps = ("spills %d restores %d evictions %d"
                         % (s.get("spills", 0), s.get("restores", 0),
                            s.get("evictions", 0)))
                rate = ("  swap/s %.1f" % s["swap_per_s"]
                        if "swap_per_s" in s else "")
                counts = ""
                if "resident_sessions" in s or "suspended_sessions" in s:
                    counts = ("  sessions resident %d / suspended %d"
                              % (s.get("resident_sessions", 0),
                                 s.get("suspended_sessions", 0)))
                print("      session swaps: %s%s%s" % (swaps, rate, counts))
        if "serve_tail" in run:
            # tail attribution over the run's sampled serve_trace
            # records: the phase histogram of the p99 — "p99 is 80%
            # queue-wait" vs "80% spill-restore" in one line
            tail = run["serve_tail"]
            shares = "  ".join(
                "%s %.1f%%" % (k[:-len("_ms")] if k.endswith("_ms")
                               else k, v)
                for k, v in sorted(tail["phases"].items(),
                                   key=lambda kv: -kv[1]))
            print("    serve tail attribution (p%g >= %.1f ms, "
                  "%d of %d traced): %s"
                  % (tail["q"], tail["threshold_ms"],
                     tail["tail_requests"], tail["requests"], shares))
        if "control_actions" in run:
            # the knob-move timeline, next to the tail attribution the
            # moves were reacting to: what the controller did, in
            # order, with the burn it was fighting
            moves = run["control_actions"]
            print("    control timeline: %d knob move(s), %d rollback(s)"
                  % (len(moves), run.get("control_rollbacks", 0)))
            for a in moves:
                burn = ("  burn %.2f" % a["burn_rate_before"]
                        if "burn_rate_before" in a else "")
                phase = (" [%s]" % a["breaching_phase"]
                         if "breaching_phase" in a else "")
                print("      t=%-8.2f %-24s %g -> %g  %s%s%s"
                      % (a.get("t", 0.0), a["knob"], a["old"], a["new"],
                         a["reason"], phase, burn))
    for fleet in summary.get("fleets", ()):
        # fleet-merged tail attribution across a WorkerSet's per-worker
        # steplog files: the per-file p99 above is each worker's OWN
        # tail — this is the fleet's, pooled before the percentile
        tail = fleet["serve_tail"]
        shares = "  ".join(
            "%s %.1f%%" % (k[:-len("_ms")] if k.endswith("_ms") else k,
                           v)
            for k, v in sorted(tail["phases"].items(),
                               key=lambda kv: -kv[1]))
        print("  fleet %s merged tail attribution (p%g >= %.1f ms, "
              "%d of %d traced across %d workers): %s"
              % (fleet["run"], tail["q"], tail["threshold_ms"],
                 tail["tail_requests"], tail["requests"],
                 len(fleet["workers"]), shares))
        breakdown = "  ".join(
            "w%s p99 %s (%d traced)"
            % (widx, ("%.1f ms" % w["p99_ms"]) if "p99_ms" in w
               else "n/a", w["traces"])
            for widx, w in sorted(fleet["workers"].items(),
                                  key=lambda kv: int(kv[0])))
        print("    per-worker: %s" % breakdown)
    for cluster in summary.get("serve_clusters", ()):
        # cluster-merged tail attribution across per-HOST steplog files
        # (run names "<run>@<host>"): each host's own p99 is blind to
        # the cluster's true tail — pool before the percentile
        tail = cluster["serve_tail"]
        shares = "  ".join(
            "%s %.1f%%" % (k[:-len("_ms")] if k.endswith("_ms") else k,
                           v)
            for k, v in sorted(tail["phases"].items(),
                               key=lambda kv: -kv[1]))
        print("  cluster %s merged tail attribution (p%g >= %.1f ms, "
              "%d of %d traced across %d hosts): %s"
              % (cluster["run"], tail["q"], tail["threshold_ms"],
                 tail["tail_requests"], tail["requests"],
                 len(cluster["hosts"]), shares))
        breakdown = "  ".join(
            "%s p99 %s (%d traced)"
            % (hid, ("%.1f ms" % h["p99_ms"]) if "p99_ms" in h
               else "n/a", h["traces"])
            for hid, h in sorted(cluster["hosts"].items()))
        print("    per-host: %s" % breakdown)
    sh = summary.get("serve_hosts")
    if sh:
        # the serving-host membership timeline — the serving twin of
        # the elastic timeline below, on the same absolute time axis
        print("  serving hosts timeline: %d event(s), %d session "
              "rehome(s)" % (len(sh["events"]), sh["rehomes"]))
        for e in sh["events"]:
            extras = []
            if e.get("hosts") is not None:
                extras.append("hosts=[%s]" % ",".join(e["hosts"]))
            if e.get("session"):
                extras.append("session=%s" % e["session"])
            if e.get("target"):
                extras.append("target=%s" % e["target"])
            if e.get("detail"):
                extras.append("(%s)" % e["detail"])
            print("    at=%.3f %-16s host=%-16s %s"
                  % (e["t_abs"], e["kind"], e.get("host", "-"),
                     "  ".join(extras)))
    tf = summary.get("train_fleet")
    if tf:
        # the training-fleet block (observe/trainview.py): per-worker
        # step-time skew against the fleet-pooled median, the straggler
        # verdict, and the merged elastic timeline
        skew = tf.get("skew")
        if skew:
            straggler = tf.get("straggler")
            rewinds = ("  rewinds %d" % tf["rewinds"]
                       if tf.get("rewinds") else "")
            print("  training fleet: %d worker(s), fleet median "
                  "%.3f ms/step%s"
                  % (len(skew["workers"]), skew["fleet_median_ms"],
                     rewinds))
            for wid, w in sorted(skew["workers"].items()):
                mark = (" <- straggler" if straggler
                        and straggler["worker"] == wid else "")
                print("    worker %-12s steps %-5d p50 %.3f ms  "
                      "p95 %.3f ms  skew %.2f%s"
                      % (wid, w.get("steps", 0), w["p50_ms"],
                         w["p95_ms"], w["skew"], mark))
            if straggler:
                from paddle_tpu.observe.trainview import (
                    DEFAULT_SKEW_THRESHOLD)

                print("    straggler: %s (skew %.2f >= %.2f)"
                      % (straggler["worker"], straggler["skew"],
                         DEFAULT_SKEW_THRESHOLD))
        timeline = tf.get("timeline")
        if timeline:
            print("  elastic timeline: %d event(s)" % len(timeline))
            for e in timeline:
                extras = []
                if e.get("members") is not None:
                    extras.append("members=[%s]"
                                  % ",".join(e["members"]))
                if e.get("lost") is not None:
                    extras.append("lost=[%s]" % ",".join(e["lost"]))
                if e.get("checkpoint"):
                    extras.append("checkpoint=%s" % e["checkpoint"])
                if e.get("step") is not None:
                    extras.append("step=%d" % e["step"])
                if e.get("detail"):
                    extras.append("(%s)" % e["detail"])
                print("    at=%.3f %-18s worker=%-12s %s"
                      % (e["at"], e["kind"], e.get("worker", "-"),
                         "  ".join(extras)))
    stats = summary.get("fleet_stats")
    if stats:
        ws = stats.get("workers", [])
        print("  live fleet (%s): %d worker(s)"
              % (args.fleet_stats, len(ws)))
        for w in ws:
            print("    %-12s lease remaining %.1fs"
                  % (w["id"], w["lease_remaining"]))
        hosts = summary.get("serve_hosts_live", {}).get("hosts", [])
        if hosts:
            print("  serving hosts: %d" % len(hosts))
            for h in hosts:
                print("    %-12s lease remaining %.1fs  %s"
                      % (h["id"], h["lease_remaining"],
                         h.get("meta", "")))
    if summary["trace_files"]:
        print("  traces (open in https://ui.perfetto.dev): %s"
              % ", ".join(summary["trace_files"]))
    if not summary["runs"]:
        print("  no *.steps.jsonl runs found")
    if regress_results is not None:
        from paddle_tpu.observe.regress import format_result

        gated = [r for r in regress_results
                 if r["status"] == "regression"]
        print("  regression gate vs %s: %d row(s) checked, %d gated"
              % (args.regress, len(regress_results), len(gated)))
        for r in regress_results:
            if r["status"] in ("regression", "ok"):
                print("    " + format_result(r))
    return rc


def cmd_analyze(args):
    """Framework-aware static analysis (docs/analyze.md).

    Default/``--all``: lint the paddle_tpu source tree (host syncs in
    hot paths, jit-cache busters, unmanaged threads, unlocked
    registries — checker catalog in paddle_tpu/analyze/lint.py) AND
    verify the derived reject_packed coverage; exits non-zero on any
    finding — the second CI one-liner, next to ``cli observe
    --regress``. With ``--topology --config cfg.py``: build the
    config's topology and run the pre-compile graph checks plus the
    jit-entry-shape prediction for its reader/buckets/steps-per-call
    combination (no tracing, no device)."""
    from paddle_tpu.analyze import lint, topology_check

    if args.topology:
        if not args.config:
            print("analyze --topology needs --config", file=sys.stderr)
            return 2
        from paddle_tpu import minibatch
        from paddle_tpu.graph import reset_name_counters
        from paddle_tpu.parameters import Parameters
        from paddle_tpu.topology import Topology

        reset_name_counters()
        cfg = _load_config(args.config, getattr(args, "config_args", ""))
        cost = cfg.cost()
        params = Parameters.create(cost)
        topo = Topology(cost)
        report = topology_check.check_topology(
            topo, parameters=params,
            steps_per_call=args.steps_per_call or None)
        optimizer = (cfg.optimizer()
                     if hasattr(cfg, "optimizer") else None)
        report["hbm"] = topology_check.estimate_hbm_bytes(
            topo, parameters=params, optimizer=optimizer)
        buckets = ([int(b) for b in args.buckets.split(",") if b]
                   if args.buckets else None)
        if hasattr(cfg, "train_reader"):
            batch_size = getattr(cfg, "batch_size", args.batch_size)
            reader = minibatch.batch(cfg.train_reader(), batch_size)
            if args.sample_batches:
                import itertools

                base = reader
                reader = lambda: itertools.islice(  # noqa: E731
                    base(), args.sample_batches)
            report["jit_entries"] = topology_check.predict_jit_entries(
                topo, reader, buckets=buckets,
                steps_per_call=args.steps_per_call or None,
                parameters=params, optimizer=optimizer)
        if args.format == "json":
            print(json.dumps(report, indent=2))
        else:
            print(topology_check.format_report(report))
            if "jit_entries" in report:
                je = report["jit_entries"]
                print("jit entries: %d program(s), est. hbm peak %s"
                      % (je["programs"],
                         topology_check._fmt_bytes(je["hbm_peak_bytes"])))
                for e in je["entries"]:
                    print("  %(kind)s rows=%(rows)d" % e
                          + (" steps=%d" % e["steps"]
                             if e["kind"] == "scan" else "")
                          + (" pad=%s" % e["seq_pad"]
                             if e["seq_pad"] else "")
                          + " hbm=%s" % topology_check._fmt_bytes(
                              e["hbm"]["total"]))
        return 1 if report["errors"] else 0

    if args.paths:
        findings = lint.lint_paths(args.paths)
        n_files = len(args.paths)
    else:
        findings, n_files = lint.lint_tree()
    coverage = topology_check.verify_reject_packed_coverage()
    rc = 1 if (findings or coverage["missing"]) else 0
    if args.format == "json":
        # machine-readable findings (file/line/id/message/fixit, stable
        # ordering) — the CI PR-annotation surface; exit code unchanged
        # no sort_keys: each finding record keeps the documented
        # file/line/id/title/message/fixit order; finding ORDER is
        # already stabilized by the (file, line, id) sort in lint
        print(json.dumps({
            "files": n_files,
            "checkers": sorted(lint.CHECKERS),
            "findings": [f.as_dict() for f in findings],
            "reject_packed": coverage}, indent=2))
        return rc
    for f in findings:
        print(lint.format_finding(f))
    for name in coverage["missing"]:
        print("reject_packed coverage gap: layer %r mixes across time "
              "positions but accepts packed input (derived set: %s)"
              % (name, coverage["expected"]))
    if rc == 0:
        print("analyze clean: %d files, %d checkers, reject_packed "
              "coverage %d/%d layers"
              % (n_files, len(lint.CHECKERS),
                 len(coverage["covered"]), len(coverage["expected"])))
    else:
        print("analyze: %d finding(s)" % (len(findings)
                                          + len(coverage["missing"])))
    return rc


def main(argv=None):
    parser = argparse.ArgumentParser(prog="paddle_tpu",
                                     description="paddle_tpu launcher")
    sub = parser.add_subparsers(dest="job", required=True)

    common = argparse.ArgumentParser(add_help=False)
    common.add_argument("--config", required=True)
    common.add_argument("--config-args", default="",
                        help="k=v,... template parameters readable via "
                             "paddle_tpu.config.get_config_arg")
    common.add_argument("--batch-size", type=int, default=64)
    common.add_argument("--use-tpu", action="store_true", default=None)
    common.add_argument("--trainer-count", type=int, default=None,
                        help="data-parallel width over visible devices "
                             "(reference --trainer_count)")

    p = sub.add_parser("train", parents=[common])
    p.add_argument("--num-passes", type=int, default=1)
    p.add_argument("--save-dir", default="")
    p.add_argument("--init-model", default="")
    p.add_argument("--feed-pipeline", type=int, default=0,
                   help="pipelined input feed depth (paddle_tpu.data, "
                        "docs/data.md); 0 = synchronous feed")
    p.add_argument("--steps-per-call", type=int, default=0,
                   help="fuse K optimizer steps per dispatch (lax.scan "
                        "with donated carries, docs/data.md); implies "
                        "the pipelined feed; 0 = one dispatch per step")
    p.add_argument("--checkpoint-dir", default="",
                   help="durable full-training-state checkpoints "
                        "(parameters + optimizer slots + rng + reader "
                        "cursor; docs/distributed.md)")
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="checkpoint cadence in global steps; saves are "
                        "OVERLAPPED (async ckpt-writer thread) unless "
                        "--checkpoint-sync; 0 = off")
    p.add_argument("--checkpoint-keep", type=int, default=3,
                   help="checkpoints retained (older ones pruned)")
    p.add_argument("--checkpoint-sync", action="store_true",
                   help="block the step thread for each save (the A/B "
                        "contrast; benchmark/exp_checkpoint.py)")
    p.add_argument("--resume", action="store_true",
                   help="restore the newest valid checkpoint in "
                        "--checkpoint-dir and continue the IDENTICAL "
                        "fixed-seed trajectory (reader position, rng and "
                        "optimizer slots included)")
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("test", parents=[common])
    p.add_argument("--params", default="")
    p.set_defaults(fn=cmd_test)

    p = sub.add_parser("time", parents=[common])
    p.add_argument("--iters", type=int, default=20)
    p.set_defaults(fn=cmd_time)

    p = sub.add_parser("checkgrad", parents=[common])
    p.set_defaults(fn=cmd_checkgrad)

    p = sub.add_parser("cluster_train", parents=[common])
    p.add_argument("--num-processes", type=int, required=True,
                   help="worker processes (1 per host slot)")
    p.add_argument("--num-passes", type=int, default=1)
    p.add_argument("--devices-per-process", type=int, default=None,
                   help="virtual CPU devices per worker (testing)")
    p.set_defaults(fn=cmd_cluster_train)

    p = sub.add_parser("observe")
    p.add_argument("directory",
                   help="telemetry directory (PADDLE_TPU_TELEMETRY)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable summary")
    p.add_argument("--regress", default="",
                   help="audited baseline JSON (a BENCH_*.json driver "
                        "record or a bench-row lines file); gates the "
                        "dir's bench_row records and exits non-zero on "
                        "a gated regression (observe/regress.py)")
    p.add_argument("--regress-tol", type=float, default=10.0,
                   help="base tolerance %% before the row's own "
                        "spread_pct widens it")
    p.add_argument("--fleet-stats", default="", metavar="HOST:PORT",
                   help="also query the task coordinator's fleet_stats "
                        "verb: live training-fleet membership + per-"
                        "lease time-to-expiry next to the file view")
    p.set_defaults(fn=cmd_observe)

    p = sub.add_parser("analyze")
    p.add_argument("paths", nargs="*",
                   help="explicit files to lint (default: the installed "
                        "paddle_tpu tree)")
    p.add_argument("--all", action="store_true",
                   help="full static-analysis gate (lint + reject_packed "
                        "coverage; the default behavior, spelled out for "
                        "the CI one-liner)")
    p.add_argument("--topology", action="store_true",
                   help="pre-compile topology checks + jit-entry-shape "
                        "prediction for --config")
    p.add_argument("--config", default="")
    p.add_argument("--config-args", default="")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--buckets", default="",
                   help="comma-separated bucket boundaries for the "
                        "jit-entry prediction")
    p.add_argument("--steps-per-call", type=int, default=0)
    p.add_argument("--sample-batches", type=int, default=64,
                   help="how many reader batches the jit-entry "
                        "prediction simulates")
    p.add_argument("--format", choices=("text", "json"), default="text",
                   help="json = machine-readable findings (file/line/id/"
                        "message/fixit, stable ordering) for CI PR "
                        "annotation")
    p.add_argument("--json", dest="format", action="store_const",
                   const="json", help="alias for --format=json")
    p.set_defaults(fn=cmd_analyze)

    p = sub.add_parser("merge_model")
    p.add_argument("--config", default="")
    p.add_argument("--builder", default="")
    p.add_argument("--params", required=True)
    p.add_argument("-o", "--output", required=True)
    p.set_defaults(fn=cmd_merge_model)

    # export/serve take the same device demand as the training jobs:
    # fail instead of exporting or serving on another backend
    use_tpu = argparse.ArgumentParser(add_help=False)
    use_tpu.add_argument("--use-tpu", action="store_true", default=None,
                         help="demand a TPU: an error names what "
                              "jax.devices() returned when there is none")
    p = sub.add_parser("export", parents=[use_tpu])
    p.add_argument("--config", default="")
    p.add_argument("--builder", default="")
    p.add_argument("--config-args", default="")
    p.add_argument("--params", required=True,
                   help="parameter tar (trainer save_parameter_to_tar)")
    p.add_argument("-o", "--output", required=True,
                   help="bundle directory to write")
    p.add_argument("--batch-sizes", default="1,8,32",
                   help="comma-separated exported batch buckets")
    p.add_argument("--seq-len", type=int, default=None,
                   help="padded time dim for sequence inputs")
    p.add_argument("--name", default="")
    p.add_argument("--platforms", default="",
                   help="comma-separated lowering platforms (e.g. cpu,tpu)")
    p.add_argument("--decode-slots", default="",
                   help="comma-separated slot capacities: additionally "
                        "export continuous-batching decode steps "
                        "(streamable recurrent topologies only)")
    p.add_argument("--decode-window", type=int, default=None,
                   help="decode timesteps per dispatch (default 8)")
    p.add_argument("--quantize", default="", choices=("", "int8"),
                   help="weight-only quantization: int8 stores matmul/"
                        "conv weights per-output-channel symmetric int8 "
                        "with f32 scale sidecars (biases/norm/embedding "
                        "tables stay fp; dequant fuses into the exported "
                        "dot) — ~4x smaller bundle, proportionally more "
                        "--replicas auto under PADDLE_TPU_HBM_BUDGET")
    p.set_defaults(fn=cmd_export)

    p = sub.add_parser("generate")
    p.add_argument("bundle",
                   help="decode-capable bundle directory "
                        "(exported with --decode-slots)")
    p.add_argument("--prime", required=True,
                   help="comma-separated token ids to prime the carry "
                        "with (e.g. 5,17,3)")
    p.add_argument("--steps", type=int, default=32,
                   help="tokens to generate after the prime")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy argmax; >0 samples from the "
                        "temperature-scaled distribution")
    p.add_argument("--seed", type=int, default=0,
                   help="sampling seed (reproducible output)")
    p.add_argument("--slots", type=int, default=None,
                   help="decode artifact to use (default: largest "
                        "exported slot capacity)")
    p.set_defaults(fn=cmd_generate)

    p = sub.add_parser("serve", parents=[use_tpu])
    p.add_argument("bundle", nargs="?", default="",
                   help="exported bundle directory (single-model mode)")
    p.add_argument("--model", action="append", default=[],
                   metavar="NAME=DIR[:PRIORITY]",
                   help="host NAME from bundle DIR with an optional "
                        "priority class (high/normal/low); repeat for "
                        "multi-model serving behind the router "
                        "(POST /infer/<name>, per-model /readyz)")
    p.add_argument("--continuous", action="store_true",
                   help="front decode-capable bundles with the "
                        "continuous-batching scheduler instead of the "
                        "whole-request batcher")
    p.add_argument("--replicas", default="",
                   help="N|auto: load each bundle onto N devices as N "
                        "shared-nothing engine replicas behind one "
                        "least-queued dispatch front (auto = one per "
                        "visible device, or — under PADDLE_TPU_HBM_"
                        "BUDGET — as many as the bundle's manifest HBM "
                        "estimate fits, so quantized bundles admit "
                        "more); /metrics gains {replica=} labels, "
                        "/readyz is all-replicas-warm")
    p.add_argument("--workers", default="",
                   help="N|auto: run each replica as its own OS worker "
                        "process behind the fleet front (GIL-free data "
                        "plane; mutually exclusive with --replicas). "
                        "Rows cross process boundaries over a shared-"
                        "memory ring; auto sizes like --replicas auto "
                        "capped at the host core count; workers write "
                        "<run>-w<i>.steps.jsonl steplogs and /metrics "
                        "merges worker snapshots under {worker=} labels")
    p.add_argument("--respawn-workers", action="store_true",
                   help="--workers: restart a dead worker process in "
                        "place (crash-only serving; sessions re-home "
                        "from their last committed carry backup)")
    p.add_argument("--selfcheck", action="store_true",
                   help="load, warm, run one batch, exit (smoke gate)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8866)
    p.add_argument("--max-batch-size", type=int, default=None)
    p.add_argument("--max-latency-ms", type=float, default=5.0)
    p.add_argument("--max-queue-rows", type=int, default=None,
                   help="bound each hosted queue; a full queue answers "
                        "429 instead of queueing (load shedding)")
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   help="declare a p99 latency objective: the burn-"
                        "rate SLO monitor evaluates the fleet-merged "
                        "health history against it (GET /debug/slo, "
                        "paddle_tpu_slo_* gauges, slo_status steplog "
                        "records on state transitions)")
    p.add_argument("--slo-availability", type=float, default=None,
                   help="availability objective in percent (default "
                        "99.0 when --slo-p99-ms is set): shed or over-"
                        "objective requests burn the 1-PCT/100 error "
                        "budget")
    p.add_argument("--autotune", action="store_true",
                   help="close the SLO loop (docs/control.md): a named "
                        "controller thread maps breaching-phase burn-"
                        "rate verdicts onto the registered serving "
                        "knobs (deadlines, queue/shed ceilings, spill "
                        "thresholds, fleet width) with hysteresis, "
                        "cooldowns, and a rollback guard; every move "
                        "is a control_action steplog record, a paddle_"
                        "tpu_control_* metric, and a GET /debug/"
                        "control entry. Needs --slo-p99-ms")
    p.add_argument("--session-store", type=int, default=4096,
                   help="session tier (--continuous): host-store "
                        "capacity in suspended sessions — live "
                        "sessions page above decode_slots instead of "
                        "429ing; an evicted session answers 410 Gone "
                        "(docs/serving.md 'Session tier & paging')")
    p.add_argument("--idle-spill-ms", type=float, default=None,
                   help="session tier: spill a parked session's carry "
                        "to the host store after this much idle time "
                        "(default: spill only under slot pressure)")
    p.add_argument("--session-slo-ms", type=float, default=None,
                   help="session tier: eviction passes over sessions "
                        "touched within this SLO grace window while "
                        "any other candidate exists")
    p.add_argument("--session-ttl-ms", type=float, default=None,
                   help="session tier: evict suspended sessions idle "
                        "past this TTL (reason=ttl)")
    p.add_argument("--join", default="", metavar="COORD:PORT",
                   help="multi-host serving (docs/serving.md 'Multi-"
                        "host serving'): register this host with the "
                        "coordinator under a TTL heartbeat lease and "
                        "publish its dial address through the lease "
                        "meta; a front started with --front routes to "
                        "it while the lease holds")
    p.add_argument("--host-id", default="",
                   help="--join: stable host identity on the hash "
                        "ring (default hostname-pid); keep it stable "
                        "across restarts so a rejoining host reclaims "
                        "its ring arcs")
    p.add_argument("--lease-ttl", type=float, default=10.0,
                   help="--join: coordinator lease TTL in seconds — "
                        "the failure-detection horizon; a host silent "
                        "this long is excluded from routing")
    p.add_argument("--session-store-addr", default="",
                   metavar="HOST:PORT",
                   help="--continuous: back the session tier with the "
                        "standalone remote store process (python -m "
                        "paddle_tpu.serve.remote_store) instead of a "
                        "process-local store, so committed sessions "
                        "survive host death and re-home bitwise")
    p.add_argument("--front", action="store_true",
                   help="run the fleet-of-fleets front instead of an "
                        "engine: no bundle, no device — only sockets, "
                        "the consistent-hash ring over the hosts "
                        "joined via --join's coordinator, and routing "
                        "state (session affinity, rehome on lease "
                        "lapse, shed reason no_host)")
    p.add_argument("--rehome-retries", type=int, default=2,
                   help="--front: extra hosts tried after the ring "
                        "home fails before the request errors out")
    p.set_defaults(fn=cmd_serve)

    args = parser.parse_args(argv)
    from paddle_tpu.utils import compile_cache

    compile_cache.enable()  # before this process's first compile
    if getattr(args, "use_tpu", None) is not None \
            and args.fn is not cmd_cluster_train:
        # the cluster launcher must NOT touch jax in the parent: device
        # enumeration would lock the TPU runtime the workers need
        import paddle_tpu as paddle

        paddle.init(use_tpu=args.use_tpu)
    if getattr(args, "trainer_count", None):
        from paddle_tpu.utils import flags

        flags.set_flag("trainer_count", args.trainer_count)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
