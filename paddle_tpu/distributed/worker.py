"""Cluster worker: one jax.distributed participant of a multi-host data-
parallel training job.

Reference roles: the per-host trainer process the cluster launcher started
(paddle/scripts/cluster_train/paddle.py job_trainer :130 — each host ran
`paddle train` with trainer_id/num_gradient_servers set). Here a worker:

1. joins the process group (distributed/multihost.py -> jax.distributed),
2. builds the user config's topology and a DataParallel plan over the
   GLOBAL mesh (all devices of all processes) — gradients psum over
   ICI/DCN with no parameter server,
3. runs the standard SGD loop; every process feeds the identical batch
   stream (same reader seed) and jax.device_put shards it onto the global
   'data' axis, each process materializing only its local shard,
4. prints per-pass costs + a final RESULT line the launcher collects.

Run via `python -m paddle_tpu.distributed.worker ...` (the launcher does).
"""

import argparse
import json
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(prog="paddle_tpu.distributed.worker")
    ap.add_argument("--config", required=True)
    ap.add_argument("--config-args", default="")
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--coordinator", required=True,
                    help="host:port of the jax.distributed coordinator")
    ap.add_argument("--num-passes", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--use-tpu", action="store_true", default=False)
    ap.add_argument("--feed-pipeline", type=int, default=0,
                    help="pipelined input feed depth (paddle_tpu.data): "
                         "batches convert and jax.device_put onto the "
                         "GLOBAL data-parallel mesh on a background "
                         "thread, ahead of the step; 0 = synchronous")
    ap.add_argument("--steps-per-call", type=int, default=0,
                    help="fuse K optimizer steps per dispatch (one "
                         "lax.scan over K mesh-sharded feeds with "
                         "donated carries — composes with the "
                         "DataParallel global-mesh plan; 0 = one "
                         "dispatch per step)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="durable training-state checkpoints "
                         "(distributed/checkpoint.py async overlapped "
                         "writer; docs/distributed.md)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint cadence in global steps (0 = off)")
    ap.add_argument("--resume", nargs="?", const="exact", default=None,
                    choices=["exact", "pass"],
                    help="restore the newest valid checkpoint. Bare "
                         "--resume (= 'exact') continues the identical "
                         "fixed-seed trajectory at the saved batch "
                         "cursor — right when the SAME worker set "
                         "relaunches (a preempted VM came back). "
                         "'--resume pass' restarts the interrupted pass "
                         "from its first batch — required when the "
                         "surviving group is SMALLER, because the "
                         "re-sharded data stream no longer matches the "
                         "old cursor (docs/distributed.md)")
    ap.add_argument("--task-coordinator", default="",
                    help="host:port of the task coordinator "
                         "(distributed/client.py): this worker registers "
                         "a TTL membership lease and renews it from the "
                         "coord-heartbeat thread, so survivors (and the "
                         "launcher) detect its death by lease lapse")
    ap.add_argument("--lease-ttl", type=float, default=10.0)
    args = ap.parse_args(argv)

    # training-fleet identity (observe/trainview.py): stamp this
    # process's worker id before any telemetry opens, so the trainer's
    # steplog meta/file name, the sentinel's crash records and the
    # metric labels all name it — overwrite, the launcher's choice wins
    os.environ["PADDLE_TPU_TRAIN_WORKER"] = "trainer-%d" % args.process_id

    from paddle_tpu.distributed.multihost import initialize_multihost
    from paddle_tpu.utils import compile_cache

    compile_cache.enable()  # before this process's first compile

    ok = initialize_multihost(coordinator_address=args.coordinator,
                              num_processes=args.num_processes,
                              process_id=args.process_id)
    assert ok, "jax.distributed initialization failed"

    if args.use_tpu:
        # after jax.distributed: init enumerates devices, which must not
        # open the backend before the process group exists
        import paddle_tpu as paddle

        paddle.init(use_tpu=True)

    import jax

    from paddle_tpu import minibatch
    from paddle_tpu.cli import _build, _load_config
    from paddle_tpu.parallel.mesh import DataParallel, build_mesh

    cfg = _load_config(args.config, args.config_args)
    # the GLOBAL mesh: every process contributes its local devices; built
    # before the trainer so __prepare__ runs ONCE with the sharded plan
    mesh = build_mesh({"data": jax.device_count()})
    cost, params, trainer = _build(cfg, parallelism=DataParallel(mesh))

    # config's batch_size wins, like the train job (cmd_train)
    batch_size = getattr(cfg, "batch_size", None) or args.batch_size or 64
    reader = minibatch.batch(cfg.train_reader(), batch_size)
    costs = []
    heartbeat = None
    if args.task_coordinator:
        # membership lease: the coordinator's lease table is how peers
        # and the launcher learn this worker died (kill -9 included —
        # the lease just lapses); distributed/elastic.py
        from paddle_tpu.distributed.elastic import HeartbeatThread

        heartbeat = HeartbeatThread(
            args.task_coordinator,
            "trainer-%d" % args.process_id, ttl=args.lease_ttl).start()

    def handler(e):
        if getattr(e, "cost", None) is not None:
            costs.append(float(e.cost))
            # self-lapse gate (distributed/elastic.py SelfLeaseLost):
            # once our lease lapsed the launcher considers this worker
            # dead and relaunches a replacement with --resume — training
            # on would race its checkpoint commits and duplicate shards
            if heartbeat is not None and heartbeat.lease_lapsed():
                from paddle_tpu.distributed.elastic import SelfLeaseLost

                raise SelfLeaseLost(
                    "trainer-%d: own lease lapsed (no successful renewal "
                    "within ttl=%.1fs); exiting for the relaunch"
                    % (args.process_id, heartbeat.ttl))

    try:
        trainer.train(reader, num_passes=args.num_passes,
                      event_handler=handler,
                      feed_pipeline=args.feed_pipeline or False,
                      steps_per_call=args.steps_per_call or None,
                      checkpoint_dir=args.checkpoint_dir or None,
                      checkpoint_every=args.checkpoint_every,
                      resume={"exact": True, "pass": "pass"}.get(
                          args.resume, False))
    finally:
        if heartbeat is not None:
            heartbeat.stop()

    final = {"process_id": args.process_id,
             "processes": jax.process_count(),
             "global_devices": jax.device_count(),
             "first_cost": costs[0] if costs else None,
             "final_cost": costs[-1] if costs else None}
    print("CLUSTER_RESULT " + json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
