"""Cluster launcher: start an N-process data-parallel training job.

Reference parity: paddle/scripts/cluster_train/paddle.py — the fabric
script that started pservers and trainers across hosts (job_pserver :101,
job_trainer :130) with trainer_id/ports wired up. The TPU-native launcher
has no parameter servers to start (gradients psum over ICI/DCN); it
spawns one worker per host/process slot, points them all at a
jax.distributed coordinator, and collects their results.

Localhost flavor (this module): all workers on this machine — the
reference's own test shape (SURVEY §4: distributed without a cluster,
test_ParameterServer2.cpp pattern). For real multi-host, run
`python -m paddle_tpu.distributed.worker` on each host with
--coordinator pointing at host 0 (or use any scheduler; the worker is a
plain argv program by design).
"""

import json
import os
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_local_cluster(config, num_processes, num_passes=1,
                         batch_size=None, config_args="", env=None,
                         timeout=900, devices_per_process=None,
                         use_tpu=None):
    """Spawn ``num_processes`` workers on localhost and wait.

    Returns the list of per-worker result dicts (CLUSTER_RESULT lines).
    Raises RuntimeError if any worker fails or the workers disagree on the
    final loss (sync data parallelism must keep them bit-identical in
    lockstep)."""
    port = _free_port()
    base_env = dict(os.environ)
    if env:
        base_env.update(env)
    if devices_per_process is not None:
        base_env["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=%d" % devices_per_process)
    from paddle_tpu.core.place import enforce_children_can_open_devices

    enforce_children_can_open_devices(
        num_processes, "cluster_train --num-processes %d" % num_processes,
        env=base_env)
    import tempfile

    workdir = tempfile.mkdtemp(prefix="paddle_tpu_cluster_")
    procs = []
    streams = []
    for pid in range(num_processes):
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.worker",
               "--config", str(config), "--process-id", str(pid),
               "--num-processes", str(num_processes),
               "--coordinator", "127.0.0.1:%d" % port,
               "--num-passes", str(num_passes)]
        if batch_size:
            cmd += ["--batch-size", str(batch_size)]
        if config_args:
            cmd += ["--config-args", config_args]
        if use_tpu:  # forwarded to each worker; the parent never touches jax
            cmd += ["--use-tpu"]
        # log FILES, not pipes: a chatty worker (log_period=1) fills a 64KB
        # pipe buffer and deadlocks long before the launcher drains it
        out_f = open(os.path.join(workdir, "worker%d.out" % pid), "w+")
        err_f = open(os.path.join(workdir, "worker%d.err" % pid), "w+")
        streams.append((out_f, err_f))
        procs.append(subprocess.Popen(cmd, stdout=out_f, stderr=err_f,
                                      text=True, env=base_env))
    import shutil
    import time

    def read_stream(f):
        f.flush()
        f.seek(0)
        return f.read()

    try:
        # poll ALL workers: one crashed worker leaves its siblings blocked
        # in a collective forever — awaiting sequentially would burn the
        # whole timeout on the innocent process and report it as the failure
        deadline = time.time() + timeout
        errors = []
        pending = dict(enumerate(procs))
        while pending and time.time() < deadline and not errors:
            for pid in list(pending):
                proc = pending[pid]
                if proc.poll() is None:
                    continue
                del pending[pid]
                if proc.returncode != 0:
                    errors.append("worker %d rc=%d: %s"
                                  % (pid, proc.returncode,
                                     read_stream(streams[pid][1])[-1500:]))
            time.sleep(0.2)
        if pending:
            sibling_failed = bool(errors)
            for pid, proc in pending.items():
                proc.kill()
                proc.wait()
                errors.append("worker %d %s" % (
                    pid, "killed (sibling failed)" if sibling_failed
                    else "timed out"))
        if errors:
            raise RuntimeError("cluster launch failed: %s (logs: %s)"
                               % ("; ".join(errors), workdir))
        results = []
        for pid in range(num_processes):
            out = read_stream(streams[pid][0])
            lines = [l for l in out.splitlines()
                     if l.startswith("CLUSTER_RESULT ")]
            if not lines:
                raise RuntimeError("worker %d printed no result (logs: %s)"
                                   % (pid, workdir))
            results.append(json.loads(lines[-1][len("CLUSTER_RESULT "):]))
        if any(r["final_cost"] is None for r in results):
            raise RuntimeError(
                "a worker trained zero batches (reader shorter than one "
                "batch?): %s (logs: %s)" % (results, workdir))
        finals = {round(r["final_cost"], 6) for r in results}
        if len(finals) != 1:
            raise RuntimeError(
                "workers disagree on the final loss (sync-SGD lockstep "
                "violated): %s (logs: %s)" % (sorted(finals), workdir))
    except BaseException:
        for out_f, err_f in streams:  # close but KEEP the logs for debugging
            out_f.close()
            err_f.close()
        raise
    for out_f, err_f in streams:
        out_f.close()
        err_f.close()
    shutil.rmtree(workdir, ignore_errors=True)  # logs kept only on failure
    return results
