#!/usr/bin/env python3
"""Does the program start on the chip? Run train -> export -> serve once on
the TPU and fail loudly if any of it does not run there.

    python chip_smoke.py               # one chip, the default check
    python chip_smoke.py --chips 4     # data parallel + four serving replicas
    python chip_smoke.py --models all  # a few SGD.train steps of every 2017 cell
    python chip_smoke.py --expect-warm # second run: the train step must
                                       # come out of the compile cache

A chip belongs to one process at a time, so this parent never imports JAX.
It runs its stages as children, one after another:

  chip    (this file, one process) device check; fused LSTM/GRU kernels
          against the lax.scan path, forward and backward, compiled by
          Mosaic; the serving reference (paddle.inference.infer); the
          flagship 2xLSTM text classifier through paddle.init ->
          SGD.train, first a dispatch per step, then steps_per_call=K;
          the Mosaic call in the lowered step; one profiler capture;
          twelve batches through a DeviceFeeder, each read back from the
          device and compared with its rows (the feeder's recycled host
          buffers against transfers still in flight); a tiny decoder
          whose recomputed blocks hand values to later blocks takes
          three steps, and its gauges read what its shapes give.
  export  python -m paddle_tpu.cli export --use-tpu ... --decode-slots
  serve   python -m paddle_tpu.cli serve --use-tpu <bundle> --continuous;
          /readyz, POST /infer at several lengths against the reference,
          no compile after warm-up, SIGTERM, exit 0.

Any stage that fails makes the run exit non-zero. The last line of stdout
is {"ok": true, "device": {...}} only when every stage passed on a TPU.

--dry-run-cpu runs the same stages at a tiny size on the CPU backend
(Pallas in interpret mode) to debug the script itself. It proves nothing
about the chip: its last line is {"dry_run_ok": true, ...}, never "ok", and
its report and logs are chiprun_out/*_dryrun.{json,log}.
"""

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".smoke_work")  # params, bundles: made by the run
BUNDLE = os.path.join(WORK, "bundle")
OUT = os.path.join(REPO, "chiprun_out")   # reports + child logs
SEED = 21

# fused-vs-scan tolerance, relative (bench.py's numeric gate uses the same)
GATE_TOL = {"float32": 2e-3, "bfloat16": 8e-2}
# served output vs paddle.inference.infer (tests/test_serve.py's HTTP atol)
SERVE_ATOL = 1e-4

FULL = {
    # BASELINE.md RNN table / bench.py flagship: 2xLSTM + fc, bs64 h256
    "flagship": {"dict_size": 30000, "emb": 128, "hidden": 256},
    "batch": 64, "seq": 100, "steps": 4, "k": 4, "chunks": 2,
    # (hidden, dtype) x (resident | tiled); batch 8, 12 steps as bench.py
    "lstm_checks": [(256, "bfloat16"), (256, "float32"),
                    (1280, "bfloat16"), (1280, "float32")],
    "gru_checks": [(256, "float32"), (256, "bfloat16")],
    # (dtype, tokens): three blocks of 128, the last one padded
    "scan_checks": [("float32", 300), ("bfloat16", 300)],
    # ROADMAP Queue 1 #2's serve model and exp_serve.py's export shape
    "tagger": {"dict_size": 1000, "label_size": 32, "emb_size": 32,
               "hidden": 256},
    "batch_sizes": "1,8", "seq_len": 128, "slots": 48, "window": 6,
    "request_lens": [5, 17, 40, 3, 64, 128],
    # the ResNet-50 cells' rows (3x224x224 float32, 256 a chip): three
    # turns of the feeder's ring of four host buffers
    "feed": {"dim": 3 * 224 * 224, "batch": 256, "batches": 12},
}
TINY = {
    "flagship": {"dict_size": 50, "emb": 8, "hidden": 8},
    "batch": 4, "seq": 6, "steps": 2, "k": 2, "chunks": 1,
    "lstm_checks": [(16, "float32"), (16, "bfloat16"), (256, "float32")],
    "gru_checks": [(16, "float32")],
    "scan_checks": [("float32", 20)],
    "tagger": {"dict_size": 50, "label_size": 4, "emb_size": 8, "hidden": 8},
    "batch_sizes": "1,2", "seq_len": 16, "slots": 4, "window": 3,
    "request_lens": [5, 2, 16, 9],
    "feed": {"dim": 48, "batch": 8, "batches": 12},
}


class SmokeFailure(RuntimeError):
    """A stage did not do on the chip what it claims."""


def require(cond, msg, *args):
    # an explicit raise, not `assert`: python -O must not strip the checks
    if not cond:
        raise SmokeFailure(msg % args if args else msg)


# ======================================================================
# the parent: stdlib only, never touches JAX
# ======================================================================

_children = []
_suffix = ""  # "_dryrun" on a CPU dry run: its files never pass for a chip's


def _spawn(name, cmd, env):
    os.makedirs(OUT, exist_ok=True)
    log = open(os.path.join(OUT, "smoke_%s%s.log" % (name, _suffix)), "w")
    proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                            cwd=REPO, env=env)
    proc.log_path = log.name
    log.close()
    _children.append(proc)
    return proc


def _log_tail(proc, nbytes=6000):
    with open(proc.log_path, "rb") as fh:
        fh.seek(0, os.SEEK_END)
        fh.seek(max(0, fh.tell() - nbytes))
        return fh.read().decode("utf-8", "replace")


def _run(name, cmd, env, timeout):
    """Run one child to its end; its exit code other than 0 fails the run."""
    t0 = time.monotonic()
    proc = _spawn(name, cmd, env)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise SmokeFailure("stage %s: no end after %ds\n%s"
                           % (name, timeout, _log_tail(proc)))
    require(rc == 0, "stage %s: exit code %d\n%s", name, rc, _log_tail(proc))
    print("[chip_smoke] stage %s passed (%.0fs)"
          % (name, time.monotonic() - t0), flush=True)
    return proc


def _stop_children():
    for proc in _children:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def _http(url, body=None, timeout=60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"{}")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _max_abs_diff(got, want):
    """Largest |got - want| over two equally nested lists of numbers."""
    if isinstance(want, list):
        require(isinstance(got, list) and len(got) == len(want),
                "served output shape differs from the reference")
        return max((_max_abs_diff(g, w) for g, w in zip(got, want)),
                   default=0.0)
    return abs(float(got) - float(want))


def _read_json(path):
    with open(path) as fh:
        return json.load(fh)


def _use_tpu_flag(args):
    return [] if args.dry_run_cpu else ["--use-tpu"]


def _stage_cmd(stage, args):
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", stage,
           "--chips", str(args.chips)]
    if args.dry_run_cpu:
        cmd.append("--dry-run-cpu")
    if args.expect_warm:
        cmd.append("--expect-warm")
    return cmd


def export_argv(cfg, use_tpu):
    """``cli export`` of the tagger phase_serve_reference wrote to WORK."""
    return ["export", *use_tpu,
            "--config", os.path.join(WORK, "tagger_config.py"),
            "--params", os.path.join(WORK, "tagger_params.tar"),
            "--output", BUNDLE, "--name", "tagger",
            "--batch-sizes", cfg["batch_sizes"],
            "--seq-len", str(cfg["seq_len"]),
            "--decode-slots", str(cfg["slots"]),
            "--decode-window", str(cfg["window"])]


def _serve_stage(args, cfg, env, use_tpu):
    """cli export, then cli serve, as the children a user would start."""
    _run("export", [sys.executable, "-m", "paddle_tpu.cli",
                    *export_argv(cfg, use_tpu)], env, timeout=600)
    manifest = _read_json(os.path.join(BUNDLE, "manifest.json"))
    if not args.dry_run_cpu:
        require("tpu" in manifest["platforms"],
                "bundle was exported for %r, not on the chip",
                manifest["platforms"])
    require(manifest.get("decode"), "bundle has no decode artifacts")

    ref = _read_json(os.path.join(WORK, "tagger_reference.json"))
    port = _free_port()
    base = "http://127.0.0.1:%d" % port
    proc = _spawn("serve", [
        sys.executable, "-m", "paddle_tpu.cli", "serve", *use_tpu, BUNDLE,
        "--continuous", "--port", str(port)], env)
    deadline = time.monotonic() + 600
    while True:
        require(proc.poll() is None, "cli serve exited with code %s before "
                "it was ready\n%s", proc.returncode, _log_tail(proc))
        require(time.monotonic() < deadline, "cli serve: /readyz not green "
                "after 600s\n%s", _log_tail(proc))
        try:
            if _http(base + "/readyz", timeout=5.0)[0] == 200:
                break
        except (urllib.error.URLError, OSError):
            pass  # not listening yet
        time.sleep(0.5)
    compiles_warm = _http(base + "/debug/compiles")[1]["compiles"]
    worst = 0.0
    for seq, want in zip(ref["requests"], ref["outputs"]):
        status, body = _http(base + "/infer", {"inputs": {"word": seq}})
        require(status == 200, "POST /infer (length %d): %s %s",
                len(seq), status, body)
        worst = max(worst,
                    _max_abs_diff(body["outputs"][ref["output"]], want))
    require(worst <= SERVE_ATOL, "served outputs differ from "
            "paddle.inference.infer by %.3g (atol %g)", worst, SERVE_ATOL)
    compiles_after = _http(base + "/debug/compiles")[1]["compiles"]
    require(compiles_after == compiles_warm, "cli serve compiled %d "
            "program(s) after warm-up", compiles_after - compiles_warm)
    stats = _http(base + "/stats")[1]
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        raise SmokeFailure("cli serve: no exit 120s after SIGTERM\n%s"
                           % _log_tail(proc))
    require(rc == 0, "cli serve: exit code %d after SIGTERM\n%s", rc,
            _log_tail(proc))
    print("[chip_smoke] stage serve passed", flush=True)
    return {"platforms": manifest["platforms"],
            "requests": len(ref["requests"]),
            "max_abs_diff_vs_infer": worst,
            "compiles_after_warmup": compiles_after - compiles_warm,
            "warmup_compiles": compiles_warm,
            "completed": stats.get("requests"), "sigterm_exit": rc}


def parent(args):
    global _suffix
    cfg = TINY if args.dry_run_cpu else FULL
    _suffix = "_dryrun" if args.dry_run_cpu else ""
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(OUT, exist_ok=True)
    env = dict(os.environ, PYTHONUNBUFFERED="1", PYTHONPATH=os.pathsep.join(
        filter(None, [REPO, os.environ.get("PYTHONPATH")])))
    use_tpu = _use_tpu_flag(args)
    summary = {"mode": ("models" if args.models else
                        "chips%d" % args.chips)}
    if args.dry_run_cpu:
        summary["dry_run"] = "cpu backend, tiny size, Pallas interpreted"
    try:
        if args.models:
            _run("models", _stage_cmd("models", args) +
                 ["--models", args.models], env, timeout=3300)
            summary.update(_read_json(os.path.join(WORK, "models.json")))
        elif args.chips > 1:
            _run("multichip", _stage_cmd("multichip", args), env,
                 timeout=1100)
            summary.update(_read_json(os.path.join(WORK, "multichip.json")))
        else:
            _run("chip", _stage_cmd("chip", args), env, timeout=900)
            summary.update(_read_json(os.path.join(WORK, "chip.json")))
            summary["serve"] = _serve_stage(args, cfg, env, use_tpu)
    finally:
        _stop_children()
    with open(os.path.join(OUT, "chip_smoke_%s%s.json"
                           % (summary["mode"], _suffix)), "w") as fh:
        json.dump(summary, fh, indent=1, sort_keys=True)
    print(json.dumps({"summary": summary}, sort_keys=True), flush=True)
    # "ok" is the chip's word only: a dry run ends on another key
    print(json.dumps({"dry_run_ok" if args.dry_run_cpu else "ok": True,
                      "device": summary["device"]}), flush=True)
    return 0


# ======================================================================
# the stages: each runs in a child that owns the chip
# ======================================================================

def open_device(args):
    """First thing in every stage: fail at once unless JAX runs on a TPU
    (and sees --chips of them). Returns the device as JAX reports it."""
    sys.path.insert(0, REPO)
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.dry_run_cpu:
        require(device["platform"] == "tpu",
                "no TPU: jax.devices() returned %r (JAX_PLATFORMS=%r)",
                devices, os.environ.get("JAX_PLATFORMS"))
        require(not os.environ.get("PADDLE_TPU_DISABLE_PALLAS"),
                "PADDLE_TPU_DISABLE_PALLAS is set: this run would check "
                "the lax.scan reference, not the kernels")
    require(len(devices) >= args.chips, "--chips %d, but JAX sees %d: %r",
            args.chips, len(devices), devices)
    if args.dry_run_cpu:
        from paddle_tpu.ops import pallas_conv, pallas_kernels

        pallas_kernels._INTERPRET = pallas_conv._INTERPRET = True
    return device, {"jax": jax.__version__, "switches": {
        k: v for k, v in os.environ.items() if k.startswith("PADDLE_TPU_")}}


def _rel_err(got, want):
    import numpy as np

    got32, want32 = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got32 - want32).max()) / max(
        1.0, float(np.abs(want32).max()))


def _run_check(both, args, mesh, kernel, label):
    """Run ``both(*args)`` (scan path and fused path, value and grads).
    With a mesh, args[0] is split on its batch axis over the mesh's "data"
    axis and the call is traced under use_mesh(mesh, batch_axis="data"),
    where the fused scan must lower as a shard_map round ``kernel``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.parallel.mesh import use_mesh

    if mesh is None:
        return jax.device_get(both(*args))
    args = (jax.device_put(args[0], NamedSharding(mesh, P("data"))),
            *args[1:])
    with use_mesh(mesh, batch_axis="data"):
        text = both.lower(*args).as_text()
        require("manual_computation" in text, "%s: no shard_map in the "
                "program lowered under the mesh", label)
        if not pk._INTERPRET:
            require('kernel_name = "%s' % kernel in text,
                    "%s: no %s* Mosaic call under the mesh", label, kernel)
        return jax.device_get(both(*args))


def check_lstm_kernel(hidden, dtype_name, mesh=None, rows=8, t=12):
    """Fused LSTM (peephole on, as the flagship's lstmemory runs it) vs
    the lax.scan path on this backend: loss and every gradient within
    GATE_TOL, at ``rows`` rows on each device. Returns a label naming the
    kernel variant that ran."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops import rnn as rnn_ops

    dtype = jnp.dtype(dtype_name)
    mode = pk.lstm_mode(rows, hidden, dtype)
    require(mode is not None, "no fused lstm mode for h=%d %s", hidden,
            dtype_name)
    batch = rows * (mesh.size if mesh is not None else 1)
    rng = np.random.RandomState(hidden)
    gates = jnp.asarray(rng.randn(batch, t, 4 * hidden) * 0.3, dtype)
    lengths = rng.randint(1, t + 1, batch)
    lengths[0] = t
    mask = jnp.asarray(np.arange(t)[None, :] < lengths[:, None], jnp.float32)
    w = jnp.asarray(rng.randn(hidden, 4 * hidden) / np.sqrt(hidden), dtype)
    peep = jnp.asarray(rng.randn(3 * hidden) * 0.3, jnp.float32)
    sel = jnp.asarray(rng.randn(batch, t, hidden), jnp.float32)
    sf = jnp.asarray(rng.randn(batch, hidden), jnp.float32)

    def loss(standard, g, w, p):
        h_seq, (h_f, c_f) = rnn_ops.lstm_scan(
            g, mask, None, None, w, standard_acts=standard,
            use_peephole=True, w_peep=p)
        return (jnp.sum(h_seq.astype(jnp.float32) * sel)
                + jnp.sum(h_f.astype(jnp.float32) * sf)
                + 0.5 * jnp.sum(c_f.astype(jnp.float32) * sf))

    @jax.jit
    def both(g, w, p):
        # standard_acts=False forces the scan path: the reference
        ref = jax.value_and_grad(lambda *a: loss(False, *a),
                                 argnums=(0, 1, 2))(g, w, p)
        fus = jax.value_and_grad(lambda *a: loss(True, *a),
                                 argnums=(0, 1, 2))(g, w, p)
        return ref, fus

    label = "lstm[h=%d,%s,%s,peephole%s]" % (
        hidden, dtype_name, mode,
        ",%d devices" % mesh.size if mesh is not None else "")
    (ref, gr), (fus, gf) = _run_check(both, (gates, w, peep), mesh,
                                      "_lstm_fwd", label)
    _require_close(label, GATE_TOL[dtype_name], ref, fus,
                   zip(gf, gr, ("dgates", "dw", "dpeep")))
    return label


def check_gru_kernel(hidden, dtype_name, mesh=None, rows=8, t=12):
    """Fused GRU vs the lax.scan path; as check_lstm_kernel."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops import rnn as rnn_ops

    dtype = jnp.dtype(dtype_name)
    require(pk.gru_mode(rows, hidden, dtype) is not None,
            "no fused gru mode for h=%d %s", hidden, dtype_name)
    batch = rows * (mesh.size if mesh is not None else 1)
    rng = np.random.RandomState(hidden + 7)
    proj = jnp.asarray(rng.randn(batch, t, 3 * hidden) * 0.3, dtype)
    lengths = rng.randint(1, t + 1, batch)
    lengths[0] = t
    mask = jnp.asarray(np.arange(t)[None, :] < lengths[:, None], jnp.float32)
    w_rz = jnp.asarray(rng.randn(hidden, 2 * hidden) / np.sqrt(hidden), dtype)
    w_c = jnp.asarray(rng.randn(hidden, hidden) / np.sqrt(hidden), dtype)
    sel = jnp.asarray(rng.randn(batch, t, hidden), jnp.float32)

    def loss(fused, p, wrz, wc):
        # gru_scan takes its fused path only for jax.nn.sigmoid itself;
        # any other callable, this equal one included, is the scan path
        gate = jax.nn.sigmoid if fused else (lambda x: jax.nn.sigmoid(x))
        h_seq, h_f = rnn_ops.gru_scan(p, mask, None, None, wrz, wc,
                                      gate_act=gate)
        return (jnp.sum(h_seq.astype(jnp.float32) * sel)
                + jnp.sum(h_f.astype(jnp.float32)))

    @jax.jit
    def both(p, wrz, wc):
        ref = jax.value_and_grad(lambda *a: loss(False, *a),
                                 argnums=(0, 1, 2))(p, wrz, wc)
        fus = jax.value_and_grad(lambda *a: loss(True, *a),
                                 argnums=(0, 1, 2))(p, wrz, wc)
        return ref, fus

    label = "gru[h=%d,%s%s]" % (
        hidden, dtype_name,
        ",%d devices" % mesh.size if mesh is not None else "")
    (ref, gr), (fus, gf) = _run_check(both, (proj, w_rz, w_c), mesh,
                                      "_gru_fwd", label)
    _require_close(label, GATE_TOL[dtype_name], ref, fus,
                   zip(gf, gr, ("dproj", "dw_rz", "dw_c")))
    return label


def check_selective_scan_kernel(dtype_name, t, channels=512, states=16):
    """The two kernels of Mamba-1's scan (ops/pallas_ssm.py) vs the plain
    loops on this backend: loss and every gradient within GATE_TOL, over
    lengths and a token count that is no multiple of the block."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_ssm
    from paddle_tpu.ops import ssm

    require(ssm.selective_scan_form(channels, states) == "fused",
            "%d channels of %d states do not take the fused scan here",
            channels, states)
    dtype = jnp.dtype(dtype_name)
    rng = np.random.RandomState(t + 11)
    x = jnp.asarray(rng.randn(2, t, channels), dtype)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(2, t, channels) - 2.0,
                                     jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.randn(channels, states) * 0.5, jnp.float32))
    b_mat, c_mat = (jnp.asarray(rng.randn(2, t, states), dtype)
                    for _ in range(2))
    d_skip = jnp.asarray(rng.randn(channels), jnp.float32)
    sel = jnp.asarray(rng.randn(2, t, channels), jnp.float32)
    lengths = jnp.asarray([t, t - t // 4])

    def plain_scan(*args, **kwargs):
        # the oracle: selective_scan with the kernels refused for this one
        # trace, the smoke's only way to the loops on a backend that fits
        fits, pallas_ssm.fits = pallas_ssm.fits, lambda *_: False
        try:
            return ssm.selective_scan(*args, **kwargs)
        finally:
            pallas_ssm.fits = fits

    def loss(fused, *args):
        scan = ssm.selective_scan if fused else plain_scan
        y, last = scan(*args, lengths=lengths)
        return jnp.sum(y.astype(jnp.float32) * sel) + jnp.sum(last)

    @jax.jit
    def both(*args):
        return tuple(jax.value_and_grad(lambda *a: loss(fused, *a),
                                        argnums=range(6))(*args)
                     for fused in (False, True))

    label = "selective_scan[t=%d,e=%d,n=%d,%s]" % (t, channels, states,
                                                   dtype_name)
    (ref, gr), (fus, gf) = jax.device_get(both(x, dt, a, b_mat, c_mat,
                                               d_skip))
    _require_close(label, GATE_TOL[dtype_name], ref, fus,
                   list(zip(gf, gr, ("x", "dt", "A", "B", "C", "D"))))
    return label


def _require_close(label, tol, ref, fus, grads):
    require(abs(float(fus) - float(ref)) / max(1.0, abs(float(ref))) < tol,
            "%s fwd mismatch: %r vs %r", label, float(fus), float(ref))
    for got, want, nm in grads:
        require(_rel_err(got, want) < tol, "%s %s grad mismatch: rel %.4g",
                label, nm, _rel_err(got, want))


def phase_kernels(cfg, mesh=None):
    from paddle_tpu.ops import pallas_kernels as pk

    require(pk.enabled(), "fused kernels are off on this backend")
    scans = [] if mesh is not None else [   # one device: see pallas_ssm.fits
        check_selective_scan_kernel(dt, t) for dt, t in cfg["scan_checks"]]
    return ([check_lstm_kernel(h, dt, mesh) for h, dt in cfg["lstm_checks"]]
            + [check_gru_kernel(h, dt, mesh) for h, dt in cfg["gru_checks"]]
            + scans)


def phase_serve_reference(cfg):
    """Write what cli export needs (config module + seeded parameter tar)
    and what the served answers must equal: paddle.inference.infer over
    the same requests, on this device."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.text import sequence_tagging_gru

    reset_name_counters()
    out = sequence_tagging_gru(**cfg["tagger"])
    params = paddle.parameters.create(out, rng=jax.random.PRNGKey(SEED))
    with open(os.path.join(WORK, "tagger_params.tar"), "wb") as fh:
        params.to_tar(fh)
    with open(os.path.join(WORK, "tagger_config.py"), "w") as fh:
        fh.write("from paddle_tpu.models.text import sequence_tagging_gru"
                 "\n\n\ndef infer_outputs():\n"
                 "    return sequence_tagging_gru(**%r)\n" % cfg["tagger"])
    rng = np.random.RandomState(SEED)
    requests = [rng.randint(0, cfg["tagger"]["dict_size"], n).astype(np.int32)
                for n in cfg["request_lens"]]
    probs = paddle.infer(output_layer=out, parameters=params,
                         input=[(r,) for r in requests])  # [B, Tmax, L]
    with open(os.path.join(WORK, "tagger_reference.json"), "w") as fh:
        json.dump({"output": out.name,
                   "requests": [r.tolist() for r in requests],
                   "outputs": [probs[i, :len(r)].tolist()
                               for i, r in enumerate(requests)]}, fh)
    return requests, probs


def flagship_reader(cfg, n_batches):
    import numpy as np

    import paddle_tpu as paddle

    def samples():
        rng = np.random.RandomState(SEED)
        for _ in range(n_batches * cfg["batch"]):
            yield (rng.randint(0, cfg["flagship"]["dict_size"], cfg["seq"]),
                   int(rng.randint(2)))

    return paddle.batch(samples, cfg["batch"])


def cost_collector(costs):
    """An SGD.train event handler that appends every step's loss."""
    import paddle_tpu as paddle

    def on_event(event):
        if isinstance(event, paddle.event.EndIteration):
            costs.append(float(event.cost))

    return on_event


def build_flagship(cfg, parallelism=None):
    """The benchmark's flagship under the benchmark's precision policy,
    through the v2 entry points a user calls."""
    import jax

    import __graft_entry__ as graft
    import paddle_tpu as paddle

    _, _, _, cost = graft._flagship(**cfg["flagship"])
    params = paddle.parameters.create(cost, rng=jax.random.PRNGKey(SEED))
    trainer = paddle.trainer.SGD(
        cost, params, paddle.optimizer.Momentum(learning_rate=0.01,
                                                momentum=0.9),
        parallelism=parallelism)
    return params, trainer


def train_and_check(trainer, params, cfg, platform):
    """A few steps one dispatch at a time through the feed pipeline, then
    a few through steps_per_call=K. Finite losses, every parameter moved,
    the training state on ``platform``'s devices."""
    import numpy as np

    import jax

    before = {n: np.array(params.get(n)) for n in params.names()}
    costs = []
    trainer.train(flagship_reader(cfg, cfg["steps"]), num_passes=1,
                  event_handler=cost_collector(costs), feed_pipeline=True)
    trainer.train(flagship_reader(cfg, cfg["k"] * cfg["chunks"]),
                  num_passes=1, event_handler=cost_collector(costs),
                  steps_per_call=cfg["k"])
    want = cfg["steps"] + cfg["k"] * cfg["chunks"]
    require(len(costs) == want, "trained %d steps, expected %d", len(costs),
            want)
    require(all(np.isfinite(costs)), "non-finite loss: %r", costs)
    still = [n for n in params.names()
             if np.array_equal(before[n], np.asarray(params.get(n)))]
    require(not still, "parameters did not change: %r", still)
    placed = {d.platform for leaf in jax.tree.leaves(
        (trainer._trainable, trainer._opt_state)) for d in leaf.devices()}
    require(placed == {platform}, "training state lives on %r, not %r",
            sorted(placed), platform)
    return costs


def lowered_step(trainer, feed):
    """StableHLO of the trainer's step on ``feed``. A data-parallel step
    is a plain function round its jit; jitting that function once more
    lowers the same trace."""
    import jax

    return jax.jit(trainer._train_step).lower(
        trainer._trainable, trainer._replica, trainer._static,
        trainer._state, trainer._opt_state, feed,
        jax.random.PRNGKey(0)).as_text()


def fused_calls(stablehlo, cell, layers, args):
    """The fused ``cell`` ("lstm" | "gru") really is in the lowered step:
    one Mosaic call per recurrent layer forward and one backward, and no
    while loop (the lax.scan ops/rnn.py takes when pk.enabled() or
    *_mode() say no)."""
    if args.dry_run_cpu:  # interpreted Pallas: plain ops in a while loop
        return None
    calls = {k: stablehlo.count('kernel_name = "_%s_%s_kernel"' % (cell, k))
             for k in ("fwd", "bwd")}
    require(calls == {"fwd": layers, "bwd": layers}, "lowered %s train "
            "step holds %r fused Mosaic calls, expected %d forward + %d "
            "backward", cell, calls, layers, layers)
    require("stablehlo.while" not in stablehlo,
            "lowered %s train step holds a while loop: a scan path", cell)
    return calls


def phase_train(cfg, args, device):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.observe import attribution
    from paddle_tpu.topology import convert_feed
    from paddle_tpu.utils import compile_cache

    paddle.init(use_tpu=None if args.dry_run_cpu else True, seed=SEED,
                compute_dtype="bfloat16", matmul_precision="default")
    params, trainer = build_flagship(cfg)

    # the step's program, before the trainer runs it: what it contains,
    # and whether the persistent cache already held it
    feed = convert_feed(trainer.topology,
                        next(iter(flagship_reader(cfg, 1)())))
    key = jax.random.PRNGKey(0)
    lowered = trainer._train_step.lower(
        trainer._trainable, trainer._replica, trainer._static,
        trainer._state, trainer._opt_state, feed, key)
    stablehlo = lowered.as_text()
    seen = compile_cache.stats()
    compiled = lowered.compile()
    now = compile_cache.stats()
    report = {"train_step_cache": {
        "requests": now["requests"] - seen["requests"],
        "hits": now["hits"] - seen["hits"]}}
    report["train_step_compiled"] = (
        report["train_step_cache"]["hits"]
        < report["train_step_cache"]["requests"])
    if args.expect_warm:
        require(not report["train_step_compiled"], "second run: the train "
                "step compiled again (%r)", report["train_step_cache"])
    calls = fused_calls(stablehlo, "lstm", 2, args)
    if not args.dry_run_cpu:
        mosaic = compiled.as_text().count('custom_call_target="tpu_custom_call"')
        require(mosaic >= 4, "compiled train step holds %d tpu_custom_call",
                mosaic)
        report["mosaic_calls"] = dict(calls, compiled=mosaic)

    report["losses"] = train_and_check(trainer, params, cfg,
                                       device["platform"])

    # one short profiler capture of the step through the reader the
    # per-layer metrics will be built on
    class Step:
        carry = (trainer._trainable, trainer._replica, trainer._state,
                 trainer._opt_state, None)

        def step(self, c):
            loss, tr, rep, st, opt, _ = trainer._train_step(
                c[0], c[1], trainer._static, c[2], c[3], feed, key)
            return (tr, rep, st, opt, loss)

        def fetch(self, c):
            return None if c[4] is None else float(c[4])

    step = Step()
    busy = attribution.device_busy_ms(step, steps=5)
    if busy is None and not args.dry_run_cpu:
        state = {"c": step.carry}  # a second capture, to name its tracks
        trace = attribution.capture(
            lambda: state.update(c=step.step(state["c"])),
            lambda: step.fetch(state["c"]))
        raise SmokeFailure(
            "device_busy_ms returned None: the trace has no 'XLA Modules' "
            "track; tracks seen: %r" % (trace and trace.tracks,))
    report["device_busy_ms_is_number"] = busy is not None
    return report


def phase_feed_readback(cfg, parallelism=None):
    """What only a chip can show of the feeder's recycled host buffers: a
    placement returns before the bytes have left the host, so a buffer
    written too early sends a mixture of two batches to the device, in
    silence. Batches of distinct seeded rows go through a DeviceFeeder as
    fast as its producer makes them (all kept, so the ring of depth + 2
    buffers wraps with transfers in flight); each yielded feed is then read
    back and must equal its rows exactly. With a ``parallelism`` the batch
    is one of 256 rows a device, placed on the mesh."""
    import numpy as np

    import jax

    from paddle_tpu import data_type as dt, layer as L
    from paddle_tpu.data.feeder import DeviceFeeder
    from paddle_tpu.observe.metrics import MetricsRegistry, get_registry
    from paddle_tpu.topology import Topology

    sizes = cfg["feed"]
    devices = 1 if parallelism is None else parallelism.mesh.size
    rows, n, classes = sizes["batch"] * devices, sizes["batches"], 1000
    tag = "readback%d" % devices  # layer names are the process's
    image = L.data(name=tag + "_image", type=dt.dense_vector(sizes["dim"]))
    label = L.data(name=tag + "_label", type=dt.integer_value(classes))
    topology = Topology(L.classification_cost(
        input=L.fc(input=image, size=classes), label=label))
    made = []
    for i in range(n):
        rng = np.random.default_rng(SEED + i)
        made.append((rng.random((rows, sizes["dim"]), dtype=np.float32),
                     rng.integers(0, classes, rows)))
    batches = [[(images[j], int(labels[j])) for j in range(rows)]
               for images, labels in made]
    registry = MetricsRegistry()
    depth = 2
    resharded = get_registry().counter("paddle_tpu_data_feed_resharded_total")
    moved = resharded.value
    feeder = DeviceFeeder(lambda: iter(batches), topology, depth=depth,
                          parallelism=parallelism, metrics_registry=registry)
    taken = list(feeder.batches())
    moved = resharded.value - moved
    require(len(taken) == n, "the feeder yielded %d of %d batches",
            len(taken), n)
    for i, (fb, (images, labels)) in enumerate(zip(taken, made)):
        got = jax.device_get(fb.feed)
        spans = len(fb.feed[tag + "_image"].sharding.device_set)
        require(spans == devices, "batch %d lies on %d devices, expected %d",
                i, spans, devices)
        require(np.array_equal(got[tag + "_image"], images),
                "batch %d of %d read back from the device differs from its "
                "rows: %d of %d values", i, n,
                int((got[tag + "_image"] != images).sum()), images.size)
        require(np.array_equal(got[tag + "_label"],
                               labels.astype(np.int32)),
                "batch %d: labels read back differ from its rows", i)
    snap = registry.snapshot()
    ring = depth + 2
    counted = {k: snap["counters"].get(
        "paddle_tpu_data_feed_buffers_%s_total" % k, 0)
        for k in ("reused", "allocated")}
    require(counted == {"reused": 2 * (n - ring), "allocated": 2 * ring},
            "%d batches of two columns over a ring of %d: %r", n, ring,
            counted)
    # over a mesh every column went from its host buffer straight to its
    # shards, and none over one device (shard_batch counts in the
    # process's registry)
    straight = snap["counters"].get(
        "paddle_tpu_data_feed_placed_sharded_total", 0)
    require((straight, moved) == (2 * n if devices > 1 else 0, 0),
            "%d batches of two columns over %d device(s): %d placed "
            "straight onto the mesh, %d moved there from a device", n,
            devices, straight, moved)
    waits = snap["histograms"]["paddle_tpu_data_feed_buffer_wait_ms"]
    return dict(counted, batches=n, rows=rows, devices=devices,
                placed_sharded=straight, resharded=moved,
                megabytes_a_batch=round(rows * sizes["dim"] * 4 / 1e6, 1),
                buffer_wait_ms_mean=waits["sum"] / waits["count"],
                host_ms_mean=sum(fb.host_ms for fb in taken[ring:])
                / (n - ring),
                place_ms_mean=sum(fb.place_ms for fb in taken[ring:])
                / (n - ring))


def phase_shared_blocks():
    """A decoder whose recomputed blocks hand values to later blocks (a
    Mamba-1 layer's scan output to Gated Memory Units, a full-attention
    layer's keys and values to cross-attention layers; window and
    differential attention beside them) takes three steps through
    SGD.train, and the gauges set as its step was traced read what the
    shapes give: the tiny preset of the `phi4flash` layout, on whatever
    device the stage holds."""
    import numpy as np

    import paddle_tpu as paddle
    from chipbench import traffic
    from paddle_tpu import layer as L
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.observe import metrics as observe_metrics
    from paddle_tpu.topology import convert_feed

    tiny = os.path.join(REPO, "tests", "chipbench", "tiny")
    cfg = _read_json(os.path.join(
        tiny, "configs", "phi-4-mini-flash-reasoning.json"))
    cell = _read_json(os.path.join(
        tiny, "workloads",
        "phi-4-mini-flash-reasoning-seq4096-bs2-train.json"))
    # 128 channels of 8 states, where the preset has 4: the narrowest
    # Mamba-1 layer that the fused scan takes (ops/pallas_ssm.py fits)
    cfg["mamba_d_state"] = 8
    L.reset_name_counters()
    cost = hybrid_lm.from_config(cfg)[3]
    trainer = paddle.trainer.SGD(
        cost, paddle.parameters.create(cost),
        paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9))
    pool = traffic.make_pool(cfg["inputs"], cell, SEED)
    costs = []
    trainer.train(lambda: iter(pool), event_handler=cost_collector(costs),
                  feed_pipeline=True)
    require(len(costs) == len(pool) and all(np.isfinite(costs)),
            "the shared-value decoder's costs are %r", costs)
    rows, padded = convert_feed(trainer.topology,
                                pool[-1])["tokens"].data.shape
    gauges = observe_metrics.get_registry().snapshot()["gauges"]
    heads = cfg["num_attention_heads"]
    width = cfg["mamba_expand"] * cfg["hidden_size"] \
        + 2 * cfg["num_key_value_heads"] * (cfg["hidden_size"] // heads)
    shared = gauges["paddle_tpu_shared_across_blocks_bytes"]
    require(shared in (rows * padded * width * 2, rows * padded * width * 4),
            "%r bytes live across blocks, not %d values of 2 or 4 bytes",
            shared, rows * padded * width)
    visited, possible = (gauges["paddle_tpu_attention_key_blocks_" + k]
                         for k in ("visited", "possible"))
    require(0 < visited <= possible, "attention visited %r of %r key blocks",
            visited, possible)
    scans = sum(cfg["layer_types"][i] == "mamba1"
                for i in cfg["kept_layers"])
    fused, plain = (gauges["paddle_tpu_selective_scan_" + k]
                    for k in ("fused", "plain"))
    print("selective scans of the traced step: %d fused, %d plain"
          % (fused, plain), flush=True)
    require((fused, plain) == (scans, 0), "of %d Mamba-1 scans %r ran as "
            "the fused kernels and %r as plain loops", scans, fused, plain)
    return {"costs": costs, "shared_across_blocks_bytes": shared,
            "attention_key_blocks": [visited, possible],
            "selective_scans": {"fused": fused, "plain": plain}}


def stage_chip(args):
    cfg = TINY if args.dry_run_cpu else FULL
    device, env_report = open_device(args)
    import paddle_tpu as paddle
    from paddle_tpu.utils import compile_cache

    paddle.init(use_tpu=None if args.dry_run_cpu else True, seed=SEED)
    report = dict(env_report, device=device)
    report["kernels"] = phase_kernels(cfg)
    phase_serve_reference(cfg)
    report["train"] = phase_train(cfg, args, device)
    report["feed_readback"] = phase_feed_readback(cfg)
    report["shared_blocks"] = phase_shared_blocks()
    report["compile_cache"] = compile_cache.stats()
    with open(os.path.join(WORK, "chip.json"), "w") as fh:
        json.dump(report, fh)
    return 0


def stage_multichip(args):
    """--chips N in one process: the fused kernels against the scan path
    with the batch split over an N-device mesh, the flagship through
    DataParallel(build_mesh({"data": N})) with the DeviceFeeder placing
    the batch, and the tagger bundle as ReplicaSet(replicas=N)."""
    cfg = TINY if args.dry_run_cpu else FULL
    n = args.chips
    device, env_report = open_device(args)
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import cli
    from paddle_tpu.data.feeder import DeviceFeeder
    from paddle_tpu.observe.metrics import MetricsRegistry
    from paddle_tpu.parallel.mesh import DataParallel, build_mesh
    from paddle_tpu.serve import ReplicaSet, load_bundle
    from paddle_tpu.topology import convert_feed
    from paddle_tpu.utils import compile_cache

    use_tpu = None if args.dry_run_cpu else True
    paddle.init(use_tpu=use_tpu, seed=SEED, compute_dtype="bfloat16",
                matmul_precision="default")
    devices = jax.devices()[:n]
    report = dict(env_report, device=device)
    dp = DataParallel(build_mesh({"data": n}, devices=devices))

    # XLA cannot partition a Mosaic kernel: under the mesh each fused scan
    # is a shard_map, a kernel instance per device on its own rows, and
    # the weight gradients are summed over the devices
    report["kernels"] = phase_kernels(cfg, dp.mesh)

    # one step on one fixed global batch, one chip then N: the same loss
    # (forward) and the same movement of every parameter (the first
    # Momentum step is -lr * gradient, so this is the backward pass and the
    # sum of the weight gradients over the devices)
    def one_step(parallelism):
        params, trainer = build_flagship(cfg, parallelism)
        before = {n: np.array(params.get(n)) for n in params.names()}
        costs = []
        trainer.train(flagship_reader(cfg, 1), num_passes=1,
                      event_handler=cost_collector(costs))
        moved = {n: np.asarray(params.get(n)) - before[n] for n in before}
        return costs[0], moved, params, trainer

    one, moved_one, _, _ = one_step(None)
    many, moved_many, params, trainer = one_step(dp)
    require(abs(many - one) <= 1e-3 * max(1.0, abs(one)), "loss of the "
            "same global batch: %r on %d chips, %r on one", many, n, one)
    tol, worst = GATE_TOL["bfloat16"], 0.0
    for name, want in moved_one.items():
        require(np.linalg.norm(want) > 0, "%s did not move on one chip", name)
        err = float(np.linalg.norm(moved_many[name] - want)
                    / np.linalg.norm(want))
        require(err <= tol, "one update moved %s differently on %d chips "
                "than on one: relative %.3g (tol %g)", name, n, err, tol)
        worst = max(worst, err)
    feed = convert_feed(trainer.topology,
                        next(iter(flagship_reader(cfg, 1)())))
    # and the step really runs the fused kernel, one instance per device
    stablehlo = lowered_step(trainer, feed)
    require("manual_computation" in stablehlo,
            "data-parallel step holds no shard_map")
    mosaic = fused_calls(stablehlo, "lstm", 2, args)

    # what the trainer's DeviceFeeder hands the step spans all N devices
    feeder = DeviceFeeder(flagship_reader(cfg, 1), trainer.topology,
                          parallelism=dp)
    batches = feeder.batches()
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree.leaves(next(batches).feed)}
    batches.close()  # stops the producer thread
    require(spans == {n}, "feed shardings span %r devices, expected %d",
            sorted(spans), n)
    losses = train_and_check(trainer, params, cfg, device["platform"])
    if not args.dry_run_cpu:  # the CPU backend reports no memory stats
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        require(all(b > 0 for b in in_use), "bytes in use per device: %r",
                in_use)
        report["bytes_in_use_per_device"] = in_use
    report["train"] = {"loss_one_chip": one, "loss_n_chips": many,
                       "update_rel_err_vs_one_chip": worst,
                       "losses": losses, "feed_spans_devices": n,
                       "mosaic_calls": mosaic}
    report["feed_readback"] = [phase_feed_readback(cfg),
                               phase_feed_readback(cfg, dp)]

    # the same bundle cli export writes, as N one-chip replicas; serving
    # runs under the framework's default precision, not the benchmark's
    paddle.init(use_tpu=use_tpu, seed=SEED, compute_dtype="",
                matmul_precision="highest")
    requests, probs = phase_serve_reference(cfg)
    rc = cli.main(export_argv(cfg, _use_tpu_flag(args)))
    require(rc == 0, "cli export returned %r", rc)
    bundle = load_bundle(BUNDLE)
    out_name = bundle.outputs[0]["name"]
    fleet = ReplicaSet(bundle, replicas=n, devices=devices, continuous=True,
                       metrics_registry=MetricsRegistry())
    try:
        homes = []
        for member in fleet.replicas():
            placed = {d for leaf in jax.tree.leaves(member.bundle.params())
                      for d in leaf.devices()}
            require(placed == {member.device}, "replica %d: parameters on "
                    "%r, not on its device %r", member.index, placed,
                    member.device)
            homes.append(member.device)
            for i, seq in enumerate(requests):  # every replica answers
                got = member.engine.infer({"word": seq},
                                          timeout=300.0)[out_name]
                diff = float(np.abs(got - probs[i, :len(seq)]).max())
                require(diff <= SERVE_ATOL, "replica %d, length %d: differs "
                        "from paddle.inference.infer by %.3g",
                        member.index, len(seq), diff)
        require(len(set(homes)) == n, "replicas share devices: %r", homes)
        for i, seq in enumerate(requests):  # and so does the front
            got = fleet.infer({"word": seq}, timeout=300.0)[out_name]
            require(float(np.abs(got - probs[i, :len(seq)]).max())
                    <= SERVE_ATOL, "fleet front, length %d: differs from "
                    "paddle.inference.infer", len(seq))
    finally:
        fleet.stop()
    report["serve"] = {"replicas": n, "devices": [str(d) for d in homes],
                       "requests_per_replica": len(requests)}
    report["compile_cache"] = compile_cache.stats()
    with open(os.path.join(WORK, "multichip.json"), "w") as fh:
        json.dump(report, fh)
    return 0


# -- every 2017 cell ROADMAP lists, at the cell's width ----------------------

def _model_costs():
    """name -> (builder of the cost layer, batch size, sequence length).
    The widths are benchmark/harness.py's (BASELINE.md, BASELINE.json)."""
    import __graft_entry__ as graft
    from paddle_tpu import data_type as dt
    from paddle_tpu import layer as L
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models import text, vision
    from paddle_tpu.models.recommender import wide_deep_ctr

    def image(fn, classes=1000, **kw):
        def build():
            out = getattr(vision, fn)(num_classes=classes, **kw)
            label = L.data(name="label", type=dt.integer_value(classes))
            return L.classification_cost(input=out, label=label)
        return build

    def tagging():
        scores = text.sequence_tagging_rnn(
            word_dict_size=30000, label_dict_size=67, emb_size=64,
            hidden=128)
        label = L.data(name="label", type=dt.integer_value_sequence(67))
        return L.crf(input=scores, label=label, name="tag_crf")

    models = {
        "lstm_h1280_bs64": (lambda: graft._flagship(hidden=1280)[3], 64, 100),
        "resnet50_bs64": (image("resnet", depth=50), 64, None),
        "alexnet_bs128": (image("alexnet"), 128, None),
        "googlenet_bs128": (image("googlenet"), 128, None),
        "tagging_bilstm_crf_bs32": (tagging, 32, 60),
        "nmt_attention_bs64": (lambda: text.seq2seq_attention(
            src_dict_size=30000, trg_dict_size=30000, emb_size=512,
            enc_size=512, dec_size=512)[0], 64, 30),
        "ctr_wide_deep_1m_bs512": (lambda: wide_deep_ctr(
            sparse_dim=1_000_000, field_dims=(1000, 1000, 100), emb=16,
            hidden=(64, 32))[2], 512, None),
    }

    def fresh(build):
        def wrapped():
            reset_name_counters()
            return build()
        return wrapped

    return {k: (fresh(b), bs, t) for k, (b, bs, t) in models.items()}


def synthetic_reader(topology, batch, seq_len, n_batches):
    """Samples for any topology, drawn from its data layers' types."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import data_type as dt

    def value(rng, itype):
        def one():
            if itype.value_type == dt.INDEX:
                return int(rng.randint(itype.dim))
            if itype.value_type == dt.DENSE:  # a 1-wide dense slot: a label
                return ((rng.rand(1) < 0.5).astype(np.float32)
                        if itype.dim == 1
                        else rng.randn(itype.dim).astype(np.float32))
            return rng.randint(0, itype.dim, 39).tolist()  # sparse ids
        if itype.seq_type == dt.SEQ_NONE:
            return one()
        return [one() for _ in range(seq_len)]

    def samples():
        rng = np.random.RandomState(SEED)
        for _ in range(n_batches * batch):
            yield tuple(value(rng, t) for _, t in topology.data_types())

    return paddle.batch(samples, batch)


def stage_models(args):
    device, env_report = open_device(args)
    import gc
    import traceback

    import numpy as np

    import paddle_tpu as paddle

    paddle.init(use_tpu=None if args.dry_run_cpu else True, seed=SEED,
                compute_dtype="bfloat16", matmul_precision="default")
    table = _model_costs()
    names = list(table) if args.models == "all" else args.models.split(",")
    report = dict(env_report, device=device, models={})
    path = os.path.join(WORK, "models.json")
    for name in names:
        build, batch, seq_len = table[name]
        if args.dry_run_cpu:
            batch = 2
        try:
            cost = build()
            params = paddle.parameters.create(cost)
            trainer = paddle.trainer.SGD(
                cost, params, paddle.optimizer.Momentum(
                    learning_rate=1e-3, momentum=0.9))
            costs = []
            trainer.train(
                synthetic_reader(trainer.topology, batch, seq_len, 3),
                num_passes=1, feed_pipeline=True,
                event_handler=cost_collector(costs))
            require(len(costs) == 3 and all(np.isfinite(costs)),
                    "losses %r", costs)
            report["models"][name] = "ran"
        except Exception:  # recorded per model; fails the run below
            report["models"][name] = ("did not run: "
                                      + traceback.format_exc()[-2500:])
        print("[chip_smoke] %s: %s" % (name, report["models"][name][:300]),
              flush=True)
        with open(path, "w") as fh:  # after every model: survives a crash
            json.dump(report, fh)
        del build
        cost = params = trainer = None
        gc.collect()
    failed = [n for n, r in report["models"].items() if r != "ran"]
    require(not failed, "models that did not run: %r", failed)
    return 0


STAGES = {"chip": stage_chip, "multichip": stage_multichip,
          "models": stage_models}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="fail unless this many TPU devices are visible; "
                         "above 1, run the data-parallel and replica paths")
    ap.add_argument("--models", default="",
                    help="'all' or a comma list: a few SGD.train steps of "
                         "each 2017 cell at its width, instead of the "
                         "default check")
    ap.add_argument("--expect-warm", action="store_true",
                    help="fail if the train step compiles instead of "
                         "coming out of the persistent cache (second run)")
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="debug the script at a tiny size on the CPU "
                         "backend; proves nothing about the chip")
    ap.add_argument("--stage", choices=sorted(STAGES), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        if args.stage:
            return STAGES[args.stage](args)
        return parent(args)
    except SmokeFailure as exc:
        print("[chip_smoke] FAILED: %s" % exc, file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
