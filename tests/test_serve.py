"""paddle_tpu.serve tests — AOT bundle export/reload + batching engine.

Covers the serving subsystem contract (docs/serving.md):

* export → reload numeric equivalence vs live ``Inference`` (atol 1e-5),
  including the acceptance check that a FRESH subprocess loads a bundle
  **without constructing the topology/layer graph** (an import blocker
  makes any graph import a hard failure) — dense MNIST MLP and the
  quick_start text-CNN model (marked ``slow``: subprocess-heavy).
* the dynamic-batching engine: flush-on-size, flush-on-deadline, bucket
  padding correctness, concurrent submitters, and the ``serve_batch`` /
  ``serve_request`` steplog records (schema-valid against
  tests/golden/steplog_schema.json) every served batch must emit.
* ``paddle_tpu.cli serve --selfcheck`` as the deployment smoke gate and
  the HTTP front end.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "steplog_schema.json")

# the subprocess side of the no-graph-rebuild acceptance check: any
# attempt to import the model-config/layer-graph machinery while loading
# and running the bundle is a hard ImportError
LOADER_SCRIPT = """\
import sys

FORBIDDEN = ("paddle_tpu.graph", "paddle_tpu.topology", "paddle_tpu.layer",
             "paddle_tpu.networks", "paddle_tpu.models", "paddle_tpu.config",
             "paddle_tpu.proto", "paddle_tpu.inference")


class GraphImportBlocker:
    def find_spec(self, name, path=None, target=None):
        if name in FORBIDDEN or any(name.startswith(f + ".")
                                    for f in FORBIDDEN):
            raise ImportError(
                "bundle loading must not rebuild the graph: import of %r"
                % name)
        return None


sys.meta_path.insert(0, GraphImportBlocker())

import numpy as np

from paddle_tpu.serve import load_bundle

bundle = load_bundle(sys.argv[1])
with np.load(sys.argv[2]) as data:
    inputs = {k: data[k] for k in data.files}
out = bundle.infer(inputs)
np.savez(sys.argv[3], **out)
print("LOADED_WITHOUT_GRAPH")
"""


def _subprocess_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.setdefault("PADDLE_TPU_LOG_LEVEL", "WARNING")
    return env


def _reload_in_subprocess(bundle_dir, inputs, tmp):
    in_npz = str(tmp / "inputs.npz")
    out_npz = str(tmp / "outputs.npz")
    np.savez(in_npz, **inputs)
    proc = subprocess.run(
        [sys.executable, "-c", LOADER_SCRIPT, bundle_dir, in_npz, out_npz],
        capture_output=True, text=True, env=_subprocess_env(), timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "LOADED_WITHOUT_GRAPH" in proc.stdout
    with np.load(out_npz) as data:
        return {k: data[k] for k in data.files}


def _mlp_bundle(tmp, batch_sizes=(1, 4)):
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.export import export_bundle

    reset_name_counters()
    out = mlp(hidden=(16, 8))
    params = Parameters.create(out)
    bundle_dir = str(tmp / "mlp_bundle")
    manifest = export_bundle(out, params, bundle_dir,
                             batch_sizes=batch_sizes, name="mnist_mlp")
    return bundle_dir, manifest, out, params


# -- bundle format / manifest ------------------------------------------------

def test_manifest_versioned_and_self_describing(tmp_path):
    from paddle_tpu.serve import is_bundle, load_bundle

    bundle_dir, manifest, _, _ = _mlp_bundle(tmp_path)
    assert manifest["format"] == "paddle_tpu-bundle-v1"
    assert manifest["version"] == 1
    assert manifest["framework"]["jax"]
    assert manifest["framework"]["paddle_tpu"]
    assert manifest["platforms"] == ["cpu"]
    assert manifest["inputs"] == [
        {"name": "pixel", "kind": "dense", "dim": 784, "dtype": "float32"}]
    assert manifest["outputs"] == [
        {"name": "mlp_out", "dtype": "float32", "shape_suffix": [10]}]
    assert [b["batch"] for b in manifest["buckets"]] == [1, 4]
    assert is_bundle(bundle_dir)
    assert not is_bundle(str(tmp_path))  # no manifest
    for bucket in manifest["buckets"]:
        assert os.path.exists(os.path.join(bundle_dir, bucket["artifact"]))
    bundle = load_bundle(bundle_dir)
    assert bundle.batch_sizes() == [1, 4] and bundle.max_batch() == 4


def test_bundle_bucket_selection_and_padding(tmp_path):
    from paddle_tpu.serve import load_bundle
    from paddle_tpu.serve.bundle import pad_rows

    bundle_dir, _, _, _ = _mlp_bundle(tmp_path, batch_sizes=(2, 8))
    bundle = load_bundle(bundle_dir)
    assert bundle.bucket_for(1)["batch"] == 2
    assert bundle.bucket_for(2)["batch"] == 2
    assert bundle.bucket_for(3)["batch"] == 8
    with pytest.raises(ValueError, match="largest exported bucket"):
        bundle.bucket_for(9)
    arr = np.arange(6, dtype=np.float32).reshape(3, 2)
    padded = pad_rows(arr, 5)
    assert padded.shape == (5, 2)
    np.testing.assert_array_equal(padded[3], arr[-1])  # replicated row
    np.testing.assert_array_equal(padded[:3], arr)
    with pytest.raises(ValueError):
        pad_rows(arr, 2)
    with pytest.raises(ValueError, match="empty"):
        pad_rows(np.zeros((0, 2), np.float32), 4)
    with pytest.raises(ValueError, match="empty"):
        bundle.infer({"pixel": np.zeros((0, 784), np.float32)})


def test_bundle_rejects_out_of_range_sequence_lengths(tmp_path):
    """Length values beyond the exported seq_len would silently ride the
    length mask and return plausible garbage — they must be rejected at
    the serving boundary (bundle.infer AND engine.submit)."""
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.text import text_classification_cnn
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve import InferenceEngine, load_bundle
    from paddle_tpu.serve.export import export_bundle

    reset_name_counters()
    out = text_classification_cnn(dict_size=20, emb_size=4, hidden=8)
    params = Parameters.create(out)
    bundle_dir = str(tmp_path / "seq_bundle")
    export_bundle(out, params, bundle_dir, batch_sizes=(2,), seq_len=6)
    bundle = load_bundle(bundle_dir)
    ids = np.zeros((1, 6), np.int32)
    good = bundle.infer({"word": ids, "word:lens": np.array([4], np.int32)})
    assert good["cnn_out"].shape == (1, 2)
    with pytest.raises(ValueError, match="seq_len"):
        bundle.infer({"word": ids, "word:lens": np.array([7], np.int32)})
    with InferenceEngine(bundle, max_latency_ms=5.0, warmup=False) as eng:
        with pytest.raises(ValueError, match="seq_len"):
            eng.submit({"word": ids, "word:lens": np.array([-1], np.int32)})


def test_bundle_infer_equals_live_inference_in_process(tmp_path):
    """In-process equivalence on the dense-regression model (the
    fit_a_line demo bundle shape): padded buckets must not change the
    sliced rows."""
    import paddle_tpu as paddle
    from paddle_tpu import data_type as dt
    from paddle_tpu import layer as L
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve import load_bundle
    from paddle_tpu.serve.export import export_bundle

    reset_name_counters()
    x = L.data(name="x", type=dt.dense_vector(13))
    pred = L.fc(input=x, size=1, act=None, name="reg_out")
    params = Parameters.create(pred)
    bundle_dir = str(tmp_path / "reg_bundle")
    export_bundle(pred, params, bundle_dir, batch_sizes=(4,),
                  name="fit_a_line")
    bundle = load_bundle(bundle_dir)
    feats = np.random.RandomState(3).randn(3, 13).astype(np.float32)
    got = bundle.infer({"x": feats})["reg_out"]
    want = paddle.inference.infer(pred, params, [(r,) for r in feats])
    assert got.shape == (3, 1)
    np.testing.assert_allclose(got, np.asarray(want).reshape(3, 1),
                               atol=1e-5)


def test_export_rejects_unexportable_sparse_input():
    from paddle_tpu import data_type as dt
    from paddle_tpu import layer as L
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.export import export_bundle
    from paddle_tpu.utils import flags

    reset_name_counters()
    dim = flags.get_flag("sparse_feed_threshold") + 1
    w = L.data(name="bow", type=dt.sparse_binary_vector(dim))
    out = L.fc(input=w, size=2, name="sp_out")
    params = Parameters.create(out)
    with pytest.raises(Exception, match="sparse"):
        export_bundle(out, params, "/tmp/never_written",
                      batch_sizes=(1,))


# -- acceptance: fresh-subprocess reload, no graph construction --------------

@pytest.mark.slow
def test_mnist_bundle_fresh_process_equivalence(tmp_path):
    """`cli export` on the dense MNIST demo model produces a bundle a
    fresh subprocess loads WITHOUT constructing the topology/layer graph
    (import blocker) and matches live inference (atol 1e-5)."""
    import paddle_tpu as paddle
    from paddle_tpu import cli
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters

    reset_name_counters()
    out = mlp()
    params = Parameters.create(out)
    params_tar = str(tmp_path / "params.tar")
    with open(params_tar, "wb") as f:
        params.to_tar(f)
    bundle_dir = str(tmp_path / "bundle")
    rc = cli.main(["export", "--builder", "paddle_tpu.models.vision:mlp",
                   "--params", params_tar, "-o", bundle_dir,
                   "--batch-sizes", "1,4"])
    assert rc == 0

    feats = np.random.RandomState(0).randn(3, 784).astype(np.float32)
    got = _reload_in_subprocess(bundle_dir, {"pixel": feats},
                                tmp_path)["mlp_out"]
    want = paddle.inference.infer(out, params, [(r,) for r in feats])
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.slow
def test_quick_start_text_bundle_fresh_process_equivalence(tmp_path):
    """The quick_start text-CNN model (sequence input): export with a
    fixed seq_len, reload in a graph-blocked subprocess, match live
    inference on same-length sequences (atol 1e-5)."""
    import paddle_tpu as paddle
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.text import text_classification_cnn
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.export import export_bundle

    reset_name_counters()
    T, vocab = 12, 50
    out = text_classification_cnn(dict_size=vocab, emb_size=8, hidden=16)
    params = Parameters.create(out)
    bundle_dir = str(tmp_path / "qs_bundle")
    manifest = export_bundle(out, params, bundle_dir, batch_sizes=(2,),
                             seq_len=T, name="quick_start_cnn")
    assert manifest["seq_len"] == T
    assert manifest["inputs"][0]["kind"] == "seq_index"

    rng = np.random.RandomState(1)
    ids = rng.randint(0, vocab, size=(2, T)).astype(np.int32)
    lens = np.full((2,), T, np.int32)
    got = _reload_in_subprocess(
        bundle_dir, {"word": ids, "word:lens": lens}, tmp_path)["cnn_out"]
    want = paddle.inference.infer(out, params, [(row.tolist(),)
                                                for row in ids])
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.slow
def test_cli_serve_selfcheck_smoke(tmp_path):
    """The deployment smoke gate: `cli serve --selfcheck <bundle>` in a
    fresh process loads, warms and runs one batch end to end."""
    bundle_dir, _, _, _ = _mlp_bundle(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "serve", bundle_dir,
         "--selfcheck"],
        capture_output=True, text=True, env=_subprocess_env(), timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"] is True
    assert result["outputs"]["mlp_out"] == [1, 10]
    assert result["stats"]["batches"] == 1


# -- engine: flush policy / padding / concurrency ----------------------------

@pytest.fixture(scope="module")
def engine_bundle(tmp_path_factory):
    from paddle_tpu.serve import load_bundle

    tmp = tmp_path_factory.mktemp("engine_bundle")
    bundle_dir, _, out, params = _mlp_bundle(tmp, batch_sizes=(1, 4, 8))
    return load_bundle(bundle_dir)


def _rows(n, seed=0):
    return {"pixel":
            np.random.RandomState(seed).randn(n, 784).astype(np.float32)}


def test_engine_flush_on_size(engine_bundle):
    """max_batch_size rows queued -> the batch launches immediately,
    long before the (deliberately huge) latency deadline."""
    from paddle_tpu.serve import InferenceEngine

    with InferenceEngine(engine_bundle, max_batch_size=4,
                         max_latency_ms=60_000.0) as eng:
        t0 = time.perf_counter()
        futures = [eng.submit(_rows(1, seed=i)) for i in range(4)]
        for f in futures:
            f.result(timeout=30)
        elapsed = time.perf_counter() - t0
        stats = eng.stats()
    assert elapsed < 30.0  # flushed on size, not after the 60s deadline
    assert stats["flush_on_size"] >= 1
    assert stats["requests"] == 4 and stats["rows"] == 4


def test_engine_flush_on_deadline(engine_bundle):
    """A partial batch launches once the oldest request has waited
    max_latency_ms, without ever reaching max_batch_size."""
    from paddle_tpu.serve import InferenceEngine

    with InferenceEngine(engine_bundle, max_batch_size=8,
                         max_latency_ms=30.0) as eng:
        f1 = eng.submit(_rows(1, seed=0))
        f2 = eng.submit(_rows(2, seed=1))
        r1 = f1.result(timeout=30)
        r2 = f2.result(timeout=30)
        stats = eng.stats()
    assert r1["mlp_out"].shape == (1, 10)
    assert r2["mlp_out"].shape == (2, 10)
    assert stats["flush_on_deadline"] >= 1
    assert stats["flush_on_size"] == 0  # never reached 8 rows


def test_engine_bucket_padding_correctness(engine_bundle):
    """3 rows pad to the 4-bucket; the padding must not leak into the
    sliced results — engine output == direct bundle.infer == per-row."""
    from paddle_tpu.serve import InferenceEngine

    inputs = _rows(3, seed=7)
    direct = engine_bundle.infer(inputs)["mlp_out"]
    with InferenceEngine(engine_bundle, max_batch_size=8,
                         max_latency_ms=5.0) as eng:
        got = eng.infer(inputs, timeout=30)["mlp_out"]
        stats = eng.stats()
    assert got.shape == (3, 10)
    np.testing.assert_allclose(got, direct, atol=1e-6)
    assert stats["pad_rows"] == 1  # 3 rows -> bucket 4
    # per-row runs through the 1-bucket agree too (bucket choice is
    # numerically invisible)
    for i in range(3):
        one = engine_bundle.infer({"pixel": inputs["pixel"][i:i + 1]})
        np.testing.assert_allclose(one["mlp_out"][0], direct[i], atol=1e-6)


def test_engine_concurrent_submitters_and_steplog(engine_bundle,
                                                  tmp_path):
    """Acceptance: concurrent submitters sustain the engine, results are
    per-request correct, and EVERY served batch appears as a
    schema-valid serve_batch record (golden steplog schema v1)."""
    from paddle_tpu.observe import steplog
    from paddle_tpu.serve import InferenceEngine

    slog = steplog.StepLog(str(tmp_path), run_name="serve",
                           compile_events=False)
    n_threads, per_thread = 4, 6
    results, errors = {}, []
    with InferenceEngine(engine_bundle, max_batch_size=8,
                         max_latency_ms=4.0, steplog=slog) as eng:

        def client(tid):
            try:
                for i in range(per_thread):
                    inputs = _rows(1 + (tid + i) % 2,
                                   seed=100 * tid + i)
                    out = eng.infer(inputs, timeout=60)["mlp_out"]
                    want = engine_bundle.infer(inputs)["mlp_out"]
                    np.testing.assert_allclose(out, want, atol=1e-6)
                    results[(tid, i)] = out.shape[0]
            except Exception as exc:  # surfaced after join
                errors.append((tid, exc))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = eng.stats()
    slog.close()
    assert not errors, errors
    assert len(results) == n_threads * per_thread
    assert stats["requests"] == n_threads * per_thread

    golden = json.load(open(GOLDEN))
    records = steplog.read_jsonl(slog.path)
    batches = [r for r in records if r["type"] == "serve_batch"]
    reqs = [r for r in records if r["type"] == "serve_request"]
    assert len(batches) == stats["batches"]  # every batch recorded
    assert len(reqs) == stats["requests"]
    for rec in batches + reqs:
        spec = golden["record_types"][rec["type"]]
        keys = set(rec)
        assert set(spec["required"]) <= keys, rec
        assert not keys - set(spec["required"]) - set(spec["optional"]), rec
    for rec in batches:
        assert 1 <= rec["rows"] <= rec["bucket"] <= 8
        assert rec["infer_ms"] > 0
        assert rec["flush"] in ("size", "deadline", "drain")
    assert sum(r["rows"] for r in batches) == stats["rows"]


def test_engine_rejects_malformed_requests(engine_bundle):
    from paddle_tpu.serve import InferenceEngine

    with InferenceEngine(engine_bundle, max_batch_size=4,
                         max_latency_ms=5.0) as eng:
        with pytest.raises(KeyError, match="feed keys"):
            eng.submit({"wrong": np.zeros((1, 784), np.float32)})
        with pytest.raises(ValueError, match="max_batch_size"):
            eng.submit(_rows(5))
    with pytest.raises(ValueError, match="largest exported bucket"):
        InferenceEngine(engine_bundle, max_batch_size=64)
    # engine is stopped: no more submissions
    eng2 = InferenceEngine(engine_bundle, warmup=False)
    eng2.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        eng2.submit(_rows(1))


def test_engine_warmup_caches_every_bucket(engine_bundle):
    from paddle_tpu.serve import InferenceEngine

    engine_bundle._executables.clear()
    with InferenceEngine(engine_bundle, max_latency_ms=5.0,
                         warmup=True) as eng:
        assert set(engine_bundle._executables) == {1, 4, 8}
        eng.infer(_rows(2), timeout=30)


# -- HTTP front end ----------------------------------------------------------

def test_http_server_infer_and_health(engine_bundle):
    import urllib.request

    from paddle_tpu.serve import InferenceEngine
    from paddle_tpu.serve.server import serve_in_thread

    with InferenceEngine(engine_bundle, max_batch_size=4,
                         max_latency_ms=5.0) as eng:
        server, _ = serve_in_thread(engine_bundle, eng)
        host, port = server.server_address
        base = "http://%s:%d" % (host, port)
        try:
            health = json.load(urllib.request.urlopen(base + "/healthz",
                                                      timeout=30))
            assert health == {"ok": True, "live": True, "ready": True,
                              "bundle": "mnist_mlp"}
            x = np.random.RandomState(5).randn(2, 784).astype(np.float32)
            body = json.dumps({"inputs": {"pixel": x.tolist()}}).encode()
            req = urllib.request.Request(
                base + "/infer", data=body,
                headers={"Content-Type": "application/json"})
            resp = json.load(urllib.request.urlopen(req, timeout=60))
            got = np.asarray(resp["outputs"]["mlp_out"], np.float32)
            want = engine_bundle.infer({"pixel": x})["mlp_out"]
            np.testing.assert_allclose(got, want, atol=1e-4)
            stats = json.load(urllib.request.urlopen(base + "/stats",
                                                     timeout=30))
            assert stats["requests"] >= 1
            # malformed request -> 400, not a dead server
            bad = urllib.request.Request(
                base + "/infer", data=b'{"inputs": {"nope": [1]}}',
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(bad, timeout=30)
            assert exc_info.value.code == 400
        finally:
            server.shutdown()
