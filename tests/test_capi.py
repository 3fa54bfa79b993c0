"""C inference API tests: build the shared lib + a real C client program and
run it against a saved model (reference pattern: paddle/capi/tests +
examples/model_inference run as part of CI)."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPI_DIR = os.path.join(REPO, "paddle_tpu", "capi")


def _build():
    subprocess.run(["make", "-C", CAPI_DIR], check=True, capture_output=True)
    subprocess.run(["make", "-C", CAPI_DIR, "example", "CC=gcc"], check=True,
                   capture_output=True)


@pytest.fixture(scope="module")
def capi_example(tmp_path_factory):
    _build()
    tmp = tmp_path_factory.mktemp("capi")
    params_tar = str(tmp / "params.tar")
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters

    reset_name_counters()
    out = mlp()
    params = Parameters.create(out)
    with open(params_tar, "wb") as f:
        params.to_tar(f)
    return params_tar, params, out


def test_c_program_runs_inference(capi_example):
    params_tar, params, out_layer = capi_example
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["LD_LIBRARY_PATH"] = CAPI_DIR
    proc = subprocess.run(
        [os.path.join(CAPI_DIR, "examples", "infer_dense"),
         "paddle_tpu.models.vision:mlp", params_tar, "784"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "C-API OK" in proc.stdout
    # C output must equal the Python inference on the same input
    row = [0.1 * (i % 10) for i in range(784)]
    import paddle_tpu as paddle

    expected = paddle.inference.infer(
        out_layer, params, [(np.asarray(row, np.float32),)])
    out_line = [l for l in proc.stdout.splitlines() if l.startswith("output")][0]
    got = np.array([float(v) for v in out_line.split(":")[1].split()])
    np.testing.assert_allclose(got, expected[0][:len(got)], rtol=1e-4)


def test_c_program_reports_bad_builder(capi_example):
    params_tar, _, _ = capi_example
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["LD_LIBRARY_PATH"] = CAPI_DIR
    proc = subprocess.run(
        [os.path.join(CAPI_DIR, "examples", "infer_dense"),
         "no.such.module:nope", params_tar, "784"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "No module named" in proc.stderr


@pytest.fixture(scope="module")
def capi_builders(tmp_path_factory):
    """Tiny sequence + sparse models saved for the C example programs,
    exposed via a throwaway module on PYTHONPATH (the builder spec is a
    'module:function' string resolved inside the embedded interpreter)."""
    _build()
    tmp = tmp_path_factory.mktemp("capi_models")
    (tmp / "capi_tiny_models.py").write_text(
        "from paddle_tpu import activation as A\n"
        "from paddle_tpu import data_type, layer as L, pooling\n"
        "from paddle_tpu.graph import reset_name_counters\n"
        "\n"
        "VOCAB = 20\n"
        "\n"
        "def seq_model():\n"
        "    reset_name_counters()\n"
        "    w = L.data(name='word', type=data_type.integer_value_sequence(VOCAB))\n"
        "    emb = L.embedding(input=w, size=8, name='tiny_emb')\n"
        "    pooled = L.pooling(input=emb, pooling_type=pooling.SumPooling())\n"
        "    return L.fc(input=pooled, size=3, act=A.Softmax(), name='tiny_out')\n"
        "\n"
        "def sparse_model():\n"
        "    reset_name_counters()\n"
        "    w = L.data(name='bow', type=data_type.sparse_binary_vector(VOCAB))\n"
        "    return L.fc(input=w, size=2, act=A.Softmax(), name='tiny_lr')\n")
    import importlib.util
    import jax

    spec = importlib.util.spec_from_file_location(
        "capi_tiny_models", str(tmp / "capi_tiny_models.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from paddle_tpu.parameters import Parameters

    tars = {}
    for fn_name in ("seq_model", "sparse_model"):
        out = getattr(mod, fn_name)()
        params = Parameters.create(out)
        tar = str(tmp / (fn_name + ".tar"))
        with open(tar, "wb") as f:
            params.to_tar(f)
        tars[fn_name] = tar
    return str(tmp), tars


def _run_example(name, builder, tar, pypath, vocab=20):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + pypath
    env["LD_LIBRARY_PATH"] = CAPI_DIR
    proc = subprocess.run(
        [os.path.join(CAPI_DIR, "examples", name), builder, tar, str(vocab)],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "C-API OK" in proc.stdout, proc.stdout
    return proc.stdout


def test_c_sequence_inference_example(capi_builders):
    """≙ capi/examples/model_inference/sequence: flat ids + start
    positions through pt_model_forward_ids; softmax rows sum to 1."""
    pypath, tars = capi_builders
    out = _run_example("infer_sequence", "capi_tiny_models:seq_model",
                       tars["seq_model"], pypath)
    line = [l for l in out.splitlines() if l.startswith("output")][0]
    rows = line.split(":")[1].split("|")
    assert len(rows) == 2
    for r in rows:
        vals = [float(v) for v in r.split()]
        assert abs(sum(vals) - 1.0) < 1e-3, vals


def test_c_sparse_binary_inference_example(capi_builders):
    """≙ capi/examples/model_inference/sparse_binary: CSR bag-of-words
    through pt_model_forward_sparse_binary, checked against the Python
    inference on the densified rows."""
    import numpy as np

    pypath, tars = capi_builders
    out = _run_example("infer_sparse", "capi_tiny_models:sparse_model",
                       tars["sparse_model"], pypath)
    line = [l for l in out.splitlines() if l.startswith("output")][0]
    rows = [[float(v) for v in r.split()] for r in line.split(":")[1].split("|")]
    assert len(rows) == 2 and len(rows[0]) == 2
    # python-side reference on the same CSR rows
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "capi_tiny_models2", os.path.join(pypath, "capi_tiny_models.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from paddle_tpu.parameters import Parameters
    import paddle_tpu as paddle

    out_layer = mod.sparse_model()
    with open(tars["sparse_model"], "rb") as f:
        params = Parameters.from_tar(f)
    expected = paddle.inference.infer(
        out_layer, params, [([1, 5, 7],), ([0, 2],)])
    np.testing.assert_allclose(np.asarray(rows), expected, rtol=1e-4,
                               atol=1e-5)


# -- bundle-backed inference (docs/serving.md, Python-free path) -------------

@pytest.fixture(scope="module")
def capi_bundle(capi_example, tmp_path_factory):
    """The same MLP exported as an AOT serve bundle: the C client loads
    it by passing the bundle DIRECTORY where the params tar would go and
    an empty builder — the embedded Python side then does pure
    deserialization, no topology/layer-graph construction."""
    params_tar, params, out_layer = capi_example
    tmp = tmp_path_factory.mktemp("capi_bundle")
    from paddle_tpu.serve.export import export_bundle

    bundle_dir = str(tmp / "mlp_bundle")
    export_bundle(out_layer, params, bundle_dir, batch_sizes=(1,),
                  name="capi_mlp")
    return bundle_dir


def test_c_program_bundle_inference_equivalence(capi_example, capi_bundle):
    """The unchanged infer_dense C binary drives the exported MNIST
    dense bundle (empty builder + bundle dir) and matches both the live
    Python inference and the tar-backed C run."""
    params_tar, params, out_layer = capi_example
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["LD_LIBRARY_PATH"] = CAPI_DIR
    proc = subprocess.run(
        [os.path.join(CAPI_DIR, "examples", "infer_dense"),
         "", capi_bundle, "784"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "C-API OK" in proc.stdout
    row = [0.1 * (i % 10) for i in range(784)]
    import paddle_tpu as paddle

    expected = paddle.inference.infer(
        out_layer, params, [(np.asarray(row, np.float32),)])
    out_line = [l for l in proc.stdout.splitlines()
                if l.startswith("output")][0]
    got = np.array([float(v) for v in out_line.split(":")[1].split()])
    np.testing.assert_allclose(got, expected[0][:len(got)], rtol=1e-4,
                               atol=1e-6)
