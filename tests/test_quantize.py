"""Quantized serving bundles (serve/quantize.py, docs/serving.md
"Quantized bundles").

Pins the int8 end-to-end chain:

* the per-output-channel symmetric int8 scheme itself (roundtrip error
  bound, zero-channel safety, scale shapes);
* parameter selection — matmul/conv weights quantize (fc native, conv
  via the top-of-forward dequant), biases/norm/embedding tables stay
  fp;
* ``Parameters.to_npz`` roundtrip for the mixed-dtype payload: int8
  tensors + f32 scale sidecars survive export -> load bit-exact;
* the ACCURACY GATE: quantized vs fp bundles on the mnist mlp and the
  quick_start text-CNN — argmax agreement + bounded logit drift — plus
  the capacity chain (manifest ``hbm_estimate_bytes`` shrinks >= 3x,
  ``replicas auto`` under a fixed ``PADDLE_TPU_HBM_BUDGET`` admits
  more replicas than fp);
* per-param-dtype HBM estimation (analyze/topology_check
  .estimate_hbm_bytes) pinned against live ``nbytes``;
* continuous batching unchanged on quantized bundles (decode carries
  stay full-precision);
* ``cli export --quantize int8`` + ``cli serve --selfcheck`` as the
  deployment smoke (slow: subprocess).
"""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the scheme --------------------------------------------------------------

def test_quantize_int8_roundtrip_error_bound():
    from paddle_tpu.serve.quantize import dequantize, quantize_int8

    rng = np.random.RandomState(0)
    w = rng.randn(64, 48).astype(np.float32)
    q, scale = quantize_int8(w)
    assert q.dtype == np.int8 and scale.dtype == np.float32
    assert q.shape == w.shape and scale.shape == (48,)
    # symmetric rounding: per-channel error bounded by half a step
    err = np.abs(dequantize(q, scale) - w)
    assert np.all(err <= scale / 2 + 1e-7)
    # channel scales track the channel maxima
    np.testing.assert_allclose(scale, np.abs(w).max(axis=0) / 127.0,
                               rtol=1e-6)


def test_quantize_int8_zero_channel_and_conv_rank():
    from paddle_tpu.serve.quantize import dequantize, quantize_int8

    w = np.zeros((8, 4), np.float32)
    w[:, 1] = np.linspace(-1, 1, 8)
    q, scale = quantize_int8(w)
    assert scale[0] == 1.0  # all-zero channel: dequant stays exact
    np.testing.assert_array_equal(dequantize(q, scale)[:, 0], 0.0)
    # conv-rank weights scale over the LAST (output-channel) axis
    w4 = np.random.RandomState(1).randn(3, 3, 4, 16).astype(np.float32)
    q4, s4 = quantize_int8(w4)
    assert q4.shape == w4.shape and s4.shape == (16,)
    assert np.abs(dequantize(q4, s4) - w4).max() <= s4.max() / 2 + 1e-7


# -- parameter selection -----------------------------------------------------

def test_quantizable_selection_mlp_and_cnn():
    """fc weights quantize NATIVE; biases never; embedding tables and
    recurrent cell weights stay fp; conv weights quantize non-native."""
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.text import text_classification_cnn
    from paddle_tpu.models.vision import lenet, mlp
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.quantize import quantizable_params
    from paddle_tpu.topology import Topology

    reset_name_counters()
    out = mlp(hidden=(16, 8))
    params = Parameters.create(out)
    chosen = quantizable_params(Topology(out), params)
    assert sorted(chosen) == ["mlp_fc0.w0", "mlp_fc1.w0", "mlp_out.w0"]
    assert all(info["native"] for info in chosen.values())

    reset_name_counters()
    cnn = text_classification_cnn(dict_size=30, emb_size=4, hidden=8)
    cp = Parameters.create(cnn)
    chosen = quantizable_params(Topology(cnn), cp)
    # the embedding table is 2D but its consumer is a gather, not a dot
    assert "cnn_emb.w0" not in chosen
    assert "cnn_conv_conv_fc.w0" in chosen and "cnn_out.w0" in chosen

    reset_name_counters()
    net = lenet()
    lp = Parameters.create(net)
    chosen = quantizable_params(Topology(net), lp)
    assert chosen["lenet_conv1.w0"] == {"native": False}  # conv: dequant
    assert chosen["lenet_fc1.w0"] == {"native": True}
    assert "lenet_conv1.wbias" not in chosen


# -- payload roundtrip (satellite: to_npz for non-f32 dtypes) ----------------

def test_parameters_npz_roundtrip_mixed_dtypes_bit_exact():
    """int8 tensors + f32 scale sidecars survive export -> load
    bit-exact through the bundle payload format (to_npz/np.load)."""
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.quantize import quantize_parameters, scale_name
    from paddle_tpu.topology import Topology

    reset_name_counters()
    out = mlp(hidden=(16, 8))
    params = Parameters.create(out)
    qparams, qmanifest = quantize_parameters(params, Topology(out))
    assert qmanifest["scheme"] == "int8-sym-perchannel"
    buf = io.BytesIO()
    qparams.to_npz(buf)
    buf.seek(0)
    with np.load(buf) as loaded:
        assert sorted(loaded.files) == qparams.names()
        for name in qparams.names():
            arr = np.asarray(qparams.get(name))
            assert loaded[name].dtype == arr.dtype, name
            np.testing.assert_array_equal(loaded[name], arr)
    # the quantized payload really is mixed-dtype
    w = np.asarray(qparams.get("mlp_fc0.w0"))
    s = np.asarray(qparams.get(scale_name("mlp_fc0.w0")))
    b = np.asarray(qparams.get("mlp_fc0.wbias"))
    assert w.dtype == np.int8 and s.dtype == np.float32
    assert b.dtype == np.float32  # biases stay fp


# -- per-param-dtype HBM estimation (satellite) ------------------------------

def test_estimate_hbm_per_param_dtypes_pinned_to_live_nbytes():
    """The spec-shape path takes a per-param dtype map instead of
    assuming f32 everywhere, and the exact (parameters=) path counts a
    mixed-dtype payload at live nbytes."""
    from paddle_tpu.analyze.topology_check import estimate_hbm_bytes
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.quantize import quantize_parameters
    from paddle_tpu.topology import Topology

    reset_name_counters()
    out = mlp(hidden=(16, 8))
    topo = Topology(out)
    params = Parameters.create(out)
    qparams, qmanifest = quantize_parameters(params, topo)

    # exact path: the resident params term IS the live nbytes sum
    est = estimate_hbm_bytes(topo, parameters=qparams, mode="infer")
    live = sum(int(np.asarray(qparams.get(n)).nbytes)
               for n in qparams.names())
    assert est["params"] == live

    # spec path, parameterized per-param dtype (int8 weights + their
    # scale sidecars, f32 biases): matches the live mixed payload
    dtypes = {name: "int8" for name in qmanifest["params"]}
    est_spec = estimate_hbm_bytes(topo, mode="infer", param_dtypes=dtypes)
    assert est_spec["params"] == live
    # and the old one-dtype-for-all assumption is gone: f32 default
    est_f32 = estimate_hbm_bytes(topo, mode="infer")
    assert est_f32["params"] > 3 * est_spec["params"]


def test_sparse_fc_int8_dequantizes_after_gather():
    """fc over SparseRows with an int8 weight: the gather picks K int8
    rows and dequantizes only those (core/sparse.py), with the
    per-output-channel scale applied to the result — numerically equal
    to the densified dequant matmul."""
    import jax.numpy as jnp

    from paddle_tpu.core.sparse import SparseRows
    from paddle_tpu.serve.quantize import dequantize, quantize_int8

    rng = np.random.RandomState(4)
    dim, size = 32, 6
    w = rng.randn(dim, size).astype(np.float32)
    q, scale = quantize_int8(w)
    rows = [[1, 5, 7], [0], [2, 2, 30]]
    sp = SparseRows.from_rows(rows, dim, with_values=False)
    got = np.asarray(sp.matmul(jnp.asarray(q))
                     * jnp.asarray(scale))
    dense = np.zeros((3, dim), np.float32)
    for i, ids in enumerate(rows):
        for j in ids:
            dense[i, j] += 1.0
    want = dense @ dequantize(q, scale)
    np.testing.assert_allclose(got, want, atol=1e-5)


# -- the accuracy gate + capacity chain --------------------------------------

def _quant_pair(tmp, build, name, **export_kwargs):
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.serve.export import export_bundle

    reset_name_counters()
    out = build()
    params = Parameters.create(out)
    fp_dir = str(tmp / (name + "_fp"))
    q_dir = str(tmp / (name + "_int8"))
    m_fp = export_bundle(out, params, fp_dir, name=name, **export_kwargs)
    m_q = export_bundle(out, params, q_dir, name=name + "_int8",
                        quantize="int8", **export_kwargs)
    return fp_dir, q_dir, m_fp, m_q


def test_quantized_mnist_mlp_accuracy_gate_and_hbm_shrink(tmp_path,
                                                          monkeypatch):
    """Tier-1 acceptance: the quantized mnist mlp bundle agrees with
    its fp twin (argmax agreement + bounded logit drift), its manifest
    hbm_estimate_bytes shrinks >= 3x, and under a fixed
    PADDLE_TPU_HBM_BUDGET ``--replicas auto`` admits more replicas."""
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.serve import load_bundle
    from paddle_tpu.serve.fleet import auto_replicas, replicas_that_fit

    fp_dir, q_dir, m_fp, m_q = _quant_pair(
        tmp_path, mlp, "mnist_mlp", batch_sizes=(1, 8))
    assert m_q["quantization"]["scheme"] == "int8-sym-perchannel"
    assert set(m_q["quantization"]["params"]) == {
        "mlp_fc0.w0", "mlp_fc1.w0", "mlp_out.w0"}

    bfp, bq = load_bundle(fp_dir), load_bundle(q_dir)
    assert bq.quantization and bfp.quantization is None
    x = np.random.RandomState(0).randn(8, 784).astype(np.float32)
    out_fp = bfp.infer({"pixel": x})["mlp_out"]
    out_q = bq.infer({"pixel": x})["mlp_out"]
    agree = float(np.mean(out_fp.argmax(1) == out_q.argmax(1)))
    assert agree >= 0.98, "argmax agreement %.3f" % agree
    assert np.abs(out_fp - out_q).max() <= 0.05

    # capacity chain: estimate shrink -> more replicas per budget
    shrink = m_fp["hbm_estimate_bytes"] / m_q["hbm_estimate_bytes"]
    assert shrink >= 3.0, "hbm estimate shrank only %.2fx" % shrink
    budget = 4 * m_fp["hbm_estimate_bytes"]
    fit_fp = replicas_that_fit(bfp, budget)
    fit_q = replicas_that_fit(bq, budget)
    assert fit_fp == 4 and fit_q > fit_fp
    monkeypatch.setenv("PADDLE_TPU_HBM_BUDGET", str(budget))
    auto_fp = auto_replicas(bfp, devices=[None])
    auto_q = auto_replicas(bq, devices=[None])
    assert auto_q > auto_fp, (
        "--replicas auto: int8 %d vs fp %d" % (auto_q, auto_fp))
    # without a budget, auto stays one-per-device
    monkeypatch.delenv("PADDLE_TPU_HBM_BUDGET")
    assert auto_replicas(bq, devices=[None, None]) == 2


def test_quantized_text_cnn_accuracy_gate(tmp_path):
    """The quick_start text-CNN side of the acceptance gate: sequence
    input, embedding stays fp, the two fc weights quantize."""
    from paddle_tpu.models.text import text_classification_cnn
    from paddle_tpu.serve import load_bundle

    T, vocab = 12, 50
    fp_dir, q_dir, _, m_q = _quant_pair(
        tmp_path, lambda: text_classification_cnn(
            dict_size=vocab, emb_size=8, hidden=16),
        "quick_start_cnn", batch_sizes=(4,), seq_len=T)
    assert "cnn_emb.w0" not in m_q["quantization"]["params"]

    bfp, bq = load_bundle(fp_dir), load_bundle(q_dir)
    rng = np.random.RandomState(1)
    ids = rng.randint(0, vocab, size=(4, T)).astype(np.int32)
    lens = np.array([T, 3, 7, 1], np.int32)
    out_fp = bfp.infer({"word": ids, "word:lens": lens})["cnn_out"]
    out_q = bq.infer({"word": ids, "word:lens": lens})["cnn_out"]
    agree = float(np.mean(out_fp.argmax(1) == out_q.argmax(1)))
    assert agree >= 0.98
    assert np.abs(out_fp - out_q).max() <= 0.05


def test_quantized_decode_bundle_streams_unchanged(tmp_path):
    """Continuous batching works unchanged on a quantized bundle: the
    decode carries stay full-precision, only the fc weights quantize,
    and the streamed outputs track the fp scheduler within the quant
    tolerance."""
    from paddle_tpu.models.text import sequence_tagging_gru
    from paddle_tpu.serve import ContinuousScheduler, load_bundle

    fp_dir, q_dir, m_fp, m_q = _quant_pair(
        tmp_path, lambda: sequence_tagging_gru(
            dict_size=40, label_size=8, emb_size=8, hidden=16),
        "tagger", batch_sizes=(2,), seq_len=8, decode_slots=(4,),
        decode_window=4)
    # carry spec identical: quantization never touches decode state
    assert m_q["decode"]["carry"] == m_fp["decode"]["carry"]

    bfp, bq = load_bundle(fp_dir), load_bundle(q_dir)
    rng = np.random.RandomState(2)
    seqs = [rng.randint(0, 40, size=(k,)).astype(np.int32)
            for k in (5, 8, 1, 3)]
    with ContinuousScheduler(bfp, warmup=True) as fp_sched, \
            ContinuousScheduler(bq, warmup=True) as q_sched:
        for seq in seqs:
            want = fp_sched.infer({"word": seq},
                                  timeout=300.0)["gru_tag_out"]
            got = q_sched.infer({"word": seq},
                                timeout=300.0)["gru_tag_out"]
            assert got.shape == want.shape
            assert np.abs(got - want).max() <= 0.05


# -- deployment smoke (cli export --quantize + serve --selfcheck) ------------

@pytest.mark.slow
def test_cli_export_quantize_and_selfcheck(tmp_path):
    """``cli export --quantize int8`` writes a quantized bundle a fresh
    ``cli serve --selfcheck`` process loads, warms and runs end to
    end."""
    from paddle_tpu import cli
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models.vision import mlp
    from paddle_tpu.parameters import Parameters

    reset_name_counters()
    out = mlp()  # the default shape the --builder below re-creates
    params = Parameters.create(out)
    params_tar = str(tmp_path / "params.tar")
    with open(params_tar, "wb") as f:
        params.to_tar(f)
    bundle_dir = str(tmp_path / "bundle_int8")
    rc = cli.main(["export", "--builder", "paddle_tpu.models.vision:mlp",
                   "--params", params_tar, "-o", bundle_dir,
                   "--batch-sizes", "1,4", "--quantize", "int8"])
    assert rc == 0
    with open(os.path.join(bundle_dir, "manifest.json")) as fh:
        manifest = json.load(fh)
    assert manifest["quantization"]["scheme"] == "int8-sym-perchannel"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.setdefault("PADDLE_TPU_LOG_LEVEL", "WARNING")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "serve", bundle_dir,
         "--selfcheck"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["ok"] is True
    assert result["outputs"]["mlp_out"] == [1, 10]
