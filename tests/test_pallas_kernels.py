"""Fused Pallas LSTM kernel vs the lax.scan reference path — the
CPU-vs-accelerator equivalence pattern (reference: Compare2Function,
paddle/function/FunctionTest.h; hl_cuda_lstm.cu vs CPU LstmCompute).
Runs the kernels in interpret mode on CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops import rnn as rnn_ops

pytestmark = pytest.mark.skipif(
    not pk.available(),
    reason="PADDLE_TPU_DISABLE_PALLAS is set; the fused path on the chip "
           "itself is checked by chip_smoke.py")

@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    """Force the fused pallas path in interpret mode on CPU — without this
    enabled() falls back to lax.scan off-TPU and the fused-vs-scan
    comparisons would compare the scan path against itself."""
    monkeypatch.setattr(pk, "_INTERPRET", True)


B, T, H = 4, 6, 64


def _inputs(seed=0):
    rng = np.random.RandomState(seed)
    gates = jnp.asarray(rng.randn(B, T, 4 * H) * 0.5, jnp.float32)
    lengths = np.array([6, 3, 5, 1])
    mask = jnp.asarray((np.arange(T)[None, :] < lengths[:, None]),
                       jnp.float32)
    w = jnp.asarray(rng.randn(H, 4 * H) / np.sqrt(H), jnp.float32)
    return gates, mask, w


def _scan_path(gates, mask, w):
    return rnn_ops.lstm_scan(gates, mask, w_in=None, b=None, w_rec=w,
                             standard_acts=False)


def _fused_path(gates, mask, w):
    return rnn_ops.lstm_scan(gates, mask, w_in=None, b=None, w_rec=w,
                             standard_acts=True)


def test_lstm_fused_forward_matches_scan():
    gates, mask, w = _inputs()
    h_ref, (hf_ref, cf_ref) = _scan_path(gates, mask, w)
    h_fus, (hf_fus, cf_fus) = _fused_path(gates, mask, w)
    np.testing.assert_allclose(np.asarray(h_fus), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf_fus), np.asarray(hf_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cf_fus), np.asarray(cf_ref),
                               rtol=1e-5, atol=1e-5)


def test_lstm_fused_grads_match_scan():
    gates, mask, w = _inputs(1)
    proj = jnp.asarray(np.random.RandomState(9).randn(B, T, H), jnp.float32)
    proj_f = jnp.asarray(np.random.RandomState(10).randn(B, H), jnp.float32)

    def loss(path, gates, w):
        h_seq, (h_f, c_f) = path(gates, mask, w)
        return (jnp.sum(h_seq * proj) + jnp.sum(h_f * proj_f)
                + 0.5 * jnp.sum(c_f * proj_f))

    g_ref = jax.grad(lambda g, w: loss(_scan_path, g, w), argnums=(0, 1))(
        gates, w)
    g_fus = jax.grad(lambda g, w: loss(_fused_path, g, w), argnums=(0, 1))(
        gates, w)
    np.testing.assert_allclose(np.asarray(g_fus[0]), np.asarray(g_ref[0]),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(g_fus[1]), np.asarray(g_ref[1]),
                               rtol=2e-4, atol=2e-5)


def test_lstm_fused_reverse_matches_scan():
    gates, mask, w = _inputs(2)
    h_ref, _ = rnn_ops.lstm_scan(gates, mask, None, None, w, reverse=True,
                                 standard_acts=False)
    h_fus, _ = rnn_ops.lstm_scan(gates, mask, None, None, w, reverse=True,
                                 standard_acts=True)
    np.testing.assert_allclose(np.asarray(h_fus), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


def test_lstmemory_layer_uses_fused_and_matches():
    """End to end through the layer: default activations trigger the fused
    kernel; exotic activations fall back — both paths must agree when the
    math is the same."""
    import paddle_tpu as paddle
    from paddle_tpu import layer as L, data_type as dt
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.topology import Topology

    rng = np.random.RandomState(3)
    seqs = [rng.randn(l, 4 * H).astype(np.float32) for l in (5, 2, 6)]
    sb = SequenceBatch.from_sequences(seqs, max_len=T)
    feed = {"xs": sb}

    reset_name_counters()
    xs = L.data(name="xs", type=dt.dense_vector_sequence(4 * H))
    lstm = L.lstmemory(input=xs, size=H, name="m")  # default acts -> fused
    topo = Topology(lstm)
    params = topo.init_params(jax.random.PRNGKey(0))
    vals, _ = topo.apply(params, feed, mode="test")
    got = np.asarray(vals["m"].data)

    # reference 7H bias layout (LstmLayer.cpp:32): gates then peep checks
    assert params["m.wbias"].shape == (7 * H,)
    gates = sb.data + params["m.wbias"][:4 * H]
    want, _ = rnn_ops.lstm_scan(gates, sb.mask(jnp.float32), None, None,
                                params["m.w0"], standard_acts=False,
                                use_peephole=True,
                                w_peep=params["m.wbias"][4 * H:])
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hidden", [H, 256])  # 256 -> tiled kernel
def test_lstm_fused_peephole_matches_scan_and_grads(hidden):
    """Nonzero peephole checks: fused kernel (resident AND tiled) vs
    lax.scan, forward + grads for gates, w_rec AND the peephole vectors
    (hl_lstm_ops parity)."""
    rng = np.random.RandomState(11)
    gates = jnp.asarray(rng.randn(B, T, 4 * hidden) * 0.5, jnp.float32)
    lengths = np.array([6, 3, 5, 1])
    mask = jnp.asarray((np.arange(T)[None, :] < lengths[:, None]),
                       jnp.float32)
    w = jnp.asarray(rng.randn(hidden, 4 * hidden) / np.sqrt(hidden),
                    jnp.float32)
    peep = jnp.asarray(rng.randn(3 * hidden) * 0.5, jnp.float32)
    proj = jnp.asarray(rng.randn(B, T, hidden), jnp.float32)

    def loss(standard, gates, w, peep):
        h_seq, (h_f, c_f) = rnn_ops.lstm_scan(
            gates, mask, None, None, w, standard_acts=standard,
            use_peephole=True, w_peep=peep)
        return jnp.sum(h_seq * proj) + jnp.sum(h_f) + 0.5 * jnp.sum(c_f)

    ref, gref = jax.value_and_grad(
        lambda *a: loss(False, *a), argnums=(0, 1, 2))(gates, w, peep)
    fus, gfus = jax.value_and_grad(
        lambda *a: loss(True, *a), argnums=(0, 1, 2))(gates, w, peep)
    np.testing.assert_allclose(float(fus), float(ref), rtol=1e-5)
    for got, want, nm in zip(gfus, gref, ("dgates", "dw", "dpeep")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-5, err_msg=nm)


def test_lstm_tiled_forward_and_grads_match_scan():
    """H=256 routes to the hidden-column-tiled kernel under interpret mode
    (pk.lstm_mode); must match lax.scan forward and gradients."""
    rng = np.random.RandomState(4)
    b, t, h = 4, 5, 256
    assert pk.lstm_mode(b, h, jnp.float32) == "tiled"
    gates = jnp.asarray(rng.randn(b, t, 4 * h) * 0.3, jnp.float32)
    lengths = np.array([5, 2, 4, 1])
    mask = jnp.asarray((np.arange(t)[None, :] < lengths[:, None]),
                       jnp.float32)
    w = jnp.asarray(rng.randn(h, 4 * h) / np.sqrt(h), jnp.float32)
    proj = jnp.asarray(rng.randn(b, t, h), jnp.float32)
    pf = jnp.asarray(rng.randn(b, h), jnp.float32)

    def loss(path, gates, w):
        h_seq, (h_f, c_f) = path(gates, mask, w)
        return (jnp.sum(h_seq * proj) + jnp.sum(h_f * pf)
                + 0.5 * jnp.sum(c_f * pf))

    h_ref, (hf_ref, cf_ref) = _scan_path(gates, mask, w)
    h_fus, (hf_fus, cf_fus) = _fused_path(gates, mask, w)
    np.testing.assert_allclose(np.asarray(h_fus), np.asarray(h_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(cf_fus), np.asarray(cf_ref),
                               rtol=1e-4, atol=1e-4)
    g_ref = jax.grad(lambda g, w: loss(_scan_path, g, w), argnums=(0, 1))(
        gates, w)
    g_fus = jax.grad(lambda g, w: loss(_fused_path, g, w), argnums=(0, 1))(
        gates, w)
    np.testing.assert_allclose(np.asarray(g_fus[0]), np.asarray(g_ref[0]),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(g_fus[1]), np.asarray(g_ref[1]),
                               rtol=2e-3, atol=2e-3)


def test_lstm_fused_bf16_tracks_f32():
    """bfloat16 inputs (mixed-precision policy) stay on the fused path and
    track the f32 scan within bf16 tolerance."""
    gates, mask, w = _inputs(5)
    h_ref, (hf_ref, cf_ref) = _scan_path(gates, mask, w)
    h_bf, (hf_bf, cf_bf) = _fused_path(gates.astype(jnp.bfloat16), mask,
                                       w.astype(jnp.bfloat16))
    assert h_bf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(h_bf, np.float32),
                               np.asarray(h_ref), rtol=0.1, atol=0.05)
    np.testing.assert_allclose(np.asarray(cf_bf, np.float32),
                               np.asarray(cf_ref), rtol=0.1, atol=0.08)


def _gru_scan_path(proj, mask, w_rz, w_c, fused):
    import paddle_tpu.ops.pallas_kernels as _pk

    old = _pk.gru_mode
    if not fused:
        _pk.gru_mode = lambda *a: None
    try:
        return rnn_ops.gru_scan(proj, mask, None, None, w_rz, w_c)
    finally:
        _pk.gru_mode = old


def test_gru_fused_forward_and_grads_match_scan():
    rng = np.random.RandomState(6)
    b, t, h = 4, 6, 64
    proj = jnp.asarray(rng.randn(b, t, 3 * h) * 0.5, jnp.float32)
    lengths = np.array([6, 3, 5, 1])
    mask = jnp.asarray((np.arange(t)[None, :] < lengths[:, None]),
                       jnp.float32)
    w_rz = jnp.asarray(rng.randn(h, 2 * h) / np.sqrt(h), jnp.float32)
    w_c = jnp.asarray(rng.randn(h, h) / np.sqrt(h), jnp.float32)
    sel = jnp.asarray(rng.randn(b, t, h), jnp.float32)
    sf = jnp.asarray(rng.randn(b, h), jnp.float32)

    h_ref, hf_ref = _gru_scan_path(proj, mask, w_rz, w_c, fused=False)
    h_fus, hf_fus = _gru_scan_path(proj, mask, w_rz, w_c, fused=True)
    np.testing.assert_allclose(np.asarray(h_fus), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf_fus), np.asarray(hf_ref),
                               rtol=1e-5, atol=1e-5)

    def loss(fused, proj, w_rz, w_c):
        h_seq, h_f = _gru_scan_path(proj, mask, w_rz, w_c, fused)
        return jnp.sum(h_seq * sel) + jnp.sum(h_f * sf)

    g_ref = jax.grad(lambda *a: loss(False, *a), argnums=(0, 1, 2))(
        proj, w_rz, w_c)
    g_fus = jax.grad(lambda *a: loss(True, *a), argnums=(0, 1, 2))(
        proj, w_rz, w_c)
    for got, want in zip(g_fus, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_gru_fused_bf16_tracks_f32():
    """bfloat16 GRU stays on the fused path (mixed-precision policy) and
    tracks the f32 scan within bf16 tolerance."""
    rng = np.random.RandomState(8)
    b, t, h = 4, 6, 64
    proj = jnp.asarray(rng.randn(b, t, 3 * h) * 0.5, jnp.float32)
    lengths = np.array([6, 3, 5, 1])
    mask = jnp.asarray((np.arange(t)[None, :] < lengths[:, None]),
                       jnp.float32)
    w_rz = jnp.asarray(rng.randn(h, 2 * h) / np.sqrt(h), jnp.float32)
    w_c = jnp.asarray(rng.randn(h, h) / np.sqrt(h), jnp.float32)
    h_ref, hf_ref = _gru_scan_path(proj, mask, w_rz, w_c, fused=False)
    h_bf, hf_bf = _gru_scan_path(proj.astype(jnp.bfloat16), mask,
                                 w_rz.astype(jnp.bfloat16),
                                 w_c.astype(jnp.bfloat16), fused=True)
    assert h_bf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(h_bf, np.float32),
                               np.asarray(h_ref), rtol=0.1, atol=0.06)
    np.testing.assert_allclose(np.asarray(hf_bf, np.float32),
                               np.asarray(hf_ref), rtol=0.1, atol=0.06)

    sel = jnp.asarray(rng.randn(b, t, h), jnp.float32)

    def loss(fused, p, wrz, wc):
        h_seq, h_f = _gru_scan_path(p, mask, wrz, wc, fused)
        return (jnp.sum(h_seq.astype(jnp.float32) * sel)
                + jnp.sum(h_f.astype(jnp.float32)))

    g_ref = jax.grad(lambda *a: loss(False, *a), argnums=(0, 1, 2))(
        proj, w_rz, w_c)
    g_bf = jax.grad(lambda *a: loss(True, *a), argnums=(0, 1, 2))(
        proj.astype(jnp.bfloat16), w_rz.astype(jnp.bfloat16),
        w_c.astype(jnp.bfloat16))
    for got, want in zip(g_bf, g_ref):
        got32 = np.asarray(got, np.float32)
        want32 = np.asarray(want, np.float32)
        denom = max(1.0, float(np.abs(want32).max()))
        assert float(np.abs(got32 - want32).max()) / denom < 8e-2


# -- int8 dequant matmul (quantized serving bundles) --------------------------

def _int8_case(m=5, k=72, n=256, seed=3):
    from paddle_tpu.serve.quantize import quantize_int8

    rng = np.random.RandomState(seed)
    w = rng.randn(k, n).astype(np.float32) / np.sqrt(k)
    q, scale = quantize_int8(w)
    x = rng.randn(m, k).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(q), jnp.asarray(scale)


def test_int8_matmul_kernel_matches_xla_fallback(monkeypatch):
    """The Pallas int8-dot kernel and the XLA dequant-fused fallback
    must agree bit-for-bit at f32 (same dequant, same contraction
    order per column block)."""
    from paddle_tpu.utils import flags

    x, q, scale = _int8_case()
    monkeypatch.setattr(flags, "_values",
                        dict(flags._values, int8_matmul="off"))
    ref = pk.int8_matmul(x, q, scale)
    monkeypatch.setattr(flags, "_values",
                        dict(flags._values, int8_matmul="on"))
    assert pk._int8_matmul_take_kernel(x.shape[0], x.shape[1],
                                       q.shape[1], x.dtype)
    got = pk.int8_matmul(x, q, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5)
    # leading batch dims flatten through the kernel and reshape back
    x3 = jnp.reshape(jnp.concatenate([x, x]), (2,) + tuple(x.shape))
    got3 = pk.int8_matmul(x3, q, scale)
    assert got3.shape == (2, x.shape[0], q.shape[1])
    np.testing.assert_allclose(np.asarray(got3[0]), np.asarray(ref),
                               atol=1e-5)


def test_int8_matmul_gate_defaults_to_xla_path():
    """Default-safe dispatch (the ops/pallas_conv.py convention):
    ``auto`` fires only for (K, N) shapes with a recorded on-chip win —
    the gate ships empty, so the kernel never takes over untested."""
    assert pk._INT8_MEASURED_WINS == frozenset()
    assert not pk._int8_matmul_take_kernel(5, 72, 256, jnp.float32)
    # unsupported shapes refuse even when forced: N must be 128-aligned
    assert pk.int8_matmul_mode(5, 72, 100, jnp.float32) is None
    x, q, scale = _int8_case(n=256)
    out = pk.int8_matmul(x, q, scale)  # XLA dequant-fused path
    want = np.asarray(x) @ (np.asarray(q, np.float32)
                            * np.asarray(scale))
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_fused_scans_shard_over_the_batch_in_a_data_parallel_step(cell):
    """XLA cannot partition a Mosaic kernel, so under a multi-device mesh
    with a batch axis (core.mesh_scope.use_mesh, which a DataParallel step
    enters) the fused scans run under shard_map, each device on its own
    rows — and the weight gradients still sum over all of them. On the
    chip the unwrapped call does not even lower; chip_smoke.py --chips 4
    trains the LSTM flagship and a GRU tagger that way."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.mesh import build_mesh, use_mesh

    gates, mask, w = _inputs()
    if cell == "lstm":
        weights = (w,)

        def loss(x, w):
            h_seq, (h_f, c_f) = _fused_path(x, mask, w)
            return jnp.sum(h_seq ** 2) + jnp.sum(h_f) + jnp.sum(c_f)
    else:
        x, weights = gates[..., :3 * H], (w[:, :2 * H], w[:, 2 * H:3 * H])
        gates = x

        def loss(x, w_rz, w_c):
            h_seq, h_f = rnn_ops.gru_scan(x, mask, None, None, w_rz, w_c)
            return jnp.sum(h_seq ** 2) + jnp.sum(h_f)

    grad = jax.value_and_grad(loss, argnums=tuple(range(1 + len(weights))))
    want = jax.jit(grad)(gates, *weights)
    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])
    rows = jax.device_put(gates, NamedSharding(mesh, P("data")))
    with use_mesh(mesh, batch_axis="data"):
        lowered = jax.jit(grad).lower(rows, *weights)
        assert "manual" in lowered.as_text()  # the shard_map is there
        got = lowered.compile()(rows, *weights)
        # a batch the mesh does not divide stays on the scan path
        assert rnn_ops._per_device(pk.lstm_fused, 6, (1,), (1,)) \
            == (None, None)
    with use_mesh(mesh):  # and so does a mesh with no batch axis named
        assert rnn_ops._per_device(pk.lstm_fused, B, (1,), (1,)) \
            == (None, None)
        # (a fresh function: the batch axis is not in jit's cache key)
        assert "manual" not in jax.jit(lambda *a: grad(*a)).lower(
            rows, *weights).as_text()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
