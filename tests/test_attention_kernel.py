"""`ops/pallas_attention.py`: blockwise attention's fused form against its
plain one (`ops/attention.py`), in Pallas interpret mode on the CPU with
tiles of 32 positions; which tiles the kernels visit and what
`attention_kernel_cost` counts over them; the gauges that count a traced
step's attention calls by form; and that a process without attention
never imports the kernels' module."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import layer as L
from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.ops import attention as attention_ops
from paddle_tpu.ops import pallas_attention
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.topology import Topology

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def small_tiles(monkeypatch):
    """Tiles of 32 positions under the interpret flag: a few dozen rows
    make several tiles, so diagonals, window edges and lengths cross
    them. (The tile is a static argument of the jitted calls.)"""
    monkeypatch.setattr(pallas_attention, "_TILE", 32)
    monkeypatch.setattr(pk, "_INTERPRET", True)


def _operands(b, t, heads, kv, d, dv, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, t, heads, d), dtype),
            jax.random.normal(ks[1], (b, t, kv, d), dtype),
            jax.random.normal(ks[2], (b, t, kv, dv), dtype),
            jax.random.normal(ks[3], (b, t, heads, dv), dtype))


def _output_and_grads(q, k, v, g, scale, causal, lengths, window):
    def total(q, k, v):
        out = attention_ops.blockwise_attention(q, k, v, scale, causal,
                                                lengths, 16, window)
        return jnp.sum((out * g).astype(jnp.float32)), out

    (_, out), grads = jax.value_and_grad(total, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return out, grads


# b, t, heads, kv heads, d, dv, causal, window, lengths, dtype
CASES = [
    (2, 96, 2, 2, 64, 64, True, None, None, "float32"),          # groups 1
    (2, 80, 4, 2, 64, 128, True, 32, (80, 37), "float32"),       # phi's pairs
    (2, 96, 8, 2, 64, 64, True, None, (96, 50), "float32"),      # groups 4
    (1, 72, 6, 1, 128, 128, True, 32, (61,), "float32"),         # groups 6
    (2, 64, 8, 1, 128, 128, True, None, (64, 20), "float32"),    # groups 8
    (1, 64, 2, 1, 64, 64, False, None, (40,), "float32"),        # not causal
    (2, 96, 8, 2, 128, 128, True, 32, (96, 70), "bfloat16"),
]


@pytest.mark.parametrize("b,t,heads,kv,d,dv,causal,window,lengths,dtype",
                         CASES)
def test_the_kernels_are_the_plain_form(b, t, heads, kv, d, dv, causal,
                                        window, lengths, dtype, small_tiles,
                                        monkeypatch):
    """Output and q, k, v gradients against the plain key-block scans:
    query-to-key-value groups of 1, 2, 4, 6 and 8, heads of 64 and 128,
    values twice as wide as keys (phi's differential pairs), causal with
    and without a window, not causal, lengths, positions that are no
    multiple of the tile, and bfloat16 operands. The cotangent is zero
    past a row's length, as the loss makes it; outputs are compared where
    a query has a key it may see."""
    dtype = jnp.dtype(dtype)
    q, k, v, g = _operands(b, t, heads, kv, d, dv, dtype)
    lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    valid = jnp.ones((b, t), bool) if lens is None \
        else jnp.arange(t)[None] < lens[:, None]
    g = g * valid[:, :, None, None].astype(dtype)
    assert attention_ops.attention_form(heads, kv, d, dv) == "fused"
    got = _output_and_grads(q, k, v, g, 0.3, causal, lens, window)
    monkeypatch.setattr(pk, "_INTERPRET", False)
    assert attention_ops.attention_form(heads, kv, d, dv) == "plain"
    want = _output_and_grads(q, k, v, g, 0.3, causal, lens, window)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2

    def close(a, c):
        a, c = np.asarray(a, np.float32), np.asarray(c, np.float32)
        np.testing.assert_allclose(a, c, rtol=tol,
                                   atol=tol * np.max(np.abs(c)))

    keep = valid[:, :, None, None]
    close(jnp.where(keep, got[0], 0), jnp.where(keep, want[0], 0))
    for a, c in zip(got[1], want[1]):
        close(a, c)


def _pallas_grids(fn, *args):
    """{call name: grid} of the Pallas calls in ``fn``'s jaxpr."""
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = \
                    eqn.params["grid_mapping"].grid
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else [p]:
                    if hasattr(sub, "jaxpr"):
                        walk(getattr(sub.jaxpr, "jaxpr", sub.jaxpr))
                    elif hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.parametrize("t,causal,window", [
    (4096, True, None), (4096, True, 512), (4096, False, None),
    (1000, True, 300)])
def test_the_kernels_visit_the_key_blocks_pairs(t, causal, window,
                                                monkeypatch):
    """One grid step a (query tile, key tile) pair of `key_blocks` (36 at
    laguna's full layers, 15 at its window layers), in the forward and
    the dq kernel for every query head, in the dk/dv kernel for every
    key-value head and each query head of its group; no Python loop over
    tiles traces more than one call a direction."""
    monkeypatch.setattr(pk, "_INTERPRET", True)
    size = pallas_attention.tile(t)
    padded = -(-t // size) * size
    want = sum(end - first for first, end in attention_ops.key_blocks(
        padded, size, causal, window))
    assert want == {(4096, None): 36 if causal else 64,
                    (4096, 512): 15, (1000, 300): 3}[t, window]
    b, heads, kv = 2, 16, 4
    q, k, v, g = (jax.ShapeDtypeStruct((b, t, n, 128), jnp.bfloat16)
                  for n in (heads, kv, kv, heads))

    def grads(q, k, v, g):
        return jax.grad(lambda *a: jnp.sum((pallas_attention.attention(
            *a, 0.1, causal, None, window) * g).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    assert _pallas_grids(grads, q, k, v, g) == {
        "attention_fwd": (b, heads, want),
        "attention_bwd_dq": (b, heads, want),
        "attention_bwd_dkv": (b, kv, want * heads // kv)}


def test_the_cost_counts_the_tiles_the_kernels_visit():
    """`attention_kernel_cost` at laguna's full layer (4 rows of 3,072 to
    4,096 positions, 48 query heads over 8 of 128): the tiles on or under
    the diagonal that hold a key under the row's length, 140 of a head's
    4 x 36, counted here tile by tile."""
    lengths = [4096, 3072, 3584, 4096]
    tiles = sum(1 for n in lengths for i in range(8) for j in range(i + 1)
                if j * 512 < n)
    assert tiles == 140
    cost = pallas_attention.attention_kernel_cost(4, 4096, 48, 8, 128, 128,
                                                  True, None, lengths)
    product = 2 * 512 * 512 * 128
    assert cost["attention_fwd"]["flops"] == 48 * tiles * 2 * product
    assert cost["attention_bwd_dq"]["flops"] == 48 * tiles * 3 * product
    assert cost["attention_bwd_dkv"]["flops"] == 48 * tiles * 4 * product
    # whole rows: the pairs of key_blocks, 36 a head and row
    whole = pallas_attention.attention_kernel_cost(4, 4096, 48, 8, 128, 128)
    assert whole["attention_fwd"]["flops"] == 4 * 48 * 36 * 2 * product
    # a query tile once, a key and a value tile once a visit, the output
    # once, the float32 log-sum-exp once
    seq = 4 * 48 * 4096
    assert cost["attention_fwd"]["bytes"] == 2 * (
        seq * 256 + 48 * tiles * 512 * 256) + 4 * seq
    window = pallas_attention.attention_kernel_cost(
        4, 4096, 64, 8, 128, 128, True, 512, lengths)
    # window layers: 15 pairs a row, less the key tiles past 3,072 (2 + 1
    # pairs) and past 3,584 (1)
    assert window["attention_fwd"]["flops"] == 64 * (15 + 12 + 14 + 15) \
        * 2 * product


def test_key_tiles_past_the_length_are_not_visited(small_tiles):
    """Keys and values of the tiles wholly past a row's length are NaN:
    the kernels never read them (the plain scans, which mask them, would
    multiply a NaN by zero), so the output of the valid rows and every
    gradient stay finite."""
    q, k, v, g = _operands(2, 128, 4, 2, 64, 64, jnp.float32)
    lengths = jnp.asarray([128, 40], jnp.int32)
    past = (jnp.arange(128)[None] >= 64) & (jnp.arange(2)[:, None] == 1)
    k, v = (jnp.where(past[:, :, None, None], jnp.nan, a) for a in (k, v))
    g = g * (jnp.arange(128)[None] < lengths[:, None])[:, :, None, None]
    out, grads = _output_and_grads(q, k, v, g, 0.3, True, lengths, None)
    assert np.isfinite(np.asarray(out[1, :40])).all()
    assert np.isfinite(np.asarray(out[0])).all()
    assert all(np.isfinite(np.asarray(a[:, :64])).all() for a in grads[1:])
    assert np.isfinite(np.asarray(grads[0])).all()


def _attention_gauges():
    gauges = observe_metrics.get_registry().snapshot()["gauges"]
    return (gauges["paddle_tpu_attention_fused"],
            gauges["paddle_tpu_attention_plain"])


@pytest.mark.parametrize("head_dim,interpret,want", [
    (64, True, (2, 0)), (64, False, (0, 2)), (16, True, (0, 2))])
def test_the_gauges_count_attention_calls_by_form(head_dim, interpret,
                                                  want, monkeypatch):
    """A full layer and a window layer traced for training: with Pallas
    at hand and heads of 64 both take the kernels; on the plain CPU, or at
    a head width the kernels do not take, both the plain scans."""
    from paddle_tpu import data_type

    monkeypatch.setattr(pk, "_INTERPRET", interpret)
    L.reset_name_counters()
    x = L.data(name="x", type=data_type.dense_vector_sequence(32))
    full = L.gqa_attention(input=x, heads=4, kv_heads=2, head_dim=head_dim,
                           name="f")
    topo = Topology(L.gqa_attention(input=full, heads=2, kv_heads=1,
                                    head_dim=head_dim, window=8, name="w"))
    params = topo.init_params(jax.random.PRNGKey(0))
    feed = SequenceBatch(jax.random.normal(jax.random.PRNGKey(1),
                                           (2, 16, 32)),
                         jnp.asarray([16, 9], jnp.int32))
    jax.eval_shape(lambda p: topo.apply(p, {"x": feed}, mode="train"),
                   params)
    assert _attention_gauges() == want


RESNET_STEP = """
import json, sys
import paddle_tpu as paddle
from chipbench import traffic
from chipbench.models import resnet50
from paddle_tpu.topology import convert_feed

tiny = "tests/chipbench/tiny/"
cfg = json.load(open(tiny + "configs/resnet50.json"))
cell = json.load(open(tiny + "workloads/resnet50-bs256-train.json"))
paddle.init(use_tpu=False, seed=7, compute_dtype="bfloat16",
            matmul_precision="default", default_dtype="float32",
            trap_fpe=False)
cost = resnet50.build(cfg)
trainer = paddle.trainer.SGD(
    cost, paddle.parameters.create(cost),
    paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9))
feed = convert_feed(trainer.topology, traffic.make_pool(
    cfg["inputs"], cell, 7)[0])
trainer._train_step.lower(
    trainer._trainable, trainer._replica, trainer._static, trainer._state,
    trainer._opt_state, feed, trainer._rng)
print(json.dumps(sorted(m for m in sys.modules
                        if m.startswith("paddle_tpu.ops."))))
"""


def test_a_process_without_attention_never_imports_the_kernels():
    """ResNet-50's train step, built and lowered at the tiny preset in a
    process of its own, loads the modules it loaded before the kernels
    existed and not `ops/pallas_attention.py`: the module is imported by
    the first attention call that could take it, and does nothing at
    import."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    done = subprocess.run([sys.executable, "-c", RESNET_STEP], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    loaded = json.loads(done.stdout.strip().splitlines()[-1])
    assert "paddle_tpu.ops.pallas_attention" not in loaded
    assert "paddle_tpu.ops.attention" in loaded
