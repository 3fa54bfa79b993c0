"""Ring / Ulysses sequence-parallel attention vs the unsharded reference.

Pattern: CPU-reference-vs-accelerator equivalence (SURVEY.md §4 pattern 2 —
the reference's Compare2Function / TensorCheck tests), here single-device
full_attention vs 8-way sequence-sharded implementations, values AND grads.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel.mesh import build_mesh
from paddle_tpu.parallel.context_parallel import (
    SequenceParallel,
    full_attention,
    ring_attention,
    ulysses_attention,
)

B, L, H, D = 2, 32, 8, 16


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(7)
    mk = lambda: jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def seq_mesh():
    return build_mesh({"seq": 8})


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full(qkv, seq_mesh, causal):
    q, k, v = qkv
    ref = full_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, seq_mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(qkv, seq_mesh, causal):
    q, k, v = qkv
    ref = full_attention(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, seq_mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_gradients_match_full(qkv, seq_mesh, strategy):
    q, k, v = qkv
    sp = SequenceParallel(seq_mesh, strategy=strategy)

    def loss_sharded(q, k, v):
        return jnp.sum(sp(q, k, v, causal=True) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

    # jitted: an eager shard_map dispatches the unrolled ring op by op
    # (~45 s for this case on the CPU mesh); one program takes seconds
    g_sharded = jax.jit(jax.grad(loss_sharded, argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for gs, gf in zip(g_sharded, g_full):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gf),
                                   rtol=2e-4, atol=2e-4)


def test_ring_jits_under_mesh(qkv, seq_mesh):
    q, k, v = qkv
    sp = SequenceParallel(seq_mesh, strategy="ring")
    qs, ks, vs = sp.shard_sequence(q), sp.shard_sequence(k), sp.shard_sequence(v)
    fn = jax.jit(lambda a, b, c: sp(a, b, c, causal=True))
    out = fn(qs, ks, vs)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_lengths_mask_full_attention(qkv):
    q, k, v = qkv
    lengths = jnp.asarray([L, L // 2], jnp.int32)
    out = full_attention(q, k, v, lengths=lengths)
    # batch 1 must ignore keys >= L//2: perturbing them changes nothing
    k2 = k.at[1, L // 2:].add(100.0)
    v2 = v.at[1, L // 2:].add(100.0)
    out2 = full_attention(q, k2, v2, lengths=lengths)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(out2[1]),
                               rtol=1e-5, atol=1e-5)
