"""Sharded embedding tables (sparse / embedding-parallel parity).

Replaces (reference): the sparse-remote-update path — SparseRowCpuMatrix
family (paddle/math/SparseRowMatrix.h:29-299), SparseRemoteParameterUpdater
prefetch/push of touched rows (trainer/RemoteParameterUpdater.h:265), and
pserver getParameterSparse (pserver/ParameterServer2.h:510) which together
let embedding tables larger than one device live sharded across pservers.

TPU-native: the table is sharded over a mesh axis on its vocab dimension;
lookup is a shard_map gather — each device gathers rows it owns and a psum
combines partial results (rows are owned by exactly one shard, so the psum
just merges disjoint contributions riding the ICI). Gradients flow through
the same program reversed (scatter-add onto the owning shard), and the
optimizer update for the table runs sharded in place — the "sparse
optimizer on the pserver" with no pserver.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from paddle_tpu.utils.error import enforce


def table_sharding(mesh, axis):
    return NamedSharding(mesh, P(axis, None))


def sharded_lookup(table, ids, mesh, axis):
    """Gather rows of a vocab-sharded table. table [V, D] sharded on V over
    ``axis``; ids int32 [...] replicated. Returns [..., D] replicated."""
    axis_size = mesh.shape[axis]
    vocab = table.shape[0]
    enforce(vocab % axis_size == 0,
            "vocab %d must divide over mesh axis %s=%d", vocab, axis, axis_size)
    rows_per_shard = vocab // axis_size

    def local_gather(tbl_shard, ids_local):
        shard_idx = jax.lax.axis_index(axis)
        base = shard_idx * rows_per_shard
        local = ids_local - base
        in_shard = (local >= 0) & (local < rows_per_shard)
        safe = jnp.clip(local, 0, rows_per_shard - 1)
        rows = jnp.take(tbl_shard, safe, axis=0)
        rows = jnp.where(in_shard[..., None], rows, 0.0)
        return jax.lax.psum(rows, axis)

    return shard_map(
        local_gather,
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
    )(table, ids)


def sharded_embedding_layer(input, size, mesh, axis="model", name=None,
                            param_attr=None):
    """Graph-layer wrapper: an embedding whose table is vocab-sharded over
    ``axis``. Drop-in for layer.embedding when the table exceeds one chip
    (Wide&Deep CTR scale — the reference's distributed-embedding use case)."""
    from paddle_tpu.graph import auto_name
    from paddle_tpu.layer.base import make_node, weight_spec, featurewise

    name = name or auto_name("sharded_embedding")
    vocab = input.size
    spec = weight_spec(name, 0, (vocab, size), param_attr, fan_in=size)
    spec.sharding_hint = ("vocab", axis)

    def forward(params, values, ctx):
        table = params[spec.name]
        ids = values[0]
        return featurewise(
            lambda d: sharded_lookup(table, jnp.clip(d, 0, vocab - 1), mesh, axis),
            ids)

    return make_node("sharded_embedding", forward, [input], name=name,
                     size=size, param_specs=[spec])
