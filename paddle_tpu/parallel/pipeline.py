"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

TPU-native successor to the reference's per-layer device placement
(ParallelNeuralNetwork.h:34,61-63 — layers pinned to devices, per-device
compute threads, dependency-driven dispatch). Here the "devices" are mesh
shards on a 'pipe' axis, each holding one stage's parameters; activations
flow stage-to-stage with neighbor ``ppermute`` over ICI while M microbatches
stream through, so all stages compute concurrently after the fill bubble
(T = M + N - 1 ticks).

Stages must be shape-homogeneous (activation shape in == out), the standard
constraint for stacked-block pipelines; heterogeneous head/tail layers run
outside the pipelined region.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from paddle_tpu.utils.error import enforce


def _pipeline_shard(params, xs, stage_fn, axis_name, n_stages):
    """Per-shard body. params: this stage's params (leading axis 1, from the
    'pipe'-sharded stack); xs: [M_local, mb, ...] microbatches — the
    microbatch axis may be data-sharded (each data shard pipelines its own
    microbatches; stages are orthogonal on the pipe axis), so the schedule
    length comes from the LOCAL shape. Every device runs every tick (SPMD);
    `where` masks make only the meaningful results land."""
    n_micro = xs.shape[0]
    p_local = jax.tree_util.tree_map(lambda a: a[0], params)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    state = jnp.zeros(xs.shape[1:], xs.dtype)   # activation entering this stage
    outs = jnp.zeros_like(xs)                   # exits, valid on last stage
    for t in range(n_micro + n_stages - 1):
        inject = xs[min(t, n_micro - 1)]
        x_in = jnp.where(idx == 0, inject, state)
        y = stage_fn(p_local, x_in)
        m = t - (n_stages - 1)                  # microbatch exiting this tick
        if 0 <= m < n_micro:
            outs = outs.at[m].set(jnp.where(idx == n_stages - 1, y, outs[m]))
        if t < n_micro + n_stages - 2:
            state = jax.lax.ppermute(y, axis_name, fwd)
    # replicate the last stage's outputs to every shard
    outs = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh, axis="pipe",
                   batch_axis=None, seq_axis=None):
    """Run ``microbatches`` through ``n_stages`` chained applications of
    ``stage_fn``, stage i's parameters living on pipe-shard i.

    - ``stage_fn(params_i, x) -> y`` with ``y.shape == x.shape``.
    - ``stacked_params``: pytree whose leaves have leading axis = n_stages
      (the stage stack), sharded over ``axis``.
    - ``microbatches``: [M, mb, ...]; optionally ``batch_axis`` names a
      mesh axis the MICROBATCH dim (axis 0) is sharded on — that is the
      natural sharding a data-parallel producer's reshape [B, ...] ->
      [M, mb, ...] yields (contiguous batch rows land in whole
      microbatches per data shard), so composing dp costs no reshard.
      Each data shard pipelines its own microbatches independently.
    - ``seq_axis``: mesh axis dim 2 (sequence) is sharded on — stage_fn
      must be elementwise along that dim (true for MLP blocks); keeps
      sequence-parallel producers/consumers aligned with no reshard.

    Returns [M, mb, ...] — equivalent to sequentially applying stage 0..N-1
    to each microbatch.
    """
    enforce(isinstance(mesh, Mesh), "pipeline_apply needs a jax Mesh")
    n_stages = mesh.shape[axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    enforce(all(l.shape[0] == n_stages for l in leaves),
            "stacked params leading axis must equal pipe axis size %d",
            n_stages)
    p_spec = jax.tree_util.tree_map(
        lambda l: P(*((axis,) + (None,) * (l.ndim - 1))), stacked_params)
    tail = (seq_axis,) + (None,) * (microbatches.ndim - 3) \
        if microbatches.ndim >= 3 else ()
    x_spec = P(*((batch_axis, None) + tail))
    body = functools.partial(_pipeline_shard, stage_fn=stage_fn,
                             axis_name=axis, n_stages=n_stages)
    return shard_map(body, mesh=mesh, in_specs=(p_spec, x_spec),
                     out_specs=x_spec, check_vma=False)(
                         stacked_params, microbatches)


def stack_stage_params(param_list):
    """[{'w': ...}, ...] per-stage param pytrees -> stacked pytree with
    leading stage axis (ready for the 'pipe' sharding)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_list)


def pipe_sharding(mesh, tree, axis="pipe"):
    """NamedShardings placing a stacked stage pytree over the pipe axis."""
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, P(*((axis,) + (None,) * (l.ndim - 1)))),
        tree)
