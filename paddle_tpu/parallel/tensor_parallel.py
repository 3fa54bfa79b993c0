"""Tensor (model) parallelism: Megatron-style sharded dense pairs.

The reference's only model parallelism is layer-to-device pinning
(ParallelNeuralNetwork, gserver/gradientmachines/ParallelNeuralNetwork.h:34
— per-layer ``device`` attr + per-device threads). On TPU the idiomatic
form is *intra-layer* sharding: split weight matrices over a mesh axis and
let one psum over ICI stitch the result. This module provides the explicit
shard_map construction (deterministic collectives, the classic
column-parallel → row-parallel pair) plus spec helpers for the GSPMD path
(annotate shardings, let XLA insert collectives).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from paddle_tpu.utils.error import enforce


def _pair_shard(x, w1, b1, w2, b2, axis_name, act):
    """Local shard body: column-parallel matmul, activation, row-parallel
    matmul, single psum. x: [..., d_in] replicated (over axis_name);
    w1: [d_in, d_h/N]; w2: [d_h/N, d_out]."""
    h = jnp.einsum("...i,ih->...h", x, w1) + b1
    h = act(h)
    y = jnp.einsum("...h,ho->...o", h, w2)
    y = jax.lax.psum(y, axis_name)
    return y + b2


def megatron_dense_pair(x, w1, b1, w2, b2, mesh, axis="model",
                        batch_axis=None, act=jnp.tanh):
    """Two dense layers with the hidden dimension sharded over ``axis``.

    Global shapes: x [..., d_in], w1 [d_in, d_h], b1 [d_h],
    w2 [d_h, d_out], b2 [d_out]; d_h must divide the axis size. The
    activation between the two matmuls runs on the sharded hidden — no
    communication until the closing psum. ``batch_axis`` optionally names
    a mesh axis the leading dim of x is sharded on (composes with dp).
    """
    enforce(isinstance(mesh, Mesh), "megatron_dense_pair needs a jax Mesh")
    n = mesh.shape[axis]
    enforce(w1.shape[1] % n == 0,
            "tp axis size %d must divide hidden dim %d", n, w1.shape[1])
    lead = (batch_axis,) + (None,) * (x.ndim - 2)
    x_spec = P(*lead, None)
    body = functools.partial(_pair_shard, axis_name=axis, act=act)
    return shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, axis), P(axis), P(axis, None), P(None)),
        out_specs=x_spec, check_vma=False,
    )(x, w1, b1, w2, b2)


def column_parallel_spec(mesh, axis="model"):
    """NamedSharding for a [d_in, d_out] weight split on the output dim."""
    return NamedSharding(mesh, P(None, axis))


def row_parallel_spec(mesh, axis="model"):
    """NamedSharding for a [d_in, d_out] weight split on the input dim."""
    return NamedSharding(mesh, P(axis, None))


class TensorParallel:
    """GSPMD-path helper: map parameter names to shardings by rule.

    ``rules`` is a list of (predicate_or_prefix, PartitionSpec). Parameters
    matching no rule are replicated. Use with Topology params dicts:

    >>> tp = TensorParallel(mesh, rules=[("big_fc.w", P(None, "model"))])
    >>> shardings = tp.param_shardings(params)
    >>> params = tp.place(params)
    """

    def __init__(self, mesh, rules=(), axis="model"):
        self.mesh = mesh
        self.axis = axis
        self.rules = list(rules)

    def _spec_for(self, name):
        for pat, spec in self.rules:
            if callable(pat):
                if pat(name):
                    return spec
            elif name.startswith(pat):
                return spec
        return P()

    def param_shardings(self, params):
        return {k: NamedSharding(self.mesh, self._spec_for(k))
                for k in params}

    def place(self, params):
        sh = self.param_shardings(params)
        return {k: jax.device_put(v, sh[k]) for k, v in params.items()}

    def constraint(self, x, *spec):
        """with_sharding_constraint shorthand inside jitted code."""
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))
