"""Device mesh + data-parallel strategy.

Replaces (reference): MultiGradientMachine's thread-ring data parallelism
(gserver/gradientmachines/MultiGradientMachine.h:43-106 — batch scatter,
per-thread replicas, ring grad merge/value dispatch) and the pserver
sync-SGD path (trainer RemoteParameterUpdater + ParameterServer2). Here the
same train_step is pjit-ed over a Mesh: inputs sharded on the 'data' axis,
parameters replicated (or sharded ZeRO-style with
``shard_optimizer_state=True``), and XLA inserts the psum over ICI — no
parameter server, no RPC, no gradient copy threads.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the active-mesh context lives in core/ (ops/ reads it too); re-exported
# here because this is where users look for it
from paddle_tpu.core.mesh_scope import current_mesh, use_mesh  # noqa: F401
from paddle_tpu.core.sequence import NestedSequenceBatch, SequenceBatch
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import spans as observe_spans
from paddle_tpu.utils.error import enforce
from paddle_tpu.utils.logger import logger


def _resharded():
    # of the process's registry, not a feeder's: the step thread and
    # evaluation come through shard_batch too
    return observe_metrics.get_registry().counter(
        "paddle_tpu_data_feed_resharded_total",
        help="batch leaves shard_batch moved from a device onto the mesh")


def local_device_count():
    return len(jax.devices())


def build_mesh(axes=None, devices=None):
    """Build a jax Mesh. axes: dict name->size or list of (name, size);
    -1 for one axis means 'fill with remaining devices'."""
    devices = devices if devices is not None else jax.devices()
    if axes is None:
        axes = {"data": len(devices)}
    items = list(axes.items()) if isinstance(axes, dict) else list(axes)
    names = [k for k, _ in items]
    sizes = [v for _, v in items]
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devices) // known
    total = int(np.prod(sizes))
    enforce(total <= len(devices),
            "mesh %s needs %d devices, have %d", dict(zip(names, sizes)),
            total, len(devices))
    dev_array = np.array(devices[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(names))


class DataParallel:
    """Synchronous data parallelism over a mesh axis.

    Usage: ``SGD(..., parallelism=DataParallel(mesh))``. The global batch
    must divide the data-axis size (reference's MultiGradientMachine had the
    same per-thread split). Equivalent multi-node story: the same pjit
    program spans hosts via jax.distributed — sync SGD without the
    reference's --num_gradient_servers machinery.
    """

    def __init__(self, mesh=None, axis="data", shard_optimizer_state=True):
        self.mesh = mesh or build_mesh()
        self.axis = axis
        self.shard_optimizer_state = shard_optimizer_state

    # sharding specs ---------------------------------------------------------
    def _batch_spec(self):
        return P(self.axis)

    def batch_sharding(self):
        return NamedSharding(self.mesh, self._batch_spec())

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def batch_leaf_sharding(self, shape):
        """Where a batch leaf of ``shape`` goes: axis 0 over the data axis
        when the mesh divides its rows, else a copy on every device. The
        one rule: :meth:`shard_batch` asks it of every leaf, and the
        DeviceFeeder of the host arrays it places itself."""
        if len(shape) >= 1 and shape[0] % self.mesh.shape[self.axis] == 0:
            return NamedSharding(
                self.mesh, P(*([self.axis] + [None] * (len(shape) - 1))))
        return self.replicated()

    def shard_batch(self, tree):
        """Place a batch onto the mesh, sharded on axis 0.
        Idempotent: leaves already carrying their target sharding pass
        through untouched, so a feed the DeviceFeeder placed
        (paddle_tpu.data.feeder; its recycled columns straight from the
        host, the rest through here on its producer) costs the step thread
        nothing. Each leaf that does move is a ``feed_place`` span, and one
        that moves from a device (a second crossing, as a program on that
        device) counts in ``paddle_tpu_data_feed_resharded_total``."""

        def place(x):
            want = self.batch_leaf_sharding(getattr(x, "shape", ()))
            if getattr(x, "sharding", None) == want:
                return x
            with observe_spans.span("feed_place"):
                if isinstance(x, jax.Array):
                    _resharded().inc()
                return jax.device_put(x, want)

        return jax.tree_util.tree_map(place, tree)

    def _param_sharding(self, pytree):
        """Replicate parameters; ZeRO-style sharding of optimizer slots is
        applied by slot_sharding()."""
        repl = self.replicated()
        return jax.tree_util.tree_map(lambda _: repl, pytree)

    def slot_sharding(self, opt_state):
        """Shard large optimizer slots on their leading axis when divisible
        (ZeRO-1 analogue; reference's pserver kept optimizer state sharded
        server-side — here it shards across the same chips doing compute)."""
        axis_size = self.mesh.shape[self.axis]

        def spec(x):
            if (self.shard_optimizer_state and hasattr(x, "ndim") and
                    x.ndim >= 1 and x.shape[0] % axis_size == 0 and
                    x.size >= 8192):
                return NamedSharding(self.mesh,
                                     P(*([self.axis] + [None] * (x.ndim - 1))))
            return self.replicated()

        return jax.tree_util.tree_map(spec, opt_state)

    # step wrappers ----------------------------------------------------------
    def shard_train_step(self, train_step, trainer):
        jitted = jax.jit(
            train_step,
            donate_argnums=(0, 1, 3, 4),
            out_shardings=None,
        )

        def run(trainable, replica, static, state, opt_state, feed, rng):
            feed = self.shard_batch(feed)
            with use_mesh(self.mesh, batch_axis=self.axis):
                return jitted(trainable, replica, static, state, opt_state,
                              feed, rng)

        return run

    def shard_train_chunk(self, train_chunk, trainer):
        """Fused multi-step twin of :meth:`shard_train_step`: the chunked
        scan runs as ONE pjit program with the same donated carries. Each
        member feed of the length-K chunk tuple gets the exact
        :meth:`shard_batch` placement of the per-step path — idempotent,
        so a DeviceFeeder chunk (pre-placed on the producer thread)
        passes through for free."""
        jitted = jax.jit(train_chunk, donate_argnums=(0, 1, 3, 4))

        def run(trainable, replica, static, state, opt_state, feeds, rng):
            feeds = tuple(self.shard_batch(f) for f in feeds)
            with use_mesh(self.mesh, batch_axis=self.axis):
                return jitted(trainable, replica, static, state, opt_state,
                              feeds, rng)

        return run

    def shard_eval_step(self, eval_step, trainer):
        jitted = jax.jit(eval_step)

        def run(trainable, static, state, feed):
            feed = self.shard_batch(feed)
            with use_mesh(self.mesh, batch_axis=self.axis):
                return jitted(trainable, static, state, feed)

        return run

    def __repr__(self):
        return "DataParallel(mesh=%s, axis=%r)" % (
            dict(self.mesh.shape), self.axis)
