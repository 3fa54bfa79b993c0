"""The mesh a step is being traced for: the one trace-time context.

``use_mesh(mesh, batch_axis=None)`` names the mesh, and optionally the axis
the batch is split over, for everything traced inside it:

- topology.py resolves ``ExtraAttr(sharding=...)`` against the mesh;
- ops/rnn.py shard_maps its fused Pallas scans over ``batch_axis``: XLA
  cannot partition a Mosaic kernel ("Mosaic kernels cannot be automatically
  partitioned"), so on a multi-device mesh each device scans its own rows,
  and with no batch axis named the scan stays a ``lax.scan``, which XLA
  partitions itself.

It lives in core/ so that ops/ can read it without knowing parallel/.
``DataParallel`` enters it round every step it jits; user code enters it
for layer-level sharding (parallel.mesh re-exports ``use_mesh``).
Thread-local, as jax's own mesh context is: a trace runs on the thread that
calls the jitted function. The mesh is part of jit's cache key (``with
mesh:`` below); the batch axis is not, so one function is not traced under
two batch axes of the same mesh.
"""

import contextlib
import threading

_scope = threading.local()


def current():
    """``(mesh, batch_axis)`` of the innermost use_mesh(), or None."""
    return getattr(_scope, "value", None)


def current_mesh():
    """The mesh use_mesh() made active, or None."""
    scope = current()
    return None if scope is None else scope[0]


@contextlib.contextmanager
def use_mesh(mesh, batch_axis=None):
    """Make ``mesh`` the active mesh (and enter it as the jax mesh
    context); ``batch_axis`` names the mesh axis the batch is split over."""
    prev = current()
    _scope.value = (mesh, batch_axis)
    try:
        with mesh:
            yield mesh
    finally:
        _scope.value = prev
