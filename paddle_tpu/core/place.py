"""Device places.

Equivalent of paddle/platform/place.h:23-59 (CPUPlace/GPUPlace variant) and
DeviceContext (device_context.h:31-56). On TPU there are no user-managed
streams — XLA owns scheduling — so a Place resolves to a `jax.Device` and a
`jax.sharding.SingleDeviceSharding`; DeviceContext's stream/event role is
subsumed by jax dispatch + ``block_until_ready``.
"""

import os
import threading

from paddle_tpu.utils.error import enforce


class Place:
    """Abstract device place; value-semantic and hashable (cf. platform::Place)."""

    device_id = 0

    def jax_device(self):
        raise NotImplementedError

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)


class CPUPlace(Place):
    def __init__(self, device_id=0):
        self.device_id = device_id

    def jax_device(self):
        import jax

        cpus = jax.devices("cpu")
        enforce(self.device_id < len(cpus), "CPUPlace(%d) out of range", self.device_id)
        return cpus[self.device_id]


class TPUPlace(Place):
    """An accelerator place (cf. platform::GPUPlace, place.h:33)."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def jax_device(self):
        tpus = tpu_devices()
        enforce(self.device_id < len(tpus), "TPUPlace(%d) out of range", self.device_id)
        return tpus[self.device_id]


def backend_initialized():
    """True once this process has opened a JAX backend. On a TPU host that
    means it holds the chips, and no child process can open them."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def host_tpu_chips():
    """TPU chips attached to this host, counted from sysfs the way JAX's
    own start-up does — without opening them, so a parent that must stay
    off the chip can ask."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def enforce_children_can_open_devices(n_children, who, env=None):
    """Refuse, before any child starts, a process layout that fails or
    hangs on a TPU host. A chip belongs to one process at a time, a JAX
    process opens every chip it sees, and nothing in this tree divides a
    host's chips among processes: so a parent that has opened the TPU can
    start no child that needs it, and at most one child can run at all.
    Children pinned to the CPU (``JAX_PLATFORMS=cpu`` in ``env``, default
    this process's environment) are always fine."""
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS") == "cpu":
        return
    if backend_initialized():
        import jax

        enforce(jax.default_backend() != "tpu",
                "%s: this process has already opened the TPU, so the %d "
                "process(es) it would start cannot; do the JAX work in a "
                "child of its own, or serve in-process (--replicas)",
                who, n_children)
    chips = host_tpu_chips()
    enforce(n_children <= 1 or chips == 0,
            "%s: %d processes on a TPU host (%d chip(s) in sysfs): each "
            "would try to open every chip it sees and all but the first "
            "fail or hang. One process can drive all chips (--replicas N, "
            "--trainer-count N); set JAX_PLATFORMS=cpu to run this "
            "layout on the CPU", who, n_children, chips)


def tpu_devices():
    """The TPU devices JAX sees. None is an error that names what JAX
    found instead: a TPU place never resolves to a CPU device."""
    import jax

    devices = jax.devices()
    tpus = [d for d in devices if d.platform == "tpu"]
    enforce(tpus, "no TPU device: jax.devices() returned %r "
            "(JAX_PLATFORMS=%r)", devices, os.environ.get("JAX_PLATFORMS"))
    return tpus


_state = threading.local()
_default_lock = threading.Lock()
_default = [None]


def default_place():
    if _default[0] is None:
        import jax

        has_tpu = any(d.platform == "tpu" for d in jax.devices())
        place = TPUPlace() if has_tpu else CPUPlace()
        with _default_lock:
            if _default[0] is None:
                _default[0] = place
    return _default[0]


def set_default_place(place):
    enforce(isinstance(place, Place), "expected a Place, got %r", place)
    with _default_lock:
        _default[0] = place


def device_count(place_type=None):
    import jax

    if place_type is CPUPlace:
        return len(jax.devices("cpu"))
    if place_type is TPUPlace:
        return len(tpu_devices())
    return len(jax.devices())


def device_put(tree, place=None):
    """Stage a pytree onto a place (cf. memcpy H2D, paddle/memory/memcpy.h)."""
    import jax

    place = place or default_place()
    return jax.device_put(tree, place.jax_device())
