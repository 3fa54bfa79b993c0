"""Parameters: named buffers + metadata + tar serialization.

Parity with python/paddle/v2/parameters.py (Parameters.to_tar :267 /
from_tar :286, numpy get/set) and the C++ Parameter save/load
(paddle/parameter/Parameter.h:197-212). Serialization is a tar of .npy
payloads plus a JSON manifest — self-describing and topology-independent,
so checkpoints restore under any later device mesh (SURVEY.md §7 hard-part:
topology-independent restore).
"""

import io
import json
import tarfile
import time

import numpy as np

from paddle_tpu.observe import spans as observe_spans
from paddle_tpu.utils.error import enforce


class Parameters:
    """A dict of name -> numpy/jax array plus per-name ParamSpec metadata."""

    def __init__(self):
        self._values = {}
        self._specs = {}

    # -- construction -------------------------------------------------------
    @staticmethod
    def create(topology_or_cost, rng=None, dtype=None):
        """Create and initialize parameters for a topology (v2
        paddle.parameters.create parity). The set-up span
        ``params_create`` (docs/observability.md)."""
        from paddle_tpu.topology import Topology
        from paddle_tpu.graph import LayerNode
        from paddle_tpu.multi_network import MultiNetwork

        with observe_spans.phase("params_create"):
            topo = topology_or_cost
            if isinstance(topo, MultiNetwork):
                topo = Topology(topo.costs)
            elif isinstance(topo, (LayerNode, list)):
                topo = Topology(topo)
            params = Parameters()
            params._specs = dict(topo.param_specs())
            params._values = dict(topo.init_params(rng=rng, dtype=dtype))
        return params

    # -- dict-like ----------------------------------------------------------
    def names(self):
        return sorted(self._values)

    def keys(self):
        return self.names()

    def has_key(self, key):
        return key in self._values

    def __contains__(self, key):
        return key in self._values

    def __len__(self):
        return len(self._values)

    def __iter__(self):
        return iter(self.names())

    def get(self, key):
        return np.asarray(self._values[key])

    def __getitem__(self, key):
        return self.get(key)

    def set(self, key, value):
        enforce(key in self._values, "unknown parameter %r", key)
        old = self._values[key]
        value = np.asarray(value)
        enforce(tuple(value.shape) == tuple(old.shape),
                "shape mismatch for %r: %s vs %s", key, value.shape, old.shape)
        self._values[key] = value.astype(np.asarray(old).dtype)

    def __setitem__(self, key, value):
        self.set(key, value)

    def get_shape(self, key):
        # the leaf's own attribute: np.asarray would copy a device array
        # to the host to read it
        return tuple(np.shape(self._values[key]))

    def spec(self, key):
        return self._specs.get(key)

    # -- trainable/state partition -----------------------------------------
    def partition(self):
        """Returns (trainable, static, state) name lists. Static parameters
        (ParamAttr.is_static) receive no updates (reference: static params
        skip the updater); state entries are running stats (BN)."""
        trainable, static, state = [], [], []
        for name in self.names():
            spec = self._specs.get(name)
            if spec is not None and spec.is_state:
                state.append(name)
            elif spec is not None and spec.attr.is_static:
                static.append(name)
            else:
                trainable.append(name)
        return trainable, static, state

    def as_dict(self):
        return dict(self._values)

    def copy(self):
        """Shallow copy: fresh name->value/spec dicts over the SAME
        arrays (values are never mutated in place, so sharing is safe).
        The async checkpointer snapshots this on the step thread and
        overlays the device snapshot on the writer thread — the live
        Parameters object is never touched off-thread."""
        clone = Parameters()
        clone._values = dict(self._values)
        clone._specs = dict(self._specs)
        return clone

    def update_from(self, values):
        """Take over the values of the names this object has. The span
        ``params_update``; inside the trainer's ``sync_back`` it is that
        span's child and its time is ``sync_back``'s, observed once."""
        with observe_spans.phase("params_update", unless_in="sync_back"):
            for key, val in values.items():
                if key in self._values:
                    self._values[key] = val

    # -- serialization ------------------------------------------------------
    def to_tar(self, f):
        """Write a tar: manifest.json + one .npy per parameter (v2
        Parameters.to_tar parity, format modernized)."""
        tar = tarfile.open(fileobj=f, mode="w")
        manifest = {
            "format": "paddle_tpu-parameters-v1",
            "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "parameters": {},
        }
        for name in self.names():
            arr = np.asarray(self._values[name])
            spec = self._specs.get(name)
            manifest["parameters"][name] = {
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "is_state": bool(spec.is_state) if spec else False,
                "is_static": bool(spec.attr.is_static) if spec else False,
            }
            payload = io.BytesIO()
            np.save(payload, arr, allow_pickle=False)
            data = payload.getvalue()
            info = tarfile.TarInfo(name=_safe_entry(name) + ".npy")
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
        mdata = json.dumps(manifest, indent=2).encode()
        info = tarfile.TarInfo(name="manifest.json")
        info.size = len(mdata)
        tar.addfile(info, io.BytesIO(mdata))
        tar.close()

    @staticmethod
    def from_tar(f):
        """Load Parameters from a tar written by to_tar (no topology needed
        — the manifest is self-describing)."""
        tar = tarfile.open(fileobj=f, mode="r")
        members = {m.name: m for m in tar.getmembers()}
        enforce("manifest.json" in members, "not a paddle_tpu parameter tar")
        manifest = json.loads(tar.extractfile(members["manifest.json"]).read())
        params = Parameters()
        from paddle_tpu.attr import ParamAttr
        from paddle_tpu.graph import ParamSpec
        from paddle_tpu.initializer import Constant

        for name, meta in manifest["parameters"].items():
            entry = _safe_entry(name) + ".npy"
            enforce(entry in members, "missing tar entry %r", entry)
            arr = np.load(io.BytesIO(tar.extractfile(members[entry]).read()),
                          allow_pickle=False)
            params._values[name] = arr
            # reconstruct is_state/is_static so partition() keeps BN stats
            # and frozen weights out of the trainable set after restore
            params._specs[name] = ParamSpec(
                name, arr.shape, Constant(0.0),
                attr=ParamAttr(is_static=bool(meta.get("is_static", False))),
                is_state=bool(meta.get("is_state", False)))
        tar.close()
        return params

    def to_npz(self, f):
        """Packed flat export of the raw values (the serve bundle's
        parameter payload, paddle_tpu/serve/export.py): one .npz the
        load side reads with nothing but numpy — no spec metadata, no
        graph types. Use :meth:`to_tar` for checkpoints that must
        restore is_state/is_static partitioning."""
        np.savez(f, **{name: np.asarray(self._values[name])
                       for name in self.names()})

    def init_from_tar(self, f):
        """Overwrite matching parameters from a tar (v2 init_from_tar)."""
        other = Parameters.from_tar(f)
        for name in other.names():
            if name in self._values:
                self.set(name, other.get(name))

    def __repr__(self):
        return "Parameters(%d params: %s)" % (len(self), ", ".join(self.names()[:6]))


def _safe_entry(name):
    return name.replace("/", "__slash__")


create = Parameters.create
