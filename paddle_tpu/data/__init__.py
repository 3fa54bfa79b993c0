"""paddle_tpu.data — the pipelined input subsystem (docs/data.md).

The training-side twin of ``paddle_tpu.serve``'s batching machinery:
``feeder.DeviceFeeder`` keeps N converted, device-resident batches
ahead of the train step (PyDataProvider2 pool-thread parity, TPU-
shaped), and ``bucketing`` owns length-bucketed batching, sequence
packing and THE bucket-choice rule the serving bundle shares.

``bucketing`` stays importable without jax/graph code (serve/bundle.py
depends on it inside graph-free processes); importing ``feeder`` pulls
in the observe stack, and the packing feed builders import jax lazily.
"""

from paddle_tpu.data import bucketing
from paddle_tpu.data.bucketing import (
    BucketBatch,
    bucket_for,
    bucket_index,
    derive_buckets,
    pack_feed,
    pack_samples,
    packed_batches,
    rebucket_batches,
)

# feeder (and the observe stack it instruments with) loads lazily
# (PEP 562): serve/bundle.py reaches bucketing through this package from
# graph-free processes and must not pay for — or be coupled to — the
# feeder's imports.
_FEEDER_NAMES = ("DeviceFeeder", "FeedBatch", "feeder")


def __getattr__(name):
    if name in _FEEDER_NAMES:
        import importlib

        # not `from paddle_tpu.data import feeder`: that asks this
        # function for the attribute first, and never returns
        feeder = importlib.import_module("paddle_tpu.data.feeder")

        globals()["feeder"] = feeder
        globals()["DeviceFeeder"] = feeder.DeviceFeeder
        globals()["FeedBatch"] = feeder.FeedBatch
        return globals()[name]
    raise AttributeError("module 'paddle_tpu.data' has no attribute %r"
                         % name)


__all__ = [
    "BucketBatch", "DeviceFeeder", "FeedBatch", "bucket_for",
    "bucket_index", "bucketing", "derive_buckets", "pack_feed",
    "pack_samples", "packed_batches", "rebucket_batches",
]
