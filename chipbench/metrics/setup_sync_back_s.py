"""Every parameter read back to the host, in the `train` calls that end
before the window: the sum of `paddle_tpu_train_sync_back_ms`, one
observation a `sync_back` span (`device_get` and `update_from`)."""

from chipbench.metrics import _setup


def read(ctx):
    return _setup.sync_back_s(ctx)
