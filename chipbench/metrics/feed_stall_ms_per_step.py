"""The step thread's wait on the feeder's queue, per batch it took in the
window: the program's `paddle_tpu_data_feed_stall_ms` histogram."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_data_feed_stall_ms")
