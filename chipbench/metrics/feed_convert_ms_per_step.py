"""The producer thread's convert and device_put, per batch it made in the
window: the program's `paddle_tpu_data_feed_convert_ms` histogram."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_data_feed_convert_ms")
