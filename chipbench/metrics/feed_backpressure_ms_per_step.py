"""The producer thread blocked on a full queue, per batch it made in the
window: the program's `paddle_tpu_data_feed_backpressure_ms` histogram, its
`feed_put` span. Higher is better: the feed is ahead, the device is the bound."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_data_feed_backpressure_ms")
