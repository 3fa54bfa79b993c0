"""The producer thread's `next()` on the reader's iterator, per batch it made
in the window: the program's `paddle_tpu_data_feed_read_ms` histogram, one
observation of its `feed_read` span a batch."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_data_feed_read_ms")
