"""The producer thread's hand-over of a batch's bytes to the device(s), per
batch it made in the window: the program's `paddle_tpu_data_feed_place_ms`
histogram, the sum of the `feed_place` spans inside one `feed_convert`."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_data_feed_place_ms")
