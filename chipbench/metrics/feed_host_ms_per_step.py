"""The producer thread's host assembly, per batch it made in the window: the
program's `paddle_tpu_data_feed_host_ms` histogram, the self time of its
`feed_convert` span (the conversion less the placements inside it)."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_data_feed_host_ms")
