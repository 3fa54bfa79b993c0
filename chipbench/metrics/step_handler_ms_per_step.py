"""The event handler's time on the step thread, per step finalized in the
window: the program's `paddle_tpu_train_handler_ms` histogram, the sum of a
step's `handler` spans."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_train_handler_ms")
