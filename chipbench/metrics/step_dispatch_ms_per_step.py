"""The step thread's dispatch of the jitted step, no sync, per step finalized
in the window: the program's `paddle_tpu_train_dispatch_ms` histogram, its
`train_step` span."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_train_dispatch_ms")
