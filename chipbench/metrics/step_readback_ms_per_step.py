"""The step thread blocked on the device reading the loss and the evaluators'
statistics back, per step finalized in the window: the program's
`paddle_tpu_train_readback_ms` histogram, its `eval_readback` span."""

from chipbench.metrics import _histogram


def read(ctx):
    return _histogram.mean_in_window(ctx, "paddle_tpu_train_readback_ms")
