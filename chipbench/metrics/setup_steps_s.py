"""The steps finalized before the window (the three compared ones and the
ramp): the sums of `paddle_tpu_data_feed_stall_ms`, `_train_dispatch_ms`,
`_train_readback_ms` and `_train_handler_ms` when the window opened. The
first dispatch holds the step's trace, lowering and compile or cache hit."""

from chipbench.metrics import _setup


def read(ctx):
    return _setup.steps_s(ctx)
