"""The `train` calls' ways in and out before the window, without their
steps and without `sync_back`: the sums of `paddle_tpu_train_enter_ms`
(steplog, sentinel, the feeder built) and `paddle_tpu_train_exit_ms` (the
pass's end, the producer's join, checkpoint close, export) less that of
`paddle_tpu_train_sync_back_ms`, whose spans lie inside `train_exit`."""

from chipbench.metrics import _setup


def read(ctx):
    return _setup.train_call_s(ctx)
