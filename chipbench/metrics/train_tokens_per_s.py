"""Valid tokens trained a second: the mean of the program's
`paddle_tpu_train_step_tokens` histogram over the steps dispatched in the
window (one observation a step: the valid tokens of its widest sequence
slot) times the window's steps a second."""

from chipbench.metrics import _histogram


def read(ctx):
    tokens = _histogram.mean_in_window(ctx, "paddle_tpu_train_step_tokens")
    if tokens is None:
        return None
    stamps = ctx["stamps"]
    return tokens * (len(stamps) - 1) / (stamps[-1] - stamps[0])
