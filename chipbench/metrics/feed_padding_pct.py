"""The share of a step's positions that are padding: 100 x (1 - tokens /
positions) over the steps dispatched in the window, from the program's
`paddle_tpu_train_step_tokens` and `paddle_tpu_train_step_positions`
histograms (one observation each a step)."""

from chipbench.metrics import _histogram


def read(ctx):
    tokens = _histogram.mean_in_window(ctx, "paddle_tpu_train_step_tokens")
    positions = _histogram.mean_in_window(
        ctx, "paddle_tpu_train_step_positions")
    if tokens is None or not positions:
        return None
    return 100.0 * (1.0 - tokens / positions)
