"""The share of the routed (token, choice) pairs that this chip's experts
computed: 100 x the program's `paddle_tpu_moe_rows_here` (one observation
a step: pairs on held experts, all expert layers) over choices a token x
the step's valid tokens (`paddle_tpu_train_step_tokens`) x expert layers.
At uniform routing it is 100 x held / total, 25 for 8 of 32; the sorted
buffers' bound, choices x positions x layers, also counts the padding,
which routes nowhere."""

from chipbench.metrics import _histogram, _moe


def read(ctx):
    shape = _moe.expert_layers(ctx["cfg"])
    rows = _moe.rows_here(ctx)
    tokens = _histogram.mean_in_window(ctx, "paddle_tpu_train_step_tokens")
    if shape is None or rows is None or not tokens:
        return None
    layers, _, k, _ = shape
    return 100.0 * rows / (k * tokens * layers)
