"""The whole step's share of the chips' peak: the train step's operations
(`flops/<config>.py`: forward times three, nothing recomputed) times the
window's steps per second, over chips times the bf16 peak."""

from chipbench import peaks


def read(ctx):
    stamps = ctx["stamps"]
    steps_per_s = (len(stamps) - 1) / (stamps[-1] - stamps[0])
    flops = ctx["flops"].train_step_flops(ctx["cfg"], ctx["cell"])
    peak = peaks.of(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * steps_per_s / (ctx["chips"] * peak)
