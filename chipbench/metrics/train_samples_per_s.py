"""All samples of all steps that ended in the window, over the whole
window (first to last stamped `EndIteration`)."""

from chipbench import window


def read(ctx):
    return window.rate_per_s(ctx["stamps"], ctx["samples_per_step"])
