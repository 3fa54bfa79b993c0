"""95th percentile of the gaps between consecutive `EndIteration` stamps,
over all steps of the window."""

from chipbench import window


def read(ctx):
    return window.percentile(window.gaps_ms(ctx["stamps"]), 95.0)
