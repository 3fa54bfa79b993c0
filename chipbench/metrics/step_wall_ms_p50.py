"""Median gap between the handler's `EndIteration` stamps in the window."""

import statistics

from chipbench import window


def read(ctx):
    return statistics.median(window.gaps_ms(ctx["stamps"]))
