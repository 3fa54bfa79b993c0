"""Counters of a train step whose values are data: what a layer can only
know once the step has run (how many rows the router sent to the experts
held here), as opposed to the gauges ``Topology.apply`` sets from shapes
while it traces.

A layer calls ``ctx.count(name, value)`` with a scalar of the traced
step; the layers' values of one name fold into one (``COUNTS`` says how:
a sum or a maximum), ``layer.recompute`` blocks hand theirs out beside
their outputs, and the trainer's step returns them beside the cost,
under ``KEY`` among the evaluators' statistics. The loop reads them in
the readback it already makes for the cost, one step behind the
dispatch, and :func:`observe` adds one observation a step to the
always-on histogram of each name. Nothing waits on the device for them,
and a step without such layers returns nothing more than it did.

Stdlib only: the table is read where no array library is loaded.
"""

# name: (how the layers' values of one step fold into one, help)
COUNTS = {
    "paddle_tpu_moe_rows_here": (
        "sum", "(token, choice) pairs a train step's expert layers computed "
        "on the experts held here, all layers"),
    "paddle_tpu_moe_expert_load_max": (
        "max", "rows of the busiest held expert of a train step, the "
        "largest over its expert layers"),
    "paddle_tpu_moe_rows_visited": (
        "sum", "rows a train step's fused expert layers' grouped products "
        "cover with their row tiles (a tile two groups share counts "
        "twice), all layers"),
}
# where the step's counters ride among its evaluators' statistics
KEY = "paddle_tpu.step_counts"


def observe(registry, counts, index=None, labels=None):
    """One observation to each name's histogram from a step's host
    values; ``index`` picks the step of a fused unit's stacked values."""
    for name, value in counts.items():
        registry.histogram(name, help=COUNTS[name][1], labels=labels).observe(
            float(value if index is None else value[index]))
