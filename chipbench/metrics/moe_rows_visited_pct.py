"""How many rows the expert layers' grouped products visit for each row
they need: 100 x the program's `paddle_tpu_moe_rows_visited` (one
observation a step: the rows the fused form's row tiles cover, a tile that
two groups share counted once for each group, all layers) over
`paddle_tpu_moe_rows_here` (the pairs computed here), both means over the
window's steps. 100 where every group fills whole tiles; above it by the
tiles' ragged edges. Nothing where the plain form ran (it observes no
tiles) or the program has no such histogram."""

from chipbench.metrics import _histogram, _moe


def read(ctx):
    visited = _histogram.mean_in_window(ctx, "paddle_tpu_moe_rows_visited")
    rows = _moe.rows_here(ctx)
    if visited is None or not rows:
        return None
    return 100.0 * visited / rows
