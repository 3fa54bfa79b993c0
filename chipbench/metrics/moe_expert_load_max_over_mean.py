"""How uneven the routing over the held experts was: the busiest held
expert's rows (`paddle_tpu_moe_expert_load_max`: the largest over the
step's expert layers) over the mean rows of a held expert and layer
(`paddle_tpu_moe_rows_here` / (experts held x expert layers)), both means
over the window's steps. 1 at uniform routing; the grouped products'
longest group, and in a deployment the chip the exchange waits for."""

from chipbench.metrics import _histogram, _moe


def read(ctx):
    shape = _moe.expert_layers(ctx["cfg"])
    rows = _moe.rows_here(ctx)
    busiest = _histogram.mean_in_window(ctx,
                                        "paddle_tpu_moe_expert_load_max")
    if shape is None or not rows or busiest is None:
        return None
    layers, held, _, _ = shape
    return busiest / (rows / (held * layers))
