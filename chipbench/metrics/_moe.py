"""What the expert layers' two step counters are held against: the
configuration's own shape. Nothing for a configuration without experts or
a program without the histograms."""

from chipbench.metrics import _histogram


def expert_layers(cfg):
    """(expert layers held, experts held in each, choices a token, all
    experts), or None for a configuration without experts."""
    if "num_experts_per_tok" not in cfg or "num_dense_layers" not in cfg:
        return None
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return (layers, cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg.get("num_experts_published", cfg["num_experts"]))


def rows_here(ctx):
    """Mean rows computed here a step of the window, all layers."""
    return _histogram.mean_in_window(ctx, "paddle_tpu_moe_rows_here")
