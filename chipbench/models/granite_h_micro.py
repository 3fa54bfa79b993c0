"""The `granite-4.0-h-micro` configuration as the program builds it:
`paddle_tpu.models.hybrid_lm.from_config` over the configuration's own
keys (every layer a recomputed block, the embedding tied to the head)
under its token-level cost, and where each of the reference's weights
goes in it."""

PREFIX = "lm"
_MAMBA = ("in_proj", "conv_w", "conv_b", "A_log", "D", "dt_bias", "norm_w",
          "out_proj")
_ATTENTION = ("q", "k", "v", "o")


def build(cfg):
    from paddle_tpu.models import hybrid_lm

    return hybrid_lm.from_config(cfg, prefix=PREFIX)[3]


def program_names(cfg):
    """{reference name: program parameter name}; the layouts agree
    (matrices [in, out], the convolution filter [channels, taps], in_proj's
    columns z, xBC, dt)."""
    names = {"emb": PREFIX + ".emb",
             "final_norm": PREFIX + ".final_norm.w0"}
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        ref, prog = "l%d." % i, "%s.l%d." % (PREFIX, i)
        names[ref + "norm1"] = prog + "norm1.w0"
        names[ref + "norm2"] = prog + "norm2.w0"
        names[ref + "mlp_in"] = prog + "mlp.w0"
        names[ref + "mlp_out"] = prog + "mlp.w1"
        for leaf in (_MAMBA if kind == "mamba" else _ATTENTION):
            names[ref + leaf] = prog + "mixer." + leaf
    return names
