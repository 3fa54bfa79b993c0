"""The `phi-4-mini-flash-reasoning` configuration as the program builds
it: `paddle_tpu.models.hybrid_lm.from_config` over the configuration's own
keys (Mamba-1 and window-attention layers, one full-attention layer, then
Gated Memory Units and cross-attention layers that read the last Mamba
layer's scan output and the full-attention layer's keys and values;
LayerNorm before the branches, a tied head; every layer a recomputed
block) under its token-level cost, and where each of the reference's
weights goes in it."""

from chipbench.reference import phi4_flash as ref

PREFIX = "lm"
# In how many of the eight layers' recomputed blocks, the last ones, a
# block keeps its MLP's first product, the residual after its mixer and a
# Mamba-1 mixer's first product: by what the chip's memory leaves at the
# step's peak (PERF.md section 6).
KEEP_LAYERS = 4


def build(cfg):
    from paddle_tpu.models import hybrid_lm

    return hybrid_lm.from_config(cfg, prefix=PREFIX,
                                 keep_layers=KEEP_LAYERS)[3]


def program_names(cfg):
    """{reference name: program parameter name}; the layouts agree
    (matrices [in, out], the table [vocab, hidden], the convolution filter
    [channels, taps], the MLP's first matrix gate then up, a norm's scale
    `.w0` and bias `.wbias`)."""
    names = {"emb": PREFIX + ".emb",
             "final_norm": PREFIX + ".final_norm.w0",
             "final_norm_b": PREFIX + ".final_norm.wbias"}
    kinds = [cfg["layer_types"][i] for i in cfg["kept_layers"]]
    for i, kind in enumerate(kinds):
        at, prog = "l%d." % i, "%s.l%d." % (PREFIX, i)
        for norm in ("norm1", "norm2"):
            names[at + norm] = prog + norm + ".w0"
            names[at + norm + "_b"] = prog + norm + ".wbias"
        names[at + "mlp_in"] = prog + "mlp.w0"
        names[at + "mlp_out"] = prog + "mlp.w1"
        for leaf in ref.leaves_of(kind):
            names[at + leaf] = prog + "mixer." + leaf
    return names
