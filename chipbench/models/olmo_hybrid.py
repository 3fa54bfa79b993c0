"""The `olmo-hybrid-7b` configuration as the program builds it:
`paddle_tpu.models.hybrid_lm.from_config` over the configuration's own
keys (Gated DeltaNet and normalised-query attention layers, norms after
the branches, an untied head; every layer a recomputed block) under its
token-level cost, and where each of the reference's weights goes in
it."""

from chipbench.reference import olmo_hybrid as ref

PREFIX = "lm"
# Of the four layers' recomputed blocks the last three keep their MLP's first
# product and the residual after their mixer (1.27 GB at the cell's shapes):
# the fourth would leave under 1 GB of the chip free (PERF.md section 6).
KEEP_LAYERS = 3


def build(cfg):
    from paddle_tpu.models import hybrid_lm

    return hybrid_lm.from_config(cfg, prefix=PREFIX,
                                 keep_layers=KEEP_LAYERS)[3]


def program_names(cfg):
    """{reference name: program parameter name}; the layouts agree
    (matrices [in, out], both tables [vocab, hidden], the convolution
    filter [channels, taps] over q, k, v side by side, the MLP's first
    matrix gate then up)."""
    names = {"emb": PREFIX + ".emb", "head": PREFIX + ".head.w0",
             "final_norm": PREFIX + ".final_norm.w0"}
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        at, prog = "l%d." % i, "%s.l%d." % (PREFIX, i)
        names[at + "norm1"] = prog + "norm1.w0"
        names[at + "norm2"] = prog + "norm2.w0"
        names[at + "mlp_in"] = prog + "mlp.w0"
        names[at + "mlp_out"] = prog + "mlp.w1"
        for leaf in ref.leaves_of(kind):
            names[at + leaf] = prog + "mixer." + leaf
    return names
