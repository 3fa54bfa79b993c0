"""The `lfm2-8b-a1b` configuration as the program builds it:
`paddle_tpu.models.hybrid_lm.from_config` over the configuration's own
keys (gated short convolutions and rotary attention with per-head norms
of queries and keys; a dense gated MLP in the leading layers, then sparse
experts of which this chip holds `num_experts` of `num_experts_published`;
RMSNorm before the branches, a tied head; every layer a recomputed block)
under its token-level cost, and where each of the reference's weights
goes in it."""

from chipbench.reference import lfm2_moe as ref

PREFIX = "lm"
# In how many of the ten layers' recomputed blocks, the last ones, a block
# keeps the residual after its mixer and its feed-forward's first product
# (the sorted rows' first grouped product in an expert layer): by what the
# chip's memory leaves at the step's peak (PERF.md section 6).
KEEP_LAYERS = 10


def build(cfg):
    from paddle_tpu.models import hybrid_lm

    return hybrid_lm.from_config(cfg, prefix=PREFIX,
                                 keep_layers=KEEP_LAYERS)[3]


def program_names(cfg):
    """{reference name: program parameter name}; the layouts agree
    (matrices [in, out], the table [vocab, hidden], the convolution filter
    [channels, taps], a gated MLP's first matrix gate then up, the
    experts' matrices stacked by held expert, a norm's scale `.w0`)."""
    names = {"emb": PREFIX + ".emb",
             "final_norm": PREFIX + ".final_norm.w0"}
    for i, (kind, sparse) in enumerate(ref.layers_of(cfg)):
        at, prog = "l%d." % i, "%s.l%d." % (PREFIX, i)
        for norm in ("norm1", "norm2"):
            names[at + norm] = prog + norm + ".w0"
        mixer, ffn = ref.leaves_of(kind, sparse)
        for leaf in mixer:
            names[at + leaf] = prog + "mixer." + leaf
        if sparse:
            for leaf in ffn:
                names[at + leaf] = prog + "moe." + leaf
            if cfg["use_expert_bias"]:
                names[at + "expert_bias"] = prog + "moe.expert_bias"
        else:
            names[at + "mlp_in"] = prog + "mlp.w0"
            names[at + "mlp_out"] = prog + "mlp.w1"
    return names
