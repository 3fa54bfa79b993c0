"""The `laguna-xs.2` configuration as the program builds it:
`paddle_tpu.models.hybrid_lm.from_config` over the configuration's own
keys (full attention with partial YaRN rotary positions beside window
attention with plain ones, each kind with its own heads, every head
gated; a dense SwiGLU in the leading layer, then sparse experts of which
this chip holds `num_experts` of `num_experts_published`, beside a shared
expert; RMSNorm before the branches, an untied head; every layer a
recomputed block) under its token-level cost, and where each of the
reference's weights goes in it."""

from chipbench.reference import laguna as ref

PREFIX = "lm"
# In how many of the five layers' recomputed blocks, the last ones, a block
# keeps the residual after its mixer and its feed-forward's first product
# (the sorted rows' first grouped product in an expert layer: 131,072 rows
# of 1,024 whatever share is held): by what the chip's memory leaves at the
# step's peak (PERF.md section 4).
KEEP_LAYERS = 2


def build(cfg):
    from paddle_tpu.models import hybrid_lm

    return hybrid_lm.from_config(cfg, prefix=PREFIX,
                                 keep_layers=KEEP_LAYERS)[3]


def program_names(cfg):
    """{reference name: program parameter name}; the layouts agree
    (matrices [in, out], both tables [vocab, hidden], a SwiGLU's first
    matrix gate then up, the experts' matrices stacked by held expert, a
    norm's scale `.w0`)."""
    names = {"emb": PREFIX + ".emb", "head": PREFIX + ".head.w0",
             "final_norm": PREFIX + ".final_norm.w0"}
    for i, (_, sparse) in enumerate(ref.layers_of(cfg)):
        at, prog = "l%d." % i, "%s.l%d." % (PREFIX, i)
        for norm in ("norm1", "norm2"):
            names[at + norm] = prog + norm + ".w0"
        attention, ffn = ref.leaves_of(sparse)
        for leaf in attention:
            names[at + leaf] = prog + "mixer." + leaf
        if sparse:
            for leaf in ffn:
                names[at + leaf] = prog + "moe." + leaf
        else:
            names[at + "mlp_in"] = prog + "mlp.w0"
            names[at + "mlp_out"] = prog + "mlp.w1"
    return names
