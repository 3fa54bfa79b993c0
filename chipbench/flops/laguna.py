"""Operations of one `laguna-xs.2` train step, from shapes alone.

Counted forward, two operations per multiply-add, over the valid tokens
the traffic sends (not the positions the program pads to): every
projection and each head's gate, the dense SwiGLU, the router, the shared
expert, the head; attention as causal (a query sees the keys up to its
own) and, in a window layer, windowed (the `sliding_window` keys that end
with its own); the routed experts at **uniform routing**: of a token's
`num_experts_per_tok` choices the share `held / total` falls on the
experts held here, so `k * held / total` rows a token and layer cross one
expert's SwiGLU (1 at 8 of 256 with 32 held), whatever the seed (the
histogram `paddle_tpu_moe_rows_here` says how far from uniform a run
was). A train step is three times the forward; what a recomputed block
computes a second time is not counted. The embedding gather, norms, the
gates' sigmoids, the rotary turn, the sort and the optimizer are left
out.
"""

from chipbench.flops.granite_h_micro import row_lengths  # noqa: F401
from chipbench.flops.lfm2_moe import keys_seen
from chipbench.reference.laguna import experts_of, heads_of, layers_of


def per_token_flops(cfg):
    """{"full_attention", "sliding_attention" (without their scores),
    "dense", "experts", "head": operations a token, forward}."""
    d, mlp, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    total, held, _ = experts_of(cfg)
    width, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    shared = cfg["shared_expert_intermediate_size"]

    def swiglu(w):
        return 2 * d * 2 * w + 2 * w * d

    def attention(heads):
        # q, k, v, the gate and o
        return 2 * d * (heads + 2 * kv) * hd + 2 * d * heads \
            + 2 * heads * hd * d

    return {
        "full_attention": attention(heads_of(cfg, "full_attention")),
        "sliding_attention": attention(heads_of(cfg, "sliding_attention")),
        "dense": swiglu(mlp),
        # the router over all experts, the token's rows here, the shared one
        "experts": 2 * d * total + swiglu(width) * k * held / total
        + swiglu(shared),
        "head": 2 * d * cfg["vocab_size"],
    }


def window_keys_seen(n, window):
    """Pairs of (query, key it sees) in a row of n tokens when a query sees
    the `window` keys that end with its own."""
    inside = min(n, window)
    return keys_seen(inside) + (n - inside) * window


def forward_flops(cfg, workload):
    per = per_token_flops(cfg)
    lengths = row_lengths(workload)
    hd = cfg["head_dim"]
    layers = layers_of(cfg)
    tokens = sum(per[kind] + per["experts" if sparse else "dense"]
                 for kind, sparse in layers) + per["head"]
    # a query and a key it sees: a score of hd products a head, and its
    # weight times hd values
    scores = 0
    for kind, _ in layers:
        seen = sum(keys_seen(n) if kind == "full_attention"
                   else window_keys_seen(n, cfg["sliding_window"])
                   for n in lengths)
        scores += heads_of(cfg, kind) * 4 * hd * seen
    return int(sum(lengths) * tokens + scores)


def train_step_flops(cfg, workload):
    return 3 * forward_flops(cfg, workload)
