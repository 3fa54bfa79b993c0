"""Operations of one `olmo-hybrid-7b` train step, from shapes alone.

Counted forward, two operations per multiply-add, over the valid tokens
the traffic sends (not the positions the program pads to): every
projection, the gated MLP, the head; the delta rule as the recurrence
needs it, three products of the key width by the value width a head and
token (what the state holds under the key, the rank-one write, the read
through the query); attention as causal (a query sees the keys up to its
own). A train step is three times the forward; what a recomputed block
computes a second time is not counted, nor what the chunked form adds
(the triangular system, the products inside a chunk). The embedding
gather, the convolution's four taps, norms, gates and the optimizer are
left out. A fused delta-rule kernel's cost functions, for its
`delta_rule_kernel_roofline_pct`, belong here when one ships.
"""

from chipbench.flops.granite_h_micro import row_lengths  # noqa: F401


def per_token_flops(cfg):
    """{layer kind or "head": operations a token, forward}, attention's
    without its scores."""
    d, mlp = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    lh = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key, value = lh * dk, lh * dv
    gated_mlp = 2 * d * 2 * mlp + 2 * mlp * d
    return {
        "linear_attention": 2 * d * (2 * key + 2 * value + 2 * lh)
        + 2 * value * d + 3 * 2 * lh * dk * dv + gated_mlp,
        "full_attention": 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
        + gated_mlp,
        "head": 2 * d * cfg["vocab_size"],
    }


def forward_flops(cfg, workload):
    per = per_token_flops(cfg)
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    lengths = row_lengths(workload)
    tokens = sum(lengths)
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    # a query at position t sees t + 1 keys: scores and their product with v
    scores = sum(2 * 2 * heads * hd * n * (n + 1) // 2 for n in lengths)
    return tokens * (sum(per[k] for k in kinds) + per["head"]) \
        + kinds.count("full_attention") * scores


def train_step_flops(cfg, workload):
    return 3 * forward_flops(cfg, workload)
