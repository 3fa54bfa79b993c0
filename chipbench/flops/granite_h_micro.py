"""Operations of one `granite-4.0-h-micro` train step, from shapes alone.

Counted forward, two operations per multiply-add, over the valid tokens
the traffic sends (not the positions the program pads to): every
projection, the gated MLP, the head; the state-space scan as the
recurrence needs it (the outer product into the state and the read
through C); attention as causal (a query sees the keys up to its own).
A train step is three times the forward; what a recomputed block computes
a second time is not counted. The embedding gather, the convolution's
four taps, norms, gates and the optimizer are left out.
"""

import numpy as np


def row_lengths(workload):
    """The lengths of a batch's rows, as `traffic.make_pool` spreads them."""
    span = workload["lengths"]
    return [int(n) for n in np.rint(np.linspace(
        span["min"], span["max"], int(workload["batch"])))]


def per_token_flops(cfg):
    """{layer kind or "head": operations a token, forward}, attention's
    without its scores."""
    d, mlp = cfg["hidden_size"], cfg["shared_intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    mh, mp, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = mh * mp
    conv = inner + 2 * cfg["mamba_n_groups"] * n
    gated_mlp = 2 * d * 2 * mlp + 2 * mlp * d
    return {
        "mamba": 2 * d * (inner + conv + mh) + 2 * inner * d
        + 2 * 2 * mh * mp * n + gated_mlp,
        "attention": 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
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
        + kinds.count("attention") * scores


def train_step_flops(cfg, workload):
    return 3 * forward_flops(cfg, workload)
