"""Operations of one `phi-4-mini-flash-reasoning` train step, from shapes
alone.

Counted forward, two operations per multiply-add, over the valid tokens
the traffic sends (not the positions the program pads to): every
projection, the gated MLP, the Gated Memory Unit's two products, the head;
the selective scan as its recurrence needs it, three multiply-adds a
channel, state and token (the decay times the state, the input times B,
the read through C); attention as causal and windowed (a query sees the
keys up to its own, at most `sliding_window` of them in a window layer),
both softmaxes of a pair counted: a pair of heads makes two score maps
over the head size and multiplies each with the pair's values, twice the
head size wide. A train step is three times the forward; what a
recomputed block computes a second time is not counted, nor what the
scan's backward steps again. The embedding gather, the convolution's four
taps, norms, gates, exponentials and the optimizer are left out. A fused
scan kernel's cost functions, for its
`selective_scan_kernel_roofline_pct`, belong here when one ships.
"""

from chipbench.flops.granite_h_micro import row_lengths  # noqa: F401
from chipbench.reference.phi4_flash import mamba_sizes


def per_token_flops(cfg):
    """{layer kind or "head": operations a token, forward}, attention's
    without its scores."""
    d, mlp = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    inner, n, _, rank = mamba_sizes(cfg)
    gated_mlp = 2 * d * 2 * mlp + 2 * mlp * d
    attention = 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
    return {
        "mamba1": 2 * d * 2 * inner + 2 * inner * (rank + 2 * n)
        + 2 * rank * inner + 3 * 2 * inner * n + 2 * inner * d + gated_mlp,
        "sliding_attention": attention + gated_mlp,
        "full_attention": attention + gated_mlp,
        "cross_attention": 2 * d * heads * hd + 2 * heads * hd * d
        + gated_mlp,
        "gmu": 2 * d * inner + 2 * inner * d + gated_mlp,
        "head": 2 * d * cfg["vocab_size"],
    }


def keys_seen(n, window=None):
    """Pairs of (query, key it sees) in a row of n tokens: a query at
    position t sees t + 1 keys, at most `window` of them."""
    if window is None or window >= n:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def forward_flops(cfg, workload):
    per = per_token_flops(cfg)
    kinds = [cfg["layer_types"][i] for i in cfg["kept_layers"]]
    lengths = row_lengths(workload)
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    # a query and a key it sees: a score of hd products a head, and its
    # weight times the pair's 2 hd values
    pair = heads * (2 * hd + 2 * 2 * hd)
    windows = {"sliding_attention": cfg["sliding_window"],
               "full_attention": None, "cross_attention": None}
    scores = sum(pair * keys_seen(n, windows[k])
                 for k in kinds if k in windows for n in lengths)
    return sum(lengths) * (sum(per[k] for k in kinds) + per["head"]) + scores


def train_step_flops(cfg, workload):
    return 3 * forward_flops(cfg, workload)
