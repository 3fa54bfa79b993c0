"""Operations of one `lfm2-8b-a1b` train step, from shapes alone.

Counted forward, two operations per multiply-add, over the valid tokens
the traffic sends (not the positions the program pads to): every
projection, the dense gated MLPs, the router, the head; attention as
causal (a query sees the keys up to its own); the experts at **uniform
routing**: of a token's `num_experts_per_tok` choices the share `held /
total` falls on the experts held here, so `k * held / total` rows a token
and layer cross one expert's gated MLP, whatever the seed (the histogram
`paddle_tpu_moe_rows_here` says how far from uniform a run was). A train
step is three times the forward; what a recomputed block computes a second
time is not counted. The embedding gather, the convolution's three taps,
norms, gates, the rotary turn, the sort and the optimizer are left out.

`grouped_matmul_cost` is the grouped products' operations and bytes for
the rows actually in the groups, for a `moe_grouped_matmul_roofline_pct`
when the products run as a kernel of this repository's; `jax.lax.ragged_dot`
owes none.
"""

from chipbench.flops.granite_h_micro import row_lengths  # noqa: F401
from chipbench.reference.lfm2_moe import experts_of, layers_of


def per_token_flops(cfg):
    """{"conv", "full_attention" (without its scores), "dense", "experts",
    "head": operations a token, forward}."""
    d, mlp = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    total, held, _ = experts_of(cfg)
    width, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    expert = 2 * d * 2 * width + 2 * width * d
    return {
        "conv": 2 * d * 3 * d + 2 * d * d,
        "full_attention": 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d,
        "dense": 2 * d * 2 * mlp + 2 * mlp * d,
        # the router over all experts, and the token's rows here
        "experts": 2 * d * total + expert * k * held / total,
        "head": 2 * d * cfg["vocab_size"],
    }


def keys_seen(n):
    """Pairs of (query, key it sees) in a row of n tokens."""
    return n * (n + 1) // 2


def forward_flops(cfg, workload):
    per = per_token_flops(cfg)
    lengths = row_lengths(workload)
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    layers = layers_of(cfg)
    tokens = sum(per[kind] + per["experts" if sparse else "dense"]
                 for kind, sparse in layers) + per["head"]
    # a query and a key it sees: a score of hd products a head, and its
    # weight times hd values
    scores = sum(kind == "full_attention" for kind, _ in layers) \
        * heads * 4 * hd * sum(keys_seen(n) for n in lengths)
    return int(sum(lengths) * tokens + scores)


def train_step_flops(cfg, workload):
    return 3 * forward_flops(cfg, workload)


def grouped_matmul_cost(cfg, rows, itemsize=2):
    """(operations, bytes) of one expert layer's two grouped products
    forward over `rows` rows inside the groups: the rows read and written
    once, every held expert's matrices read once."""
    d, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = experts_of(cfg)[1]
    flops = rows * (2 * d * 2 * width + 2 * width * d)
    nbytes = itemsize * (rows * (d + 2 * width + width + d)
                         + held * (d * 2 * width + width * d))
    return flops, nbytes
