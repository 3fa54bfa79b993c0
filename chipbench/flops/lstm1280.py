"""Operations and bytes of one `lstm1280` train step, from shapes alone.

Counted forward: the two input projections, the two recurrent products
and the classifier, two operations per multiply-add, over the tokens the
traffic sends (`seq_len`, not the length the program pads to). A train
step is three times the forward. The embedding gather, the gates'
elementwise work and the optimizer are left out.
"""


def forward_flops(cfg, workload):
    rows, steps = workload["batch"], workload["lengths"]["max"]
    hidden, emb = cfg["hidden_size"], cfg["emb_size"]
    per_token = 2 * emb * 4 * hidden + 3 * (2 * hidden * 4 * hidden)
    return rows * steps * per_token \
        + rows * 2 * hidden * cfg["num_classes"]


def train_step_flops(cfg, workload):
    return 3 * forward_flops(cfg, workload)


def lstm_kernel_cost(cfg, workload, itemsize=2):
    """(operations, bytes, bound) that the fused LSTM kernels of one train
    step need: per layer and time step, the forward kernel multiplies
    h [B, H] by W [H, 4H] and the backward kernel the gates' gradient
    [B, 4H] by W transposed (the weights' own gradient is one product
    outside the kernels and is not theirs). At H = 1280 the weights do not
    stay in fast memory, so each kernel reads W once per time step, beside
    its streamed blocks: forward gates in, h and c out; backward gates,
    previous h, previous c, c and the incoming gradient in, the gates'
    gradient out. `bound` names the larger of the two least times on a
    chip with `peaks`: see `least_seconds`."""
    rows, steps = workload["batch"], workload["lengths"]["max"]
    hidden, layers = cfg["hidden_size"], cfg["num_lstm_layers"]
    product = 2 * rows * hidden * 4 * hidden
    flops = layers * steps * 2 * product
    w = hidden * 4 * hidden * itemsize
    gate = rows * 4 * hidden * itemsize
    vec = rows * hidden * itemsize
    fwd = w + gate + 2 * vec
    bwd = w + gate + 4 * vec + gate
    return flops, layers * steps * (fwd + bwd)


def least_seconds(flops, nbytes, peaks):
    """(seconds, which bound) of the roofline: the larger of operations
    over the peak rate and bytes over the peak bandwidth."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "bandwidth")
