"""The fused LSTM kernels' share of their roofline: the least time the
chip could take for their operations and bytes (`flops/lstm1280.py`: at
h1280 the bytes bound it) over the time of the trace's `_lstm_*_kernel`
events per step. Nothing where the step holds no such event."""

from chipbench import peaks


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_steps"]:
        return None
    spent = sum(trace["kernel_s"].get(m, 0.0)
                for m in ctx["cell"]["trace"].get("kernel_marks", ()))
    if spent <= 0:
        return None
    flops, nbytes = ctx["flops"].lstm_kernel_cost(ctx["cfg"], ctx["cell"])
    least, _ = ctx["flops"].least_seconds(flops, nbytes,
                                          peaks.of(ctx["device_kind"]))
    return 100.0 * least * ctx["traced_steps"] / spent
