"""Operations of one `resnet50` train step, from shapes alone.

Counted: the multiply-adds of every convolution and of the classifier,
two operations each, forward, and only those that read the input: a tap
that falls on the zero padding is no work (so the count is what XLA's own
cost analysis gives for the convolutions, about 2% under the usual
k*k*c_in*c_out*oh*ow); a train step is three times the forward
(the backward pass multiplies once for the input's gradient and once for
the weights'). Batch norm, pooling, activations and the optimizer are
bandwidth, not arithmetic, and are left out, so a share of the peak
computed from this count is a little low, never high. Nothing is counted
twice for rematerialisation.
"""


def _out(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def _taps(size, k, stride, pad):
    """Taps of a k-wide window that fall on the input, summed over the
    output positions along one axis."""
    return sum(min(o * stride - pad + k, size) - max(o * stride - pad, 0)
               for o in range(_out(size, k, stride, pad)))


def conv_shapes(cfg):
    """[(name, kh, kw, c_in, c_out, out_h, out_w, taps)] in forward order,
    with the stem's pool rounding its output size up, as the configuration
    states; `taps` is the window positions that read the input, both axes
    multiplied."""
    stages = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
    im = cfg["im_size"]
    size = _out(im, 7, 2, 3)
    shapes = [("stem", 7, 7, 3, 64, size, size, _taps(im, 7, 2, 3) ** 2)]
    size = -(-(size + 2 - 3) // 2) + 1
    c_in = 64
    for stage, (n, ch) in enumerate(zip(stages[cfg["depth"]],
                                        (64, 128, 256, 512))):
        for i in range(n):
            stride = 2 if (i == 0 and stage > 0) else 1
            out = _out(size, 1, stride, 0)
            p = "s%db%d" % (stage + 2, i)
            if c_in != ch * 4 or stride != 1:
                shapes.append((p + ".sc", 1, 1, c_in, ch * 4, out, out,
                               out * out))
            shapes.append((p + ".a", 1, 1, c_in, ch, out, out, out * out))
            shapes.append((p + ".b", 3, 3, ch, ch, out, out,
                           _taps(out, 3, 1, 1) ** 2))
            shapes.append((p + ".c", 1, 1, ch, ch * 4, out, out, out * out))
            c_in, size = ch * 4, out
    return shapes


def conv_forward_flops(cfg, rows):
    return rows * sum(2 * c_in * c_out * taps
                      for _, _, _, c_in, c_out, _, _, taps
                      in conv_shapes(cfg))


def forward_flops(cfg, workload):
    rows = workload["batch"]
    return conv_forward_flops(cfg, rows) \
        + rows * 2 * 2048 * cfg["num_classes"]


def train_step_flops(cfg, workload):
    return 3 * forward_flops(cfg, workload)
