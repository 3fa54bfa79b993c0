"""The benchmark's own operation counts, pinned: against XLA's
`cost_analysis()` of the plain references' forward at a small size, and at
the cells' sizes to the number."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench.flops import lstm1280 as lstm_flops
from chipbench.flops import resnet50 as resnet_flops
from chipbench.reference import lstm1280 as lstm_ref
from chipbench.reference import resnet50 as resnet_ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


def test_lstm_count_is_pinned_at_the_cell_size():
    cell = {"batch": 256, "lengths": {"min": 100, "max": 100}}
    assert lstm_flops.train_step_flops(_cfg("lstm1280"), cell) \
        == 3_120_566_108_160


def test_resnet50_count_is_pinned_at_the_cell_size():
    flops = resnet_flops.train_step_flops(_cfg("resnet50"), {"batch": 256})
    assert flops == 6_460_851_486_720
    # the stem's pool rounds up: 57, 29, 15, 8
    sizes = [s[5] for s in resnet_flops.conv_shapes(_cfg("resnet50"))]
    assert sizes[0] == 112 and sorted(set(sizes[1:])) == [8, 15, 29, 57]


def test_lstm_count_agrees_with_xla_on_the_reference_forward():
    cfg = dict(_cfg("lstm1280"), dict_size=500, emb_size=64,
               hidden_size=128)
    cell = {"batch": 8, "lengths": {"min": 16, "max": 16}}
    weights, state = lstm_ref.init_weights(3, cfg)
    batch = (jnp.zeros((8, 16), jnp.int32), jnp.full((8,), 16, jnp.int32),
             jnp.zeros((8,), jnp.int32))
    # the scan's body is counted once by XLA: unroll by hand, one step
    one = {"batch": 8, "lengths": {"min": 1, "max": 1}}
    step = (batch[0][:, :1], jnp.ones((8,), jnp.int32), batch[2])
    xla = _xla_flops(lambda w: lstm_ref.loss(w, state, step, cfg)[0], weights)
    mine = lstm_flops.forward_flops(cfg, one)
    assert mine <= xla <= 1.15 * mine, (mine, xla)
    assert lstm_flops.forward_flops(cfg, cell) == pytest.approx(
        16 * (mine - 8 * 2 * 128 * 2) + 8 * 2 * 128 * 2)


def test_resnet50_count_agrees_with_xla_on_the_reference_forward():
    cfg = dict(_cfg("resnet50"), im_size=32, num_classes=10)
    weights, state = resnet_ref.init_weights(3, cfg)
    batch = (jnp.zeros((2, 3 * 32 * 32), jnp.float32),
             jnp.zeros((2,), jnp.int32))
    xla = _xla_flops(lambda w: resnet_ref.loss(w, state, batch, cfg)[0],
                     weights)
    mine = resnet_flops.forward_flops(cfg, {"batch": 2})
    # XLA also counts batch norm, pooling and the cost: a little more
    assert mine <= xla <= 1.1 * mine, (mine, xla)


def test_lstm_kernel_roofline_is_bound_by_bandwidth_at_h1280():
    cell = {"batch": 256, "lengths": {"min": 100, "max": 100}}
    flops, nbytes = lstm_flops.lstm_kernel_cost(_cfg("lstm1280"), cell)
    assert flops == 2 * 100 * 2 * (2 * 256 * 1280 * 5120)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = lstm_flops.least_seconds(flops, nbytes, peaks)
    assert bound == "bandwidth" and 0.008 < seconds < 0.011


def test_lstm_kernel_roofline_reader_says_nothing_without_kernel_time():
    from chipbench.metrics import lstm_kernel_roofline as reader

    mark = "tpu_custom_call"
    ctx = {"cfg": _cfg("lstm1280"), "flops": lstm_flops,
           "device_kind": "TPU v5 lite", "traced_steps": 20,
           "cell": {"batch": 256, "lengths": {"min": 100, "max": 100},
                    "trace": {"kernel_marks": [mark]}},
           "trace": {"kernel_s": {mark: 0.4}}}
    # 20 steps of 20 ms in the kernels against a least time of 8 to 11 ms
    assert 40 < reader.read(ctx) < 55
    # a reader that finds nothing returns nothing: no 0 for a roofline
    ctx["trace"] = {"kernel_s": {mark: 0.0}}
    assert reader.read(ctx) is None
    assert reader.read({**ctx, "trace": None}) is None
