"""`ops/moe.py` and the step counters of `observe/step_counts.py`: the
dispatch drops no pair and groups by held expert at any routing, the two
gathers are each other's backward, the layer equals its sum written out
token by token, a recomputed block hands the counters of the layers
inside it out, and a model without expert layers returns no counter.
(The layer against the benchmark's plain reference, its shares and the
whole model are in `tests/chipbench/test_chipbench_lfm2_moe.py`.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import data_type
from paddle_tpu import layer as L
from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.observe import step_counts
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.topology import Topology


@pytest.fixture(autouse=True)
def _highest():
    L.reset_name_counters()
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("routing", ["random", "all_on_one", "all_absent",
                                     "all_held"])
def test_the_dispatch_drops_no_pair_at_any_routing(routing):
    n, k, total, first, held = 50, 3, 12, 4, 5
    rng = np.random.default_rng(0)
    chosen = np.stack([rng.permutation(total)[:k] for _ in range(n)])
    if routing == "all_on_one":
        chosen[:, 0] = 6
        chosen[:, 1:] = [0, 1]
    elif routing == "all_absent":
        chosen = chosen % first
    elif routing == "all_held":
        chosen = first + chosen % held
    valid = np.arange(n) < 44
    order, place, sizes, here = moe_ops.dispatch(
        jnp.asarray(chosen, jnp.int32), jnp.asarray(valid), first, held)
    order, place, sizes, here = (np.asarray(a) for a in (order, place,
                                                         sizes, here))
    want_here = (chosen >= first) & (chosen < first + held) & valid[:, None]
    np.testing.assert_array_equal(here, want_here)
    # a permutation of the k * n pairs and its inverse: nothing is lost
    assert sorted(order) == list(range(n * k))
    np.testing.assert_array_equal(place[order], np.arange(n * k))
    # the held pairs come first, grouped by expert in order, every one
    flat = chosen.reshape(-1)
    used = int(sizes.sum())
    assert used == want_here.sum()
    np.testing.assert_array_equal(
        sizes, [(want_here & (chosen == first + e)).sum()
                for e in range(held)])
    np.testing.assert_array_equal(
        flat[order[:used]], np.repeat(first + np.arange(held), sizes))
    assert want_here.reshape(-1)[order[:used]].all()
    assert not want_here.reshape(-1)[order[used:]].any()
    if routing == "all_held":
        assert used == k * 44   # the bound, but for the padding


def test_the_two_gathers_are_each_others_backward():
    n, k, d = 20, 2, 6
    rng = np.random.default_rng(1)
    order = jnp.asarray(rng.permutation(n * k), jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((n * k, d)), jnp.float32)
    mine = jax.grad(lambda x_: jnp.sum(
        moe_ops.gather_rows(x_, order, place, k) * g))(x)
    plain = jax.grad(lambda x_: jnp.sum(x_[order // k] * g))(x)
    np.testing.assert_allclose(mine, plain, rtol=1e-6)
    y = jnp.asarray(rng.standard_normal((n * k, d)), jnp.float32)
    mine = jax.grad(lambda y_: jnp.sum(
        moe_ops._unsort(y_, order, place) * g))(y)
    plain = jax.grad(lambda y_: jnp.sum(y_[place] * g))(y)
    np.testing.assert_allclose(mine, plain, rtol=1e-6)


def _written_out(x, valid, router, bias, w_in, w_out, k, first):
    """The layer token by token in numpy."""
    scores = 1.0 / (1.0 + np.exp(-(x @ router)))
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        if not valid[n]:
            continue
        chosen = np.argsort(-(scores[n] + bias), kind="stable")[:k]
        norm = scores[n][chosen].sum() + 1e-6
        for e in chosen:
            if first <= e < first + w_in.shape[0]:
                h = x[n] @ w_in[e - first]
                a, b = np.split(h, 2)
                out[n] += scores[n][e] / norm * (
                    (a / (1.0 + np.exp(-a)) * b) @ w_out[e - first])
    return out


def test_the_layer_is_its_sum_written_out_token_by_token():
    rng = np.random.default_rng(2)
    n, d, total, held, first, k, width = 30, 8, 6, 3, 2, 2, 5
    x = rng.standard_normal((n, d)).astype(np.float32)
    router = rng.standard_normal((d, total)).astype(np.float32)
    bias = (0.3 * rng.standard_normal(total)).astype(np.float32)
    w_in = rng.standard_normal((held, d, 2 * width)).astype(np.float32) * 0.4
    w_out = rng.standard_normal((held, width, d)).astype(np.float32) * 0.4
    valid = np.arange(n) < 26
    out, here, busiest = moe_ops.moe(
        jnp.asarray(x), jnp.asarray(valid), jnp.asarray(router),
        jnp.asarray(bias), jnp.asarray(w_in), jnp.asarray(w_out), k, first)
    want = _written_out(x.astype(np.float64), valid, router, bias, w_in,
                        w_out, k, first)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert 0 < int(busiest) <= int(here) <= k * 26


def test_a_recomputed_block_hands_its_layers_counters_out():
    """Two expert layers, one inside a recomputed block: the step's
    counters are the sum and the maximum over both, with the block and
    without it, and gradients flow through the block as they did."""
    x = SequenceBatch(jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 10, 8)), jnp.float32), jnp.asarray([10, 7], jnp.int32))

    def build(recompute):
        L.reset_name_counters()
        data = L.data(name="x", type=data_type.dense_vector_sequence(8))
        inner = L.moe(input=data, experts_total=4, experts_held=2,
                      first_held=1, top_k=2, width=6, name="a")
        block = L.recompute(inner, inputs=[data], enabled=recompute,
                            name="block")
        return L.moe(input=block, experts_total=4, experts_held=4,
                     first_held=0, top_k=1, width=6, name="b")

    read = {}
    for recompute in (False, True):
        topo = Topology(build(recompute))
        params = topo.init_params(jax.random.PRNGKey(0))
        static = {n: v for n, v in params.items()
                  if n.endswith("expert_bias")}
        train = {n: v for n, v in params.items() if n not in static}

        def loss(p):
            counts = {}
            out = topo.apply({**p, **static}, {"x": x}, mode="train",
                             counts=counts)[0]["b"].data
            return jnp.sum(out * out), counts

        (value, counts), grads = jax.value_and_grad(loss, has_aux=True)(
            train)
        read[recompute] = (value, {k: int(v) for k, v in counts.items()},
                           grads)
    assert read[True][1] == read[False][1]
    counts = read[True][1]
    assert sorted(counts) == sorted(step_counts.COUNTS)
    # layer b holds every expert and takes one choice of 17 valid tokens
    assert counts["paddle_tpu_moe_rows_here"] >= 17
    assert counts["paddle_tpu_moe_expert_load_max"] >= 17 / 4
    np.testing.assert_allclose(read[True][0], read[False][0], rtol=1e-6)
    for name, grad in read[False][2].items():
        np.testing.assert_allclose(read[True][2][name], grad, atol=1e-6,
                                   err_msg=name)
    gauges = observe_metrics.get_registry().snapshot()["gauges"]
    assert gauges["paddle_tpu_moe_rows_bound"] == 2 * 20 + 1 * 20


def test_a_model_without_expert_layers_returns_no_counter():
    """The trainer's step of such a model has the outputs it had: nothing
    rides beside its cost."""
    import paddle_tpu as paddle

    paddle.init(use_tpu=False, seed=1)
    L.reset_name_counters()
    tokens = L.data(name="t", type=data_type.integer_value_sequence(16))
    labels = L.data(name="y", type=data_type.integer_value_sequence(16))
    h = L.embedding(input=tokens, size=8, name="e")
    h = L.recompute(L.gated_mlp(input=h, size=8, name="mlp"), inputs=[h],
                    name="block")
    cost = L.lm_cost(input=L.lm_head(input=h, vocab=16, param_attr=None,
                                     name="head"), label=labels, name="c")
    trainer = paddle.trainer.SGD(
        cost, paddle.parameters.create(cost),
        paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9))
    from paddle_tpu.topology import convert_feed

    feed = convert_feed(trainer.topology,
                        [(np.arange(5, dtype=np.int32),
                          np.arange(5, dtype=np.int32))])
    out = jax.eval_shape(trainer._train_step, trainer._trainable,
                         trainer._replica, trainer._static, trainer._state,
                         trainer._opt_state, feed, trainer._rng)
    assert out[-1] == {}


def test_observe_adds_one_observation_a_step():
    registry = observe_metrics.MetricsRegistry()
    step_counts.observe(registry, {"paddle_tpu_moe_rows_here":
                                   np.asarray([3, 5])}, index=1)
    step_counts.observe(registry, {"paddle_tpu_moe_rows_here": 7})
    held = registry.snapshot()["histograms"]["paddle_tpu_moe_rows_here"]
    assert (held["count"], held["sum"]) == (2, 12.0)
    step_counts.observe(registry, {})
