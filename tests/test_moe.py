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
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops import pallas_moe
from paddle_tpu.topology import Topology


@pytest.fixture(autouse=True)
def _highest():
    L.reset_name_counters()
    jax.config.update("jax_enable_x64", False)  # check_layer_grad sets it
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
    out, here, busiest, _ = moe_ops.moe(
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
    # the plain form observes no row tiles
    assert sorted(counts) == sorted(
        set(step_counts.COUNTS) - {"paddle_tpu_moe_rows_visited"})
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


# -- the fused form (ops/pallas_moe.py), interpreted on the CPU ---------------

_N, _D, _W, _E, _HELD, _FIRST, _K = 96, 128, 128, 8, 4, 2, 2


@pytest.fixture()
def small_tiles(monkeypatch):
    """Tiles of 32 rows: the 192 sorted rows make six, and groups share
    them. (The tile is a constant of the jitted calls: no trace made with
    one tile may serve the other.)"""
    jax.clear_caches()
    monkeypatch.setattr(pallas_moe, "_ROW_TILE", 32)
    yield
    jax.clear_caches()


def _routed(routing, seed=4):
    """The layer's inputs, routed as ``routing`` says: the selection bias
    steers every token's choices."""
    rng = np.random.default_rng(seed)
    held = (np.arange(_E) >= _FIRST) & (np.arange(_E) < _FIRST + _HELD)
    bias = {"uniform": np.zeros(_E), "padded": np.zeros(_E),
            "all_here": 10.0 * held, "none_here": 10.0 * ~held,
            "all_on_one": 10.0 * (np.arange(_E) == _FIRST) + 5.0 * ~held,
            }[routing]
    valid = np.arange(_N) % 48 < (20 if routing == "padded" else 44)
    return dict(
        x=jnp.asarray(rng.standard_normal((_N, _D)), jnp.float32),
        router=jnp.asarray(0.3 * rng.standard_normal((_D, _E)), jnp.float32),
        w_in=jnp.asarray(0.1 * rng.standard_normal((_HELD, _D, 2 * _W)),
                         jnp.float32),
        w_out=jnp.asarray(0.1 * rng.standard_normal((_HELD, _W, _D)),
                          jnp.float32),
        bias=jnp.asarray(bias, jnp.float32), valid=jnp.asarray(valid),
        cot=jnp.asarray(rng.standard_normal((_N, _D)), jnp.float32))


def _layer(inputs, kept=lambda product: product):
    """(loss, rows here) and the gradients by x, the router, w_in and
    w_out."""
    def loss(x, router, w_in, w_out):
        out, here, _, _ = moe_ops.moe(x, inputs["valid"], router,
                                   inputs["bias"], w_in, w_out, _K, _FIRST,
                                   kept=kept)
        return jnp.sum(out * inputs["cot"]), here

    return jax.value_and_grad(loss, argnums=range(4), has_aux=True)(
        inputs["x"], inputs["router"], inputs["w_in"], inputs["w_out"])


def _by_form(inputs, monkeypatch, kept=lambda product: product):
    got = {}
    for form in ("plain", "fused"):
        monkeypatch.setattr(pk, "_INTERPRET", form == "fused")
        assert moe_ops.experts_form(_D, _W, _N, _K) == form
        got[form] = _layer(inputs, kept)
    return got


def _agree(got, want):
    (value, here), grads = got
    (want_value, want_here), want_grads = want
    assert int(here) == int(want_here)
    np.testing.assert_allclose(value, want_value, rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("x", "router", "w_in", "w_out"), grads,
                          want_grads):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(
            g, w, rtol=1e-5, atol=1e-5 * max(1.0, float(jnp.abs(w).max())),
            err_msg=name)


@pytest.mark.parametrize("routing", ["uniform", "all_here", "none_here",
                                     "all_on_one", "padded"])
def test_the_fused_form_is_the_plain_one(routing, small_tiles, monkeypatch):
    """The kernels give the plain form's output and gradients by x, the
    router, w_in and w_out whatever the routing: groups that share tiles,
    empty groups, one group, none at all, half the positions padding."""
    inputs = _routed(routing)
    got = _by_form(inputs, monkeypatch)
    here = int(got["plain"][0][1])
    want_here = {"all_here": _K * 88, "none_here": 0, "all_on_one": 88,
                 "padded": None, "uniform": None}[routing]
    assert want_here is None or here == want_here
    _agree(got["fused"], got["plain"])


def test_a_recomputed_block_keeps_the_same_first_product(small_tiles,
                                                         monkeypatch,
                                                         capsys):
    """Under a block that keeps ``MOE_PRODUCT`` the fused form keeps the
    product the plain form keeps (its rows in the groups), and it is the
    one [k * N, 2 width] value the block saves; the gradients are those
    without the block."""
    from jax.ad_checkpoint import checkpoint_name

    from paddle_tpu.layer import decoder

    inputs = _routed("uniform")
    seen = {}

    def keep(form):
        def kept(product):
            seen[form] = product
            return product
        return kept

    for form in ("plain", "fused"):
        monkeypatch.setattr(pk, "_INTERPRET", form == "fused")
        moe_ops.moe(inputs["x"], inputs["valid"], inputs["router"],
                    inputs["bias"], inputs["w_in"], inputs["w_out"], _K,
                    _FIRST, kept=keep(form))
    rows = int(moe_ops.moe(inputs["x"], inputs["valid"], inputs["router"],
                           inputs["bias"], inputs["w_in"], inputs["w_out"],
                           _K, _FIRST)[1])
    assert seen["fused"].shape == seen["plain"].shape == (_K * _N, 2 * _W)
    np.testing.assert_allclose(seen["fused"][:rows], seen["plain"][:rows],
                               rtol=1e-5, atol=1e-5)

    policy = jax.checkpoint_policies.save_only_these_names(
        decoder.MOE_PRODUCT)

    def block(x, router, w_in, w_out):
        return jnp.sum(moe_ops.moe(
            x, inputs["valid"], router, inputs["bias"], w_in, w_out, _K,
            _FIRST, kept=lambda p: checkpoint_name(p, decoder.MOE_PRODUCT)
        )[0] * inputs["cot"])

    args = (inputs["x"], inputs["router"], inputs["w_in"], inputs["w_out"])
    jax.ad_checkpoint.print_saved_residuals(
        jax.checkpoint(block, policy=policy), *args)
    saved = capsys.readouterr().out.splitlines()
    wide = [line for line in saved
            if "[%d,%d]" % (_K * _N, 2 * _W) in line.replace(" ", "")]
    # the value the name marks (JAX says where it was named)
    assert len(wide) == 1 and "block.<locals>.<lambda>" in wide[0], \
        "\n".join(saved)
    grads = jax.grad(jax.checkpoint(block, policy=policy),
                     argnums=range(4))(*args)
    for g, w in zip(grads, jax.grad(block, argnums=range(4))(*args)):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


def _poisoned(fn, rows_written):
    """``fn`` whose results over the sorted rows hold NaN past the rows
    ``rows_written(args)`` says it wrote."""
    def poison(out, written):
        keep = jnp.arange(out.shape[0]) < written
        keep = keep.reshape((-1,) + (1,) * (out.ndim - 1))
        return jnp.where(keep, out, jnp.nan).astype(out.dtype)

    def wrapped(*args, **kwargs):
        out = fn(*args, **kwargs)
        written = rows_written(args)
        if isinstance(out, (tuple, list)):
            return type(out)(poison(o, written) for o in out)
        return poison(out, written)
    return wrapped


@pytest.mark.parametrize("form", ["plain", "fused"])
def test_rows_outside_the_groups_never_reach_a_sum(form, small_tiles,
                                                   monkeypatch):
    """Every row of the sorted buffers past the groups filled with NaN
    (what a kernel never writes may hold anything): output and gradients
    stay finite and equal to the untouched plain form's."""
    inputs = _routed("uniform")
    want = _layer(inputs)
    if form == "fused":
        monkeypatch.setattr(pk, "_INTERPRET", True)
        # the gathers write the tiles that hold the groups: their count
        # comes last
        for name in ("_gather", "_gather_t"):
            monkeypatch.setattr(pallas_moe, name, _poisoned(
                getattr(pallas_moe, name),
                lambda a: a[-1] * pallas_moe._ROW_TILE))
        # the products write the groups' rows: the last of the groups'
        # offsets
        for name in ("_gmm_in", "_gmm_out", "_gmm_out_t", "_gmm_in_t"):
            monkeypatch.setattr(pallas_moe, name, _poisoned(
                getattr(pallas_moe, name),
                lambda a: [m for m in a if isinstance(m, tuple)][0][0][-1]))
    else:
        grouped = moe_ops._grouped
        monkeypatch.setattr(moe_ops, "_grouped", lambda rows, w, sizes:
                            _poisoned(grouped, lambda a: jnp.sum(sizes))(
                                rows, w, sizes))
    assert moe_ops.experts_form(_D, _W, _N, _K) == form
    _agree(_layer(inputs), want)


def _moe_gauges():
    gauges = observe_metrics.get_registry().snapshot()["gauges"]
    return gauges["paddle_tpu_moe_fused"], gauges["paddle_tpu_moe_plain"]


@pytest.mark.parametrize("width,interpret,want", [
    (128, True, (2, 0)), (128, False, (0, 2)), (96, True, (0, 2))])
def test_the_gauges_count_the_expert_layers_by_form(width, interpret, want,
                                                    small_tiles,
                                                    monkeypatch):
    """Two expert layers traced for training: with Pallas at hand and
    widths of 128 both take the kernels; on the plain CPU, or at a width
    that does not tile the lanes, both the plain form."""
    monkeypatch.setattr(pk, "_INTERPRET", interpret)
    data = L.data(name="x", type=data_type.dense_vector_sequence(_D))
    h = L.moe(input=data, experts_total=4, experts_held=2, first_held=1,
              top_k=2, width=width, name="a")
    topo = Topology(L.moe(input=h, experts_total=4, experts_held=4,
                          first_held=0, top_k=2, width=width, name="b"))
    x = SequenceBatch(jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 16, _D)), jnp.float32), jnp.asarray([16, 9], jnp.int32))
    topo.apply(topo.init_params(jax.random.PRNGKey(0)), {"x": x},
               mode="train")
    assert _moe_gauges() == want


@pytest.mark.parametrize("tokens,k,form", [
    (8192, 4, "fused"), (16384, 8, "fused"), (32768, 8, "plain"),
    (16384, 16, "plain")])
def test_the_fused_form_needs_its_pair_lists_in_the_scalar_memory(
        tokens, k, form, monkeypatch):
    """The gathers and the pair sums prefetch an int32 a pair whole into
    the 1 MiB scalar memory: lfm2's top-4 of 8,192 positions and laguna's
    top-8 of 16,384 (131,072 pairs, 512 KiB) compile for v5e; twice
    laguna's pairs take the plain form before Mosaic could refuse."""
    monkeypatch.setattr(pk, "_INTERPRET", True)
    assert moe_ops.experts_form(2048, 512, tokens, k) == form


def test_the_cost_of_the_kernels_from_shapes():
    """Pinned at the `lfm2-8b-a1b` cell's widths and 8,192 rows in the
    groups against `chipbench/flops/lfm2_moe.py grouped_matmul_cost`,
    which counts the two grouped products forward: the same operations;
    the same bytes once the gather's (a row read from x and written to
    the buffer) and the gate's second half are added (the fused second
    product reads both halves of the first's output; the plain one read
    the gated [rows, width] array, which the kernels never write)."""
    import json

    from chipbench.flops import lfm2_moe as flops
    from chipbench.reference.lfm2_moe import experts_of

    with open("chipbench/configs/lfm2-8b-a1b.json") as f:
        cfg = json.load(f)
    rows, d, width = 8192, cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = experts_of(cfg)[1]
    assert (d, width, held) == (2048, 1792, 8)
    cost = pallas_moe.moe_kernel_cost(rows, d, width, held, 8192)
    forward = [cost[name] for name in ("moe_gather", "moe_gmm_in",
                                       "moe_gmm_out")]
    want_flops, want_bytes = flops.grouped_matmul_cost(cfg, rows)
    assert sum(c["flops"] for c in forward) == want_flops
    gather, gate_half = 2 * rows * d * 2, rows * width * 2
    assert sum(c["bytes"] for c in forward) \
        == want_bytes + gather + gate_half
    # backward: the two products again, each as a row gradient and a
    # weight gradient
    backward = sum(cost[name]["flops"] for name in (
        "moe_gmm_out_t", "moe_tgmm_out", "moe_gmm_in_t", "moe_tgmm_in"))
    assert backward == 2 * want_flops


@pytest.mark.parametrize("visit_empty", [False, True])
def test_the_groups_are_laid_out_as_megablox_lays_them(visit_empty,
                                                       small_tiles):
    """`pallas_moe.groups` against `make_group_metadata` of the installed
    `jax.experimental.pallas.ops.tpu.megablox` over random sizes, empty
    groups and full buffers: the same offsets, steps and, for each step,
    group and tile."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata

    rng = np.random.default_rng(6)
    for _ in range(20):
        rows, held = 32 * int(rng.integers(1, 9)), int(rng.integers(1, 9))
        total = rows if rng.random() < 0.2 else int(rng.integers(0, rows))
        sizes = np.diff(np.concatenate([[0], np.sort(rng.integers(
            0, total + 1, held - 1)), [total]]))
        sizes = jnp.asarray(sizes, jnp.int32)
        (offsets, ids, tiles), steps = make_group_metadata(
            group_sizes=sizes, m=rows, tm=32,
            start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=held,
            visit_empty_groups=visit_empty)
        (mine, my_ids, my_tiles), my_steps = pallas_moe.groups(
            sizes, rows=rows, visit_empty=visit_empty)
        n = int(steps)
        assert int(my_steps) == n
        np.testing.assert_array_equal(mine, offsets)
        np.testing.assert_array_equal(my_ids[:n], ids[:n])
        np.testing.assert_array_equal(my_tiles[:n], tiles[:n])
