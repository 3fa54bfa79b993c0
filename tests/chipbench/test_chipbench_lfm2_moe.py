"""The `lfm2-8b-a1b` configuration's benchmark files: the configuration
against its manifest entry and the catalog's numbers, the expert layer,
the short convolution and rotary attention against the plain reference's
layers (all experts held, a share of them, every pair on held experts,
none; the four shares adding up to the uncut layer), the tiny preset of
the program against the reference leaf by leaf (the first gradient, and
three Momentum steps through `SGD.train`), the operation and parameter
counts, the gauges at the cell's shapes, the scopes in the compiled step,
a rehearsal of the cell, the readers, and the control at a tiny size."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, control, traffic
from chipbench.flops import lfm2_moe as flops
from chipbench.metrics import (moe_expert_load_max_over_mean,
                               moe_rows_here_pct)
from chipbench.models import lfm2_moe as bench_model
from chipbench.reference import common
from chipbench.reference import lfm2_moe as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
CONFIG = "lfm2-8b-a1b"
CELL = "lfm2-8b-a1b-seq4096-bs2-train"
# config.json of LiquidAI/LFM2-8B-A1B, the numbers that shape it (the
# catalog's entry)
PUBLISHED = {
    "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168,
    "max_position_embeddings": 128000, "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "vocab_size": 65536,
}
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                  "conv"] * 4 \
    + ["full_attention", "conv", "conv"] * 2


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _load("chipbench", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def cell():
    return _load("chipbench", "workloads", CELL + ".json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "chipbench", "tiny", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def tiny_cell():
    return _load("tests", "chipbench", "tiny", "workloads", CELL + ".json")


def test_the_configuration_holds_the_published_widths(cfg):
    entry = next(c for c in _load("BENCHMARK.json")["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "chipbench/configs/%s.json" % CONFIG
    reduced = ["num_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == reduced
    assert sorted(k for k, v in PUBLISHED.items() if cfg[k] != v) == reduced
    assert sorted(cfg["reduced"]) == reduced
    for key in reduced:
        assert cfg["reduced"][key]["published"] == PUBLISHED[key]
        assert cfg["reduced"][key]["here"] == cfg[key]
        assert cfg["reduced"][key]["how"]
    # the chip's share: 8 of the 32 experts the router scores, a quarter
    # of the table, the first ten layers: the two dense ones and two
    # whole periods of one attention layer to three convolutions
    assert (cfg["num_experts"], cfg["num_experts_published"],
            cfg["first_expert"]) == (8, 32, 0)
    assert ref.experts_of(cfg) == (32, 8, 0)
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert cfg["layer_types"] == LAYER_TYPES and len(LAYER_TYPES) == 24
    assert ref.layers_of(cfg) == [("conv", False), ("conv", False)] + [
        ("full_attention", True), ("conv", True), ("conv", True),
        ("conv", True)] * 2
    assert cfg["model_type"] == "lfm2_moe"
    assert cfg["conv_bias"] is False and cfg["norm_topk_prob"] is True
    assert cfg["use_expert_bias"] is True
    assert cfg["tie_word_embeddings"] is True
    assert cfg["deployment"] and cfg["precision"]["control"] == "fp8"
    assert cfg["precision"]["compute_dtype"] == "bfloat16"
    for item in ("scoring", "expert_bias", "tie_word_embeddings", "head_dim",
                 "qk_norm", "rotary", "short_conv", "projections", "norms",
                 "init", "optimizer", "recompute", "routing_precision",
                 "modelling_code"):
        assert cfg["assumed"][item], item


def test_the_cell_is_the_traffic_the_issue_gives(cell):
    assert (cell["batch"], cell["pool_batches"], cell["chips"]) == (2, 3, 1)
    assert cell["lengths"] == {"min": 3072, "max": 4096}
    assert cell["trace"]["after_s"] == 3.0 and cell["trace"]["steps"] == 8
    assert set(cell["limits"]) == {"grad1", "grad1_med", "delta3",
                                   "delta3_med"}
    # each limit with its reason beside it
    assert set(cell["limits"]) < set(cell["limits_why"])
    assert flops.row_lengths(cell) == [3072, 4096]
    manifest = _load("BENCHMARK.json")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "seq4096-bs2-train", 1)
    assert manifest["workloads"][-1] == entry
    assert manifest["configs"][-1]["name"] == CONFIG
    # letter for letter the traffic of the three other language models'
    for other in ("granite-4.0-h-micro-seq4096-bs2-train",
                  "olmo-hybrid-7b-seq4096-bs2-train",
                  "phi-4-mini-flash-reasoning-seq4096-bs2-train"):
        theirs = _load("chipbench", "workloads", other + ".json")
        for key in ("driver", "traffic", "chips", "parallelism", "batch",
                    "lengths", "pool_batches", "trace"):
            assert cell[key] == theirs[key], (other, key)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_reference_imports_nothing_of_the_program():
    source = open(os.path.join(ROOT, "chipbench", "reference",
                               "lfm2_moe.py")).read()
    assert "paddle_tpu" not in source
    assert "from chipbench.reference import common" in source
    # every held expert over every token, no sort and no grouped product
    assert "lax.scan(one" in source
    for word in ("argsort", "ragged", "jnp.sort", "pallas"):
        assert word not in source, word


STEP_FLOPS = 16_360_780_333_056


def test_the_counts_are_pinned_at_the_cell_size(cfg, cell):
    per = flops.per_token_flops(cfg)
    assert per == {"conv": 33_554_432, "full_attention": 20_971_520,
                   "dense": 88_080_384,
                   "experts": 131_072 + 22_020_096 * 4 * 8 / 32,
                   "head": 67_108_864}
    assert flops.keys_seen(4096) == 8_390_656
    assert flops.train_step_flops(cfg, cell) == STEP_FLOPS
    assert flops.grouped_matmul_cost(cfg, 1024 * 8) == (
        8192 * 22_020_096, 2 * (8192 * 9472 + 8 * 11_010_048))
    # the parameters the cut holds, by layer
    count = ref.parameter_count(cfg)
    assert count == cfg["parameters"] == 982_084_096
    shapes = ref._shapes(cfg)
    per_layer = [sum(int(np.prod(shape)) for name, (shape, _)
                     in shapes.items() if name.startswith("l%d." % i))
                 for i in range(10)]
    dense, conv, attention = 60_827_648, 104_933_408 - 32, 98_635_936 - 32
    assert per_layer == [dense, dense] + [attention, conv, conv, conv] * 2
    assert count == sum(per_layer) + 8 * 32 + 16_384 * 2048 + 2048
    # the whole model from the same equations: the card's 8.3 B
    whole = dict(cfg, num_hidden_layers=24, num_experts=32,
                 vocab_size=65_536)
    assert ref.parameter_count(whole) == cfg["parameters_published"] \
        == 8_339_930_560


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


def test_the_count_agrees_with_xla_on_the_reference_forward(monkeypatch,
                                                            tiny):
    """XLA counts a loop's body once, so the reference's loops are opened
    for the count (one row, one block of queries), and the reference
    applies every held expert to every token where the count takes a
    token's `k * held / total` rows: all experts held and chosen makes
    the two agree."""
    cfg = dict(tiny, hidden_size=128, intermediate_size=256,
               moe_intermediate_size=64, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=512, num_experts=4,
               num_experts_published=4, num_experts_per_tok=4)
    t = 64
    cell = {"batch": 1, "lengths": {"min": t, "max": t}}

    def every_expert(u, w, bias, cfg_, quant=None, first=None):
        weights = ref.routing(u, w["router"], bias, cfg_, quant)
        return sum(weights[..., e:e + 1] * ref._mlp(u, w["w_in"][e],
                                                    w["w_out"][e], quant)
                   for e in range(w["w_in"].shape[0]))

    monkeypatch.setattr(ref, "experts", every_expert)
    monkeypatch.setattr(ref, "_QUERY_BLOCK", t)
    monkeypatch.setattr(
        ref, "_row_by_row", lambda fn, *rows: jax.tree.map(
            lambda x: x[None], fn(*rows)))
    weights, state = ref.init_weights(3, cfg)
    batch = (jnp.zeros((1, t), jnp.int32), jnp.zeros((1, t), jnp.int32),
             jnp.full((1,), t, jnp.int32))
    xla = _xla_flops(lambda w: ref.loss(w, state, batch, cfg)[0], weights)
    mine = flops.forward_flops(cfg, cell)
    # XLA counts the whole square of scores where the count takes what the
    # mask leaves, and norms, gates, taps, the turn and the cost besides
    square = 2 * 4 * 4 * 32 * (t * t - flops.keys_seen(t))
    assert mine <= xla - square <= 1.1 * mine, (mine, xla, square)


# -- the layers against the reference's ------------------------------------

SMALL = {"hidden_size": 32, "moe_intermediate_size": 24,
         "num_experts_published": 8, "num_experts_per_tok": 2,
         "norm_topk_prob": True, "routed_scaling_factor": 1.0,
         "use_expert_bias": True, "conv_L_cache": 3, "norm_eps": 1e-5,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "rope_theta": 1000000}


def _seq(seed, t=12, lengths=(12, 9), width=32):
    from paddle_tpu.core.sequence import SequenceBatch

    rng = np.random.default_rng(seed)
    data = jnp.asarray(rng.standard_normal((len(lengths), t, width)),
                       jnp.float32)
    return SequenceBatch(data, jnp.asarray(lengths, jnp.int32))


def _moe_weights(seed, held, bias=None, router=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"router": jax.random.normal(k[0], (32, 8)) * 0.5
            if router is None else router,
            "w_in": jax.random.normal(k[1], (held, 32, 48)) * 0.3,
            "w_out": jax.random.normal(k[2], (held, 24, 32)) * 0.3,
            "expert_bias": jnp.zeros((8,)) if bias is None else bias}


def _moe_layer(x, w, first, with_counts=False):
    """(the value and the weights' gradients of sum(out * probe)) of
    `layer.moe` over x with the weights `w`, held from `first`."""
    from paddle_tpu import data_type
    from paddle_tpu import layer as L
    from paddle_tpu.topology import Topology

    L.reset_name_counters()
    node = L.moe(
        input=L.data(name="x", type=data_type.dense_vector_sequence(32)),
        experts_total=8, experts_held=w["w_in"].shape[0], first_held=first,
        top_k=2, width=24, name="moe")
    topo = Topology(node)
    assert {n: s.shape for n, s in topo.param_specs().items()} == {
        "moe." + k: v.shape for k, v in w.items()}
    assert [n for n, s in topo.param_specs().items()
            if s.attr.is_static] == ["moe.expert_bias"]
    probe = jnp.asarray(np.random.default_rng(1).standard_normal(
        x.data.shape), jnp.float32)
    counts = {}

    def value(p, into=None):
        out = topo.apply({"moe." + k: v for k, v in p.items()}, {"x": x},
                         mode="train", counts=into)[0]["moe"].data
        return jnp.sum(out * probe), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.value_and_grad(value, has_aux=True)(w)
        value(w, counts)
    return (out, grads, counts) if with_counts else (out, grads)


def _moe_reference(x, w, first, held=None):
    cfg = dict(SMALL, num_experts=w["w_in"].shape[0], first_expert=first)
    probe = jnp.asarray(np.random.default_rng(1).standard_normal(
        x.data.shape), jnp.float32)

    def value(p):
        out = ref.experts(x.data, p, w["expert_bias"], cfg)
        return jnp.sum(out * probe), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.value_and_grad(value, has_aux=True)(
            {k: v for k, v in w.items() if k != "expert_bias"})
    return out, grads


def _valid(x):
    return (np.arange(x.data.shape[1])[None, :]
            < np.asarray(x.lengths)[:, None])


@pytest.mark.parametrize("held,first", [(8, 0), (2, 0), (2, 4), (3, 5)])
def test_the_expert_layer_is_the_references(held, first):
    """Value and every weight's gradient over the valid positions (a
    padded position routes nowhere in the program and is no token of the
    reference's loss), all experts held and a share of them."""
    x = _seq(3)
    w = _moe_weights(4, held,
                     bias=0.3 * jax.random.normal(jax.random.PRNGKey(9),
                                                  (8,)))
    out, grads, counts = _moe_layer(x, w, first, with_counts=True)
    valid = _valid(x)
    masked = type(x)(jnp.where(valid[..., None], x.data, 0.0), x.lengths)
    want, want_grads = _moe_reference(masked, w, first)
    np.testing.assert_allclose(out[valid], want[valid], atol=2e-6)
    assert float(jnp.abs(out[~valid]).max()) == 0.0
    # the reference over zeroed padding: a zero row adds nothing to a
    # matrix's gradient
    for leaf in ("router", "w_in", "w_out"):
        np.testing.assert_allclose(grads[leaf], want_grads[leaf], atol=3e-5,
                                   err_msg=leaf)
    assert float(jnp.abs(grads["expert_bias"]).max()) == 0.0
    # rows here: the valid tokens' pairs whose expert is held
    chosen = np.asarray(jax.lax.top_k(jax.nn.sigmoid(
        x.data @ w["router"]) + w["expert_bias"], 2)[1])
    here = (chosen >= first) & (chosen < first + held) & valid[..., None]
    assert int(counts["paddle_tpu_moe_rows_here"]) == here.sum()
    assert int(counts["paddle_tpu_moe_expert_load_max"]) == max(
        (here & (chosen == e)).sum() for e in range(first, first + held))


def test_every_pair_on_held_experts_fills_the_buffer_and_none_is_dropped():
    """A selection bias that sends every token's two choices to the two
    held experts: the sorted buffer is full, top_k rows a position, and
    the layer is still the reference's."""
    x = _seq(5, lengths=(12, 12))
    bias = jnp.zeros((8,)).at[jnp.asarray([4, 5])].set(10.0)
    w = _moe_weights(6, 2, bias=bias)
    out, grads, counts = _moe_layer(x, w, 4, with_counts=True)
    assert int(counts["paddle_tpu_moe_rows_here"]) == 2 * 2 * 12
    assert int(counts["paddle_tpu_moe_expert_load_max"]) == 2 * 12
    want, want_grads = _moe_reference(x, w, 4)
    np.testing.assert_allclose(out, want, atol=2e-6)
    for leaf in ("router", "w_in", "w_out"):
        np.testing.assert_allclose(grads[leaf], want_grads[leaf], atol=3e-5,
                                   err_msg=leaf)
    # all on one expert: the other's group is empty
    lone = jnp.zeros((8,)).at[4].set(10.0).at[0].set(5.0)
    out, grads, counts = _moe_layer(x, dict(w, expert_bias=lone), 4,
                                    with_counts=True)
    assert int(counts["paddle_tpu_moe_expert_load_max"]) == 24 \
        == int(counts["paddle_tpu_moe_rows_here"])
    want, _ = _moe_reference(x, dict(w, expert_bias=lone), 4)
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert float(jnp.abs(grads["w_in"][1]).max()) == 0.0


def test_no_pair_on_a_held_expert_gives_zero_and_no_nan():
    x = _seq(7)
    bias = jnp.zeros((8,)).at[jnp.asarray([0, 1])].set(10.0)
    w = _moe_weights(8, 2, bias=bias)
    out, grads, counts = _moe_layer(x, w, 6, with_counts=True)
    assert int(counts["paddle_tpu_moe_rows_here"]) == 0
    assert float(jnp.abs(out).max()) == 0.0
    for leaf, g in grads.items():
        assert np.all(np.isfinite(np.asarray(g))), leaf
        assert float(jnp.abs(g).max()) == 0.0, leaf


def test_the_bias_changes_the_choice_and_not_the_weight():
    from paddle_tpu.ops import moe as moe_ops

    x = jnp.asarray(np.random.default_rng(2).standard_normal((40, 32)),
                    jnp.float32)
    router = _moe_weights(1, 8)["router"]
    plain, plain_w = moe_ops.route(x, router, jnp.zeros((8,)), 2)
    bias = jnp.zeros((8,)).at[3].set(10.0)
    chosen, weights = moe_ops.route(x, router, bias, 2)
    assert bool(jnp.all(jnp.any(chosen == 3, axis=-1)))
    assert not bool(jnp.all(jnp.any(plain == 3, axis=-1)))
    # the weights are the chosen experts' own scores over their sum
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(weights.max()) < 1.0
    np.testing.assert_allclose(plain_w.sum(-1), 1.0, atol=1e-4)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Guide section 4: at 8 experts the outputs of the four shares of
    two add up to the uncut reference layer's output, and their experts'
    gradients are the uncut layer's slices; no shared expert, so nothing
    is counted twice. The router's gradients add up too."""
    x = _seq(11, lengths=(12, 12))
    whole = _moe_weights(12, 8, bias=0.2 * jax.random.normal(
        jax.random.PRNGKey(3), (8,)))
    want, want_grads = _moe_reference(x, whole, 0)
    total, router = 0.0, 0.0
    for first in (0, 2, 4, 6):
        share = dict(whole, w_in=whole["w_in"][first:first + 2],
                     w_out=whole["w_out"][first:first + 2])
        out, grads = _moe_layer(x, share, first)
        total, router = total + out, router + grads["router"]
        for leaf in ("w_in", "w_out"):
            np.testing.assert_allclose(
                grads[leaf], want_grads[leaf][first:first + 2], atol=3e-5,
                err_msg="%s of the share from %d" % (leaf, first))
    np.testing.assert_allclose(total, want, atol=3e-6)
    np.testing.assert_allclose(router, want_grads["router"], atol=3e-5)


def _mixer(kind, x, w):
    from paddle_tpu import data_type
    from paddle_tpu import layer as L
    from paddle_tpu.topology import Topology

    L.reset_name_counters()
    data = L.data(name="x", type=data_type.dense_vector_sequence(32))
    node = L.short_conv(input=data, conv_width=3, name="mix") \
        if kind == "conv" else L.gqa_attention(
            input=data, heads=4, kv_heads=2, head_dim=8, qk_norm="head",
            rope_theta=1000000, block=8, name="mix")
    topo = Topology(node)
    assert {n: s.shape for n, s in topo.param_specs().items()} == {
        "mix." + k: v.shape for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        return topo.apply({"mix." + k: v for k, v in w.items()}, {"x": x},
                          mode="test")[0]["mix"].data


def test_the_short_convolution_is_the_references():
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    w = {"in_proj": jax.random.normal(k[0], (32, 96)) * 0.3,
         "conv_w": jax.random.uniform(k[1], (32, 3), minval=-0.6,
                                      maxval=0.6),
         "out_proj": jax.random.normal(k[2], (32, 32)) * 0.3}
    x = _seq(13, t=14, lengths=(14, 9))
    out = _mixer("conv", x, w)
    with jax.default_matmul_precision("highest"):
        want = ref.short_conv(x.data, w, SMALL)
    valid = _valid(x)
    np.testing.assert_allclose(out[valid], want[valid], atol=2e-6)
    assert float(jnp.abs(out[~valid]).max()) == 0.0
    # three taps back and no further; nothing of a later token
    moved = type(x)(x.data.at[:, 6].add(1.0), x.lengths)
    changed = np.abs(np.asarray(_mixer("conv", moved, w) - out)).max(
        axis=(0, 2)) > 1e-7
    assert changed.tolist() == [False] * 6 + [True] * 3 + [False] * 5


@pytest.mark.parametrize("t,lengths", [(12, (12, 9)), (21, (21, 16))])
def test_rotary_attention_with_per_head_norms_is_the_references(t, lengths):
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    w = {"q": jax.random.normal(k[0], (32, 32)) * 0.3,
         "k": jax.random.normal(k[1], (32, 16)) * 0.3,
         "v": jax.random.normal(k[2], (32, 16)) * 0.3,
         "o": jax.random.normal(k[3], (32, 32)) * 0.3,
         "q_norm": 1.0 + 0.2 * jax.random.normal(k[4], (8,)),
         "k_norm": 1.0 + 0.2 * jax.random.normal(k[5], (8,))}
    x = _seq(17, t=t, lengths=lengths)
    out = _mixer("full_attention", x, w)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x.data, w, SMALL)
    valid = _valid(x)
    np.testing.assert_allclose(out[valid], want[valid], atol=3e-6)
    # positions count: the same tokens one place later give other outputs
    from paddle_tpu.ops import attention as attention_ops

    q = jnp.asarray(np.random.default_rng(0).standard_normal((1, 5, 2, 8)),
                    jnp.float32)
    turned = attention_ops.rotary(q, 1e6)
    np.testing.assert_allclose(turned[:, 0], q[:, 0], atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(turned, axis=-1),
                               jnp.linalg.norm(q, axis=-1), rtol=1e-6)
    np.testing.assert_allclose(turned, ref.rotary(q, 1e6), atol=1e-6)
    # value i turns with value i + 4 by position * theta^(-i / 4)
    angle = 3 * 1e6 ** (-1 / 4)
    np.testing.assert_allclose(
        turned[0, 3, 0, 1], q[0, 3, 0, 1] * np.cos(angle)
        - q[0, 3, 0, 5] * np.sin(angle), rtol=1e-5)


# -- the whole model ---------------------------------------------------------

def _program(cfg, seed):
    """(topology, cost node, {program name: reference value}, names, the
    names of the static ones)."""
    from paddle_tpu import layer as L
    from paddle_tpu.topology import Topology

    L.reset_name_counters()
    cost = bench_model.build(cfg)
    names = bench_model.program_names(cfg)
    weights, state = ref.init_weights(seed, cfg)
    topo = Topology(cost)
    return topo, cost, {names[k]: v for k, v in {**weights, **state}.items()}, \
        names, sorted(names[k] for k in state)


def test_every_reference_leaf_has_its_place_in_the_program(tiny, cfg):
    topo, _, params, names, static = _program(tiny, 3)
    specs = topo.param_specs()
    assert set(specs) == set(names.values()) == set(params)
    for name, value in params.items():
        assert specs[name].shape == value.shape, name
    assert sorted(n for n, s in specs.items() if s.attr.is_static) == static \
        == ["lm.l%d.moe.expert_bias" % i for i in range(2, 6)]
    blocks = [n for n in topo.nodes if n.layer_type == "recompute"]
    assert [n.name for n in blocks] == ["lm.l%d.block" % i for i in range(6)]
    assert [len(n.inputs) for n in blocks] == [1] * 6
    kinds = {n.name: n.layer_type for b in blocks for n in [b]}
    assert len(kinds) == 6
    # at the real widths too, from the shapes alone
    real = bench_model.program_names(cfg)
    weights = set(ref._shapes(cfg))
    assert set(real) == weights | {"l%d.expert_bias" % i
                                   for i in range(2, 10)}
    assert len(set(real.values())) == len(real)
    assert real["l2.q_norm"] == "lm.l2.mixer.q_norm"
    assert real["l0.mlp_in"] == "lm.l0.mlp.w0" and "l2.mlp_in" not in real
    assert real["l3.w_in"] == "lm.l3.moe.w_in" and "l1.router" not in real
    assert ref._shapes(cfg)["l9.w_in"][0] == (8, 2048, 3584)
    assert ref._shapes(cfg)["l9.router"][0] == (2048, 32)


def test_the_tiny_program_follows_the_reference_leaf_by_leaf(tiny,
                                                             tiny_cell):
    """The loss and the first gradient, in float32 at `highest`, by the
    difference's norm over the reference's or the median leaf's, whichever
    is larger, as `check.leaf_gaps` takes it: every leaf within 1e-4."""
    from paddle_tpu.topology import convert_feed

    pool = traffic.make_pool(tiny["inputs"], tiny_cell, 5)
    assert sorted(len(row[0]) for row in pool[0]) == [40, 56]
    with jax.default_matmul_precision("highest"):
        topo, cost, params, names, static = _program(tiny, 5)
        fixed = {n: params.pop(n) for n in static}
        loss, grads = jax.value_and_grad(lambda p: jnp.mean(topo.apply(
            {**p, **fixed}, convert_feed(topo, pool[0]),
            mode="train")[0][cost.name]))(params)
        weights, state = ref.init_weights(5, tiny)
        batch = tuple(jnp.asarray(a) for a in ref.batch_arrays(pool[0], tiny))
        want_loss, want = jax.value_and_grad(
            lambda w: ref.loss(w, state, batch, tiny)[0])(weights)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    norms = sorted(float(np.linalg.norm(v)) for v in want.values())
    median = norms[len(norms) // 2]
    for leaf in weights:
        scale = max(float(np.linalg.norm(want[leaf])), median)
        assert np.linalg.norm(np.asarray(grads[names[leaf]]) - want[leaf]) \
            < 1e-4 * scale, leaf
    # no leaf that is compared has a zero gradient
    assert min(norms) > 1e-6 * median


def test_three_steps_through_sgd_train_follow_the_reference(tiny, tiny_cell):
    """The driver's own first three steps at the tiny size: `SGD.train`
    with reader, `convert_feed` and the feeder, one call of one batch and
    one of two, against three plain Momentum steps of the reference (the
    tolerances are the phi test's, for the same reasons). The selection
    biases are static: no step moves them, and the step's two counters
    come back with the cost, one observation a step."""
    import paddle_tpu as paddle
    from paddle_tpu import layer as L
    from paddle_tpu.observe import metrics as observe_metrics

    lr, mu = 0.01, 0.9
    pool = traffic.make_pool(tiny["inputs"], tiny_cell, 5)
    registry = observe_metrics.get_registry()

    def observed():
        held = registry.snapshot()["histograms"]
        return {name: held.get(name, {"count": 0, "sum": 0.0})
                for name in ("paddle_tpu_moe_rows_here",
                             "paddle_tpu_moe_expert_load_max")}

    paddle.init(use_tpu=False, seed=5, compute_dtype="float32",
                matmul_precision="highest")
    try:
        L.reset_name_counters()
        cost = bench_model.build(tiny)
        names = bench_model.program_names(tiny)
        weights, state = ref.init_weights(5, tiny)
        biases = {k: v + 0.01 * (i + 1) for i, (k, v)
                  in enumerate(state.items())}
        want = common.train3(
            _with_state(ref, biases), tiny, 5,
            [ref.batch_arrays(b, tiny) for b in pool], lr, mu)
        start = {k: np.asarray(v) for k, v in {**weights, **biases}.items()}
        params = paddle.parameters.create(cost)
        params.update_from({names[k]: v for k, v in start.items()})
        trainer = paddle.trainer.SGD(
            cost, params, paddle.optimizer.Momentum(learning_rate=lr,
                                                    momentum=mu))
        assert sorted(trainer._static) == sorted(names[k] for k in state)
        losses = []

        def collect(event):
            if isinstance(event, paddle.event.EndIteration):
                losses.append(event.cost)

        def read():
            return {k: np.array(trainer.parameters.get(n), copy=True)
                    for k, n in names.items()}

        before = observed()
        trainer.train(lambda: iter(pool[:1]), event_handler=collect,
                      feed_pipeline=True)
        after1 = read()
        trainer.train(lambda: iter(pool[1:3]), event_handler=collect,
                      feed_pipeline=True)
        after3 = read()
        after = observed()
    finally:
        paddle.init(use_tpu=False)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    for leaf in state:
        np.testing.assert_array_equal(after3[leaf], start[leaf])
    norms = sorted(float(np.linalg.norm(v)) for v in want["grad1"].values())
    median = norms[len(norms) // 2]
    for leaf in weights:
        first = (start[leaf] - after1[leaf]) / lr
        assert np.linalg.norm(first - want["grad1"][leaf]) \
            <= 1e-4 * max(np.linalg.norm(want["grad1"][leaf]), median) \
            + 2e-7 / lr * np.linalg.norm(start[leaf]), leaf
        moved = after3[leaf] - start[leaf]
        assert np.linalg.norm(moved - want["delta3"][leaf]) \
            <= 2e-3 * np.linalg.norm(want["delta3"][leaf]) \
            + 4e-7 * np.linalg.norm(start[leaf]), leaf
    program = {"losses": losses,
               "grad1": {k: (start[k] - after1[k]) / lr for k in weights},
               "delta3": {k: after3[k] - start[k] for k in weights},
               "state3": {k: after3[k] - start[k] for k in state}}
    numbers = check.readings(program, want)
    assert numbers["grad1"] < 5e-3 and numbers["delta3"] < 1e-3
    assert numbers["state3"] == 0.0
    # three steps, three observations each; of 96 tokens' 2 choices over
    # 4 expert layers about a half falls on the 4 held of 8
    for name in before:
        assert after[name]["count"] - before[name]["count"] == 3, name
    rows = (after["paddle_tpu_moe_rows_here"]["sum"]
            - before["paddle_tpu_moe_rows_here"]["sum"]) / 3
    assert 0.25 * 768 < rows < 0.75 * 768


def _with_state(module, state):
    """The reference with another starting state (selection biases that
    are not zero), for `common.train3`."""
    import types

    return types.SimpleNamespace(
        init_weights=lambda seed, cfg: (module.init_weights(seed, cfg)[0],
                                        dict(state)),
        loss=module.loss)


def test_packed_rows_are_refused(tiny, tiny_cell):
    from paddle_tpu.core.sequence import PackedSequenceBatch
    from paddle_tpu.topology import convert_feed
    from paddle_tpu.utils.error import EnforceError

    topo, cost, params, _, _ = _program(tiny, 3)
    feed = convert_feed(topo, traffic.make_pool(tiny["inputs"], tiny_cell,
                                                3)[0])
    packed = {k: PackedSequenceBatch(
        v.data, v.lengths, jnp.zeros(v.data.shape[:2], jnp.int32))
        for k, v in feed.items()}
    with pytest.raises(EnforceError, match="packed"):
        topo.apply(params, packed, mode="train")


def _gauges():
    from paddle_tpu.observe import metrics as observe_metrics

    return observe_metrics.get_registry().snapshot()["gauges"]


def test_the_gauges_read_their_values_at_the_cells_shapes(cfg, cell):
    """The step traced at the cell's own shapes on abstract values, under
    the configuration's bfloat16 (nothing is computed): eight expert
    layers of 8 held of 32, sorted buffers of 4 rows a position."""
    import paddle_tpu as paddle
    from paddle_tpu import layer as L
    from paddle_tpu.layer import decoder
    from paddle_tpu.topology import Topology, convert_feed

    paddle.init(use_tpu=False, seed=1, compute_dtype="bfloat16")
    try:
        L.reset_name_counters()
        cost = bench_model.build(cfg)
        topo = Topology(cost)
        feed = convert_feed(topo, traffic.make_pool(cfg["inputs"], cell,
                                                    1)[0])
        params = {name: jax.ShapeDtypeStruct(spec.shape, jnp.float32)
                  for name, spec in topo.param_specs().items()}
        counts = {}
        out = jax.eval_shape(lambda p: topo.apply(
            p, feed, mode="train", counts=counts)[0][cost.name], params)
        assert out.shape == (2,)
    finally:
        paddle.init(use_tpu=False)
    gauges = _gauges()
    assert gauges["paddle_tpu_moe_experts_held"] == 8
    assert gauges["paddle_tpu_moe_experts_total"] == 32
    assert gauges["paddle_tpu_moe_rows_bound"] == 4 * 2 * 4096 * 8
    assert sorted(counts) == ["paddle_tpu_moe_expert_load_max",
                              "paddle_tpu_moe_rows_here"]
    positions, kept = 2 * 4096, bench_model.KEEP_LAYERS
    sparse = min(kept, 8)
    assert gauges["paddle_tpu_recompute_kept_bytes"] == 2 * (
        kept * positions * 2048
        + sparse * 4 * positions * 2 * 1792
        + (kept - sparse) * positions * 2 * 7168)
    assert decoder.MOE_PRODUCT == "paddle_tpu.moe.product"


def test_the_new_scopes_are_in_the_compiled_step(tiny, tiny_cell):
    from paddle_tpu.topology import convert_feed

    topo, cost, params, _, _ = _program(tiny, 3)
    feed = convert_feed(topo, traffic.make_pool(tiny["inputs"], tiny_cell,
                                                3)[0])
    text = jax.jit(jax.grad(lambda p: jnp.mean(topo.apply(
        p, feed, mode="train")[0][cost.name]))).lower(params).compile(
            ).as_text()
    for scope in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
                  "short_conv", "rope", "qk_norm", "causal_conv1d",
                  "gqa_attention", "gated_mlp", "rmsnorm", "block"):
        assert "paddle_tpu." + scope in text, scope


def test_a_rehearsal_of_the_cell_is_correct(tiny_cell):
    import contextlib
    import io

    from chipbench import run as run_mod

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run_mod.main(["--workload", CELL, "--seed", str(2 ** 31 + 17),
                             "--seconds", "3", "--trace", "0",
                             "--rehearse", TINY]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line) == ["rehearsal", "correct", "attempted", "failed",
                          "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(tiny_cell["limits"])


def _ctx(cfg, cell, rows, busiest, tokens):
    def held(total, count):
        return {"count": count, "sum": float(total)}

    return {"cfg": cfg, "cell": cell,
            "registry_open": {
                "paddle_tpu_moe_rows_here": held(5 * rows, 5),
                "paddle_tpu_moe_expert_load_max": held(5 * busiest, 5),
                "paddle_tpu_train_step_tokens": held(5 * tokens, 5)},
            "registry_close": {
                "paddle_tpu_moe_rows_here": held(15 * rows, 15),
                "paddle_tpu_moe_expert_load_max": held(15 * busiest, 15),
                "paddle_tpu_train_step_tokens": held(15 * tokens, 15)}}


def test_the_readers_read_the_windows_observations(cfg, cell):
    """At uniform routing a quarter of the valid tokens' pairs falls on
    the 8 experts held of 32, and the busiest expert has the mean's
    rows."""
    uniform = 7168 * 4 * 8 * 8 // 32
    ctx = _ctx(cfg, cell, uniform, 7168 * 4 // 32, 7168)
    assert moe_rows_here_pct.read(ctx) == pytest.approx(25.0)
    assert moe_expert_load_max_over_mean.read(ctx) == pytest.approx(1.0)
    ctx = _ctx(cfg, cell, 1.2 * uniform, 2 * 7168 * 4 // 32, 7168)
    assert moe_rows_here_pct.read(ctx) == pytest.approx(30.0)
    assert moe_expert_load_max_over_mean.read(ctx) == pytest.approx(2 / 1.2)
    # a program without the histograms (the parent's): nothing, no error
    for name in ("paddle_tpu_moe_rows_here",
                 "paddle_tpu_moe_expert_load_max"):
        del ctx["registry_open"][name], ctx["registry_close"][name]
    assert moe_rows_here_pct.read(ctx) is None
    assert moe_expert_load_max_over_mean.read(ctx) is None
    # a configuration without experts: nothing
    granite = _load("chipbench", "configs", "granite-4.0-h-micro.json")
    assert moe_rows_here_pct.read(dict(
        _ctx(cfg, cell, 1, 1, 1), cfg=granite)) is None


def test_the_wrapper_reads_the_two_through_appended_entries(cfg, cell):
    """`chipbench/moe_counters.py`: the entries a `benchmark` PR appends,
    and the harness finding their readers for this cell's traced line and
    no other cell's."""
    from chipbench import moe_counters, run as run_mod

    accepted = _load("BENCHMARK.json")
    manifest = moe_counters.with_entries(accepted)
    assert manifest["per_layer"][:-2] == accepted["per_layer"]
    assert [m["name"] for m in manifest["per_layer"][-2:]] == [
        "moe_rows_here_pct", "moe_expert_load_max_over_mean"]
    for entry in moe_counters.ENTRIES:
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["workloads"] == [CELL]
        assert entry["layer"] in {m["layer"] for m in accepted["per_layer"]}
    ctx = _ctx(cfg, cell, 7168 * 8, 1000, 7168)
    for other in accepted["workloads"]:
        line = run_mod.read_metrics(
            [m for m in run_mod.metrics_of(manifest, "per_layer",
                                           other["name"])
             if m["name"].startswith("moe_")], ctx)
        assert sorted(line) == (["moe_expert_load_max_over_mean",
                                 "moe_rows_here_pct"]
                                if other["name"] == CELL else [])
    assert line["moe_rows_here_pct"] == {"value": pytest.approx(25.0),
                                         "unit": "%"}


def test_the_routing_agreement_reads_the_pairs_chosen_otherwise(tiny,
                                                                tiny_cell):
    """`chipbench/routing_agreement.py`: the share of pairs whose expert
    the reference did not choose, whatever the order of a token's
    choices; in float32 the program's own `route` calls and the
    reference's choices are the same, padding and all."""
    import paddle_tpu as paddle
    from chipbench import routing_agreement

    program = np.asarray([[[0, 1], [2, 3], [4, 5]]])
    reference = np.asarray([[[1, 0], [2, 5], [6, 7]]])
    valid = np.asarray([[True, True, False]])
    assert routing_agreement.shares(program, reference, valid, 2, 2) == {
        "all": 0.25, "here": 0.5}
    assert routing_agreement.shares(program, reference, valid, 6, 2) == {
        "all": 0.25, "here": 0.0}
    exact = dict(tiny, precision=dict(tiny["precision"],
                                      compute_dtype="float32",
                                      matmul_precision="highest"))
    try:
        out = routing_agreement.read_seed(dict(tiny_cell, name=CELL), exact,
                                          2 ** 31 + 5, rehearsal=True)
    finally:
        paddle.init(use_tpu=False)
    assert sorted(out["layers"]) == ["2", "3", "4", "5"]
    assert out["all"] == 0.0 and out["here"] == 0.0


# The tiny preset in float32 against its own fp8 and half of its batch (fp8
# reads 0.022-0.041, 0.0033-0.0045, 0.016-0.028, 0.0023-0.0032 on three
# seeds; the float32 program under 0.005 and 0.001); the cell's own limits
# come from the chip (PERF.md section 6).
TINY_LIMITS = {"loss1": 0.01, "grad1": 0.015, "grad1_med": 0.002,
               "delta3": 0.012, "delta3_med": 0.0015}


@pytest.mark.parametrize("seed", [1, 2])
def test_control_and_half_batch_fail_a_limit(seed, tiny, tiny_cell):
    cell = dict(tiny_cell, name=CELL, limits=TINY_LIMITS)
    out = control.read_seed(cell, tiny, seed)
    assert set(out) == {"control_fp8", "half_batch"}
    for name, stood in out.items():
        assert stood["correct"] is False, (name, stood["numbers"])
