"""The `olmo-hybrid-7b` configuration's benchmark files: the configuration
against its manifest entry and the catalog's numbers, the tiny preset of
the program against the plain reference leaf by leaf (the first gradient
and three Momentum steps), the operation count against XLA's, a rehearsal
of the cell, and the control at a tiny size."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, control, traffic
from chipbench.flops import olmo_hybrid as flops
from chipbench.models import olmo_hybrid as bench_model
from chipbench.reference import common
from chipbench.reference import olmo_hybrid as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
CONFIG = "olmo-hybrid-7b"
CELL = "olmo-hybrid-7b-seq4096-bs2-train"
# config.json of allenai/Olmo-Hybrid-7B, the numbers that shape it
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "max_position_embeddings": 65536,
    "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
}


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


def test_the_configuration_holds_the_published_widths(cfg):
    entry = next(c for c in _load("BENCHMARK.json")["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == ["num_hidden_layers", "vocab_size"]
    differing = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
    assert differing == sorted(entry["reduced"])
    assert sorted(cfg["reduced"]) == differing
    for key in differing:
        assert cfg["reduced"][key]["published"] == PUBLISHED[key]
        assert cfg["reduced"][key]["here"] == cfg[key]
        assert cfg["reduced"][key]["how"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # layer_types is kept whole; the layers held are one period of it
    kinds = cfg["layer_types"]
    assert len(kinds) == 32 and kinds.count("full_attention") == 8
    assert kinds[:cfg["num_hidden_layers"]] == [
        "linear_attention"] * 3 + ["full_attention"]
    assert cfg["model_type"] == "olmo_hybrid"
    assert cfg["tie_word_embeddings"] is False
    assert cfg["linear_allow_neg_eigval"] is True
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert cfg["hidden_act"] == "silu" and cfg["attention_bias"] is False
    assert cfg["deployment"] and cfg["precision"]["control"] == "fp8"
    for item in ("norm_placement", "qk_norm", "head_dim", "positions",
                 "mixer_output", "mixer_convolution", "mixer_l2norm",
                 "chunk", "init", "recompute"):
        assert cfg["assumed"][item], item


def test_the_cell_is_the_traffic_the_issue_gives(cell):
    assert (cell["batch"], cell["pool_batches"], cell["chips"]) == (2, 3, 1)
    assert cell["lengths"] == {"min": 3072, "max": 4096}
    assert cell["trace"]["after_s"] == 3.0 and cell["trace"]["steps"] == 8
    assert set(cell["limits"]) == {"grad1", "grad1_med", "delta3",
                                   "delta3_med"}
    assert flops.row_lengths(cell) == [3072, 4096]
    entry = next(w for w in _load("BENCHMARK.json")["workloads"]
                 if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "seq4096-bs2-train", 1)


def test_the_reference_imports_nothing_of_the_program():
    source = open(os.path.join(ROOT, "chipbench", "reference",
                               "olmo_hybrid.py")).read()
    assert "paddle_tpu" not in source
    assert "from chipbench.reference import common" in source
    # the rule is stepped token by token: no triangular system, no chunks
    assert "solve_triangular" not in source and "lax.scan(token" in source


def test_the_count_is_pinned_at_the_cell_size(cfg, cell):
    per = flops.per_token_flops(cfg)
    assert per == {"linear_attention": 434_350_080,
                   "full_attention": 371_589_120, "head": 96_337_920}
    # of a linear layer, the rule as the recurrence needs it
    assert 6 * 96 * 192 * 30 == 3_317_760
    assert flops.train_step_flops(cfg, cell) == 38_687_240_355_840
    # the parameters the cut holds
    count = sum(int(np.prod(shape)) for shape, _ in ref._shapes(cfg).values())
    assert count == 928_862_196
    mixer = sum(int(np.prod(shape)) for name, (shape, _)
                in ref._shapes(cfg).items()
                if name.startswith("l0.") and name[3:] in ref.leaves_of(
                    "linear_attention"))
    assert mixer == 88_750_332


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


def test_the_count_agrees_with_xla_on_the_reference_forward(monkeypatch,
                                                            tiny):
    """XLA counts a loop's body once, so the reference's loops are opened
    for the count: one row, one block of queries, and the rule's three
    products from sums over all tokens at once (the same
    operations)."""
    cfg = dict(tiny, hidden_size=128, intermediate_size=256,
               num_attention_heads=4, num_key_value_heads=4,
               linear_num_key_heads=4, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=32,
               vocab_size=512,
               layer_types=["linear_attention", "full_attention"],
               num_hidden_layers=2)
    t = 64
    cell = {"batch": 1, "lengths": {"min": t, "max": t}}

    def all_tokens_at_once(q, k, v, alpha, beta, quant):
        # k^T S, k (x) that, k (x) v and S^T q, for every token
        state = jnp.einsum("bthk,bthv->bthkv", k, beta[..., None] * v)
        held = jnp.einsum("bthk,bthkv->bthv", k, state)
        state = alpha[..., None, None] * (
            state - jnp.einsum("bthk,bthv->bthkv", k, held))
        return jnp.einsum("bthk,bthkv->bthv", q, state)

    monkeypatch.setattr(ref, "_recurrence", all_tokens_at_once)
    monkeypatch.setattr(ref, "_QUERY_BLOCK", t)
    monkeypatch.setattr(ref, "_row_by_row",
                        lambda fn, *rows: jnp.stack([fn(*rows)]))
    weights, _ = ref.init_weights(3, cfg)
    batch = (jnp.zeros((1, t), jnp.int32), jnp.zeros((1, t), jnp.int32),
             jnp.full((1,), t, jnp.int32))
    xla = _xla_flops(lambda w: ref.loss(w, {}, batch, cfg)[0], weights)
    mine = flops.forward_flops(cfg, cell)
    # XLA counts the whole square of scores where the count takes the causal
    # half, and norms, gates, the convolutions and the cost besides
    heads, hd = 4, 128 // 4
    square = 2 * 2 * heads * hd * t * t - 2 * 2 * heads * hd * t * (t + 1) // 2
    assert mine <= xla - square <= 1.1 * mine, (mine, xla, square)


def _program(cfg, seed):
    """(topology, cost node, {program name: reference weight}, names)."""
    from paddle_tpu import layer as L
    from paddle_tpu.topology import Topology

    L.reset_name_counters()
    cost = bench_model.build(cfg)
    names = bench_model.program_names(cfg)
    weights, _ = ref.init_weights(seed, cfg)
    return Topology(cost), cost, {names[k]: v for k, v in weights.items()}, \
        names


def test_every_reference_leaf_has_its_place_in_the_program(tiny, cfg):
    topo, _, params, names = _program(tiny, 3)
    specs = topo.param_specs()
    assert set(specs) == set(names.values()) == set(params)
    for name, value in params.items():
        assert specs[name].shape == value.shape, name
    # at the real widths too, from the shapes alone
    real = bench_model.program_names(cfg)
    assert set(real) == set(ref._shapes(cfg))
    assert real["head"] != real["emb"]
    assert len(set(real.values())) == len(real)


def test_the_tiny_program_follows_the_reference_leaf_by_leaf(tiny):
    """The first gradient and the change after three Momentum steps, in
    float32 at `highest`, by the difference's norm over the reference's:
    every leaf's gradient within 1e-4 (5e-6 is read); its change within
    2e-3 (2e-4 to 6e-4 is read on every leaf alike: at these widths three
    steps of 0.01 move a matrix by a third of itself and the loss rises,
    so the first step's rounding grows) and the rounding of the parameter
    itself (each step rounds a float32 parameter, on both sides: 4e-7 of
    its magnitude in all)."""
    from paddle_tpu.topology import convert_feed

    lr, mu = 0.01, 0.9
    cell = _load("tests", "chipbench", "tiny", "workloads", CELL + ".json")
    pool = traffic.make_pool(tiny["inputs"], cell, 5)
    assert sorted(len(row[0]) for row in pool[0]) == [40, 56]
    with jax.default_matmul_precision("highest"):
        want = common.train3(ref, tiny, 5,
                             [ref.batch_arrays(b, tiny) for b in pool],
                             lr, mu)
        topo, cost, params, names = _program(tiny, 5)
        start = dict(params)
        velocity = jax.tree.map(jnp.zeros_like, params)
        grad = jax.jit(jax.value_and_grad(lambda p, feed: jnp.mean(
            topo.apply(p, feed, mode="train")[0][cost.name])))
        losses, first = [], None
        for batch in pool:
            loss, g = grad(params, convert_feed(topo, batch))
            losses.append(float(loss))
            first = g if first is None else first
            velocity = jax.tree.map(lambda v, g_: mu * v - lr * g_,
                                    velocity, g)
            params = jax.tree.map(lambda p, v: p + v, params, velocity)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)

    def gap(got, ref_value):
        return np.linalg.norm(np.asarray(got) - ref_value) \
            / np.linalg.norm(ref_value)

    for leaf, name in names.items():
        assert gap(first[name], want["grad1"][leaf]) < 1e-4, leaf
        moved = np.asarray(params[name] - start[name])
        assert np.linalg.norm(moved - want["delta3"][leaf]) \
            <= 2e-3 * np.linalg.norm(want["delta3"][leaf]) \
            + 4e-7 * np.linalg.norm(start[name]), leaf
    program = {"losses": losses,
               "grad1": {k: np.asarray(first[n]) for k, n in names.items()},
               "delta3": {k: np.asarray(params[n] - start[n])
                          for k, n in names.items()},
               "state3": {}}
    numbers = check.readings(program, want)
    assert numbers["grad1"] < 1e-4 and numbers["delta3"] < 1e-3


def test_a_rehearsal_of_the_cell_is_correct():
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
    cell = _load("tests", "chipbench", "tiny", "workloads", CELL + ".json")
    assert set(line["checks"]) == set(cell["limits"])


# The tiny preset in float32 against its own fp8 and half of its batch; the
# cell's own limits come from the chip (PERF.md section 6).
TINY_LIMITS = {"loss1": 0.01, "grad1": 0.03, "grad1_med": 0.01,
               "delta3": 0.03, "delta3_med": 0.01}


@pytest.mark.parametrize("seed", [1, 2])
def test_control_and_half_batch_fail_a_limit(seed, tiny):
    cell = dict(_load("tests", "chipbench", "tiny", "workloads",
                      CELL + ".json"), name=CELL, limits=TINY_LIMITS)
    out = control.read_seed(cell, tiny, seed)
    assert set(out) == {"control_fp8", "half_batch"}
    for name, stood in out.items():
        assert stood["correct"] is False, (name, stood["numbers"])
