"""The `phi-4-mini-flash-reasoning` configuration's benchmark files: the
configuration against its manifest entry and the catalog's numbers, the
tiny preset of the program against the plain reference leaf by leaf (the
first gradient, and three Momentum steps through `SGD.train`), the
operation count against XLA's, the gauges at the cell's shapes, the new
scopes in the compiled step, a rehearsal of the cell, and the control at a
tiny size."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, control, traffic
from chipbench.flops import phi4_flash as flops
from chipbench.models import phi4_flash as bench_model
from chipbench.reference import common
from chipbench.reference import phi4_flash as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
CONFIG = "phi-4-mini-flash-reasoning"
CELL = "phi-4-mini-flash-reasoning-seq4096-bs2-train"
# config.json of microsoft/Phi-4-mini-flash-reasoning, the numbers that
# shape it (the catalog's entry)
PUBLISHED = {
    "embd_pdrop": 0, "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144,
    "mb_per_layer": 2, "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "vocab_size": 200064,
}
KINDS = ["mamba1", "sliding_attention", "mamba1", "full_attention", "gmu",
         "cross_attention", "gmu", "cross_attention"]


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
    # layer_types is kept whole, for all 32 published layers; the layers
    # held are those of kept_layers, every kind among them, and each of
    # the two shared values has two readers
    kinds = cfg["layer_types"]
    assert len(kinds) == 32
    assert [kinds.count(k) for k in ("mamba1", "sliding_attention",
                                     "full_attention", "gmu",
                                     "cross_attention")] == [9, 8, 1, 7, 7]
    assert all((k in ("mamba1", "gmu")) == (i % cfg["mb_per_layer"] == 0)
               for i, k in enumerate(kinds))
    assert cfg["kept_layers"] == [0, 1, 16, 17, 18, 19, 20, 21]
    assert [kinds[i] for i in cfg["kept_layers"]] == KINDS
    assert len(cfg["kept_layers"]) == cfg["num_hidden_layers"] == 8
    assert cfg["model_type"] == "phi4flash"
    assert cfg["tie_word_embeddings"] is True
    assert cfg["mlp_bias"] is False and cfg["lm_head_bias"] is False
    assert cfg["hidden_act"] == "silu"
    assert ref.mamba_sizes(cfg) == (5120, 16, 4, 160)
    assert cfg["deployment"] and cfg["precision"]["control"] == "fp8"
    for item in ("layer_types", "mamba", "positions", "gmu",
                 "differential_attention", "cross_attention", "biases",
                 "projections", "window", "head_dim", "norms", "init",
                 "recompute", "scan_precision", "modelling_code"):
        assert cfg["assumed"][item], item


def test_the_cell_is_the_traffic_the_issue_gives(cell):
    assert (cell["batch"], cell["pool_batches"], cell["chips"]) == (2, 3, 1)
    assert cell["lengths"] == {"min": 3072, "max": 4096}
    assert cell["trace"]["after_s"] == 3.0 and cell["trace"]["steps"] == 8
    assert set(cell["limits"]) == {"grad1", "grad1_med", "delta3",
                                   "delta3_med"}
    assert flops.row_lengths(cell) == [3072, 4096]
    manifest = _load("BENCHMARK.json")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "seq4096-bs2-train", 1)
    # letter for letter the traffic of the two other language models' cells
    for other in ("granite-4.0-h-micro-seq4096-bs2-train",
                  "olmo-hybrid-7b-seq4096-bs2-train"):
        theirs = _load("chipbench", "workloads", other + ".json")
        for key in ("driver", "traffic", "chips", "parallelism", "batch",
                    "lengths", "pool_batches", "trace"):
            assert cell[key] == theirs[key], (other, key)
    assert len(manifest["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # no per-layer entry came with the cell
    assert [m["name"] for m in manifest["per_layer"]][-7:] == [
        "feed_read_ms_per_step", "feed_host_ms_per_step",
        "feed_place_ms_per_step", "feed_backpressure_ms_per_step",
        "step_dispatch_ms_per_step", "step_readback_ms_per_step",
        "step_handler_ms_per_step"]


def test_the_reference_imports_nothing_of_the_program():
    source = open(os.path.join(ROOT, "chipbench", "reference",
                               "phi4_flash.py")).read()
    assert "paddle_tpu" not in source
    assert "from chipbench.reference import common" in source
    # the scan is stepped token by token, the scores are whole rows
    assert "lax.scan(token" in source and "associative_scan" not in source
    assert "jax.nn.softmax(jnp.where(seen" in source


def test_the_count_is_pinned_at_the_cell_size(cfg, cell):
    per = flops.per_token_flops(cfg)
    assert per == {"mamba1": 82_739_200 + 157_286_400,
                   "sliding_attention": 39_321_600 + 157_286_400,
                   "full_attention": 39_321_600 + 157_286_400,
                   "cross_attention": 26_214_400 + 157_286_400,
                   "gmu": 52_428_800 + 157_286_400,
                   "head": 128_040_960}
    # of a Mamba layer, the scan as the recurrence needs it
    assert 6 * 5120 * 16 == 491_520
    assert flops.keys_seen(4096) == 8_390_656
    assert flops.keys_seen(4096, 512) == 512 * 513 // 2 + 3584 * 512
    assert flops.keys_seen(300, 512) == 300 * 301 // 2
    assert flops.train_step_flops(cfg, cell) == STEP_FLOPS
    # the parameters the cut holds
    count = sum(int(np.prod(shape)) for shape, _ in ref._shapes(cfg).values())
    assert count == cfg["parameters"] == 893_728_256
    per_layer = [sum(int(np.prod(shape)) for name, (shape, _)
                     in ref._shapes(cfg).items()
                     if name.startswith("l%d." % i)) for i in range(8)]
    assert per_layer == [119_895_040, 98_322_304, 119_895_040, 98_322_304,
                         104_867_840, 91_766_144, 104_867_840, 91_766_144]
    assert count == sum(per_layer) + 25_008 * 2560 + 2 * 2560
    # the whole model: 9 + 8 + 1 + 7 + 7 layers, the table and the final
    # norm are the published 3.8 B
    assert 9 * 119_895_040 + 9 * 98_322_304 + 7 * 104_867_840 \
        + 7 * 91_766_144 + 200_064 * 2560 + 5120 == 3_852_562_944


STEP_FLOPS = 40_413_057_515_520


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


def test_the_count_agrees_with_xla_on_the_reference_forward(monkeypatch,
                                                            tiny):
    """XLA counts a loop's body once, so the reference's loops are opened
    for the count: one row, one block of queries, and the scan's three
    multiply-adds over all tokens at once (the same operations)."""
    cfg = dict(tiny, hidden_size=128, intermediate_size=256,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
               mamba_d_state=16, mamba_dt_rank=8, sliding_window=16,
               layer_types=["mamba1", "sliding_attention", "mamba1",
                            "full_attention", "gmu", "cross_attention"],
               kept_layers=[0, 1, 2, 3, 4, 5], num_hidden_layers=6)
    t = 64
    cell = {"batch": 1, "lengths": {"min": t, "max": t}}

    def all_tokens_at_once(dt, x, a, b_mat, c_mat):
        # decay times a state, the input times B, the read through C
        state = (dt * x)[..., None] * b_mat[:, :, None, :]
        state = jnp.exp(dt[..., None] * a) * state + state
        return jnp.sum(state * c_mat[:, :, None, :], axis=-1)

    monkeypatch.setattr(ref, "_recurrence", all_tokens_at_once)
    monkeypatch.setattr(ref, "_QUERY_BLOCK", t)
    monkeypatch.setattr(
        ref, "_row_by_row", lambda fn, *rows: jax.tree.map(
            lambda x: x[None], fn(*rows)))
    weights, _ = ref.init_weights(3, cfg)
    batch = (jnp.zeros((1, t), jnp.int32), jnp.zeros((1, t), jnp.int32),
             jnp.full((1,), t, jnp.int32))
    xla = _xla_flops(lambda w: ref.loss(w, {}, batch, cfg)[0], weights)
    mine = flops.forward_flops(cfg, cell)
    # XLA counts the whole square of scores where the count takes what the
    # masks leave, and norms, gates, the convolutions and the cost besides
    heads, hd = 4, 128 // 4
    pair = heads * (2 * hd + 2 * 2 * hd)
    square = pair * (3 * t * t - 2 * flops.keys_seen(t)
                     - flops.keys_seen(t, 16))
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
    blocks = [n for n in topo.nodes if n.layer_type == "recompute"]
    assert [n.name for n in blocks] == ["lm.l%d.block" % i for i in range(8)]
    # the two makers hand a second value out, and the four readers take it
    assert [len(n.inputs) for n in blocks] == [1, 1, 1, 1, 2, 2, 2, 2]
    assert [p.name for n in blocks[4:] for p in n.inputs[1:]] == [
        "lm.l2.block.1", "lm.l3.block.1"] * 2
    # at the real widths too, from the shapes alone
    real = bench_model.program_names(cfg)
    assert set(real) == set(ref._shapes(cfg))
    assert len(set(real.values())) == len(real)
    assert real["l3.lambda_q1"] == "lm.l3.mixer.lambda_q1"
    assert "l5.k" not in real and "l4.in_proj" in real


def test_the_tiny_program_follows_the_reference_leaf_by_leaf(tiny):
    """The loss and the first gradient, in float32 at `highest`, by the
    difference's norm over the reference's or the median leaf's, whichever
    is larger, as `check.leaf_gaps` takes it: every leaf within 1e-4 (a
    key bias moves no score relative to another, so its gradient is
    round-off on both sides)."""
    from paddle_tpu.topology import convert_feed

    cell = _load("tests", "chipbench", "tiny", "workloads", CELL + ".json")
    pool = traffic.make_pool(tiny["inputs"], cell, 5)
    assert sorted(len(row[0]) for row in pool[0]) == [40, 56]
    with jax.default_matmul_precision("highest"):
        topo, cost, params, names = _program(tiny, 5)
        loss, grads = jax.value_and_grad(lambda p: jnp.mean(topo.apply(
            p, convert_feed(topo, pool[0]), mode="train")[0][cost.name]))(
                params)
        weights, _ = ref.init_weights(5, tiny)
        batch = tuple(jnp.asarray(a) for a in ref.batch_arrays(pool[0], tiny))
        want_loss, want = jax.value_and_grad(
            lambda w: ref.loss(w, {}, batch, tiny)[0])(weights)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    norms = sorted(float(np.linalg.norm(v)) for v in want.values())
    median = norms[len(norms) // 2]
    for leaf, name in names.items():
        scale = max(float(np.linalg.norm(want[leaf])), median)
        assert np.linalg.norm(np.asarray(grads[name]) - want[leaf]) \
            < 1e-4 * scale, leaf
    dead = [k for k, v in want.items() if np.linalg.norm(v) < 1e-6 * median]
    assert dead == ["l1.k_b", "l3.k_b"]


def test_three_steps_through_sgd_train_follow_the_reference(tiny):
    """The driver's own first three steps at the tiny size: `SGD.train`
    with reader, `convert_feed` and the feeder, one call of one batch and
    one of two, against three plain Momentum steps of the reference. The
    first gradient is read off the parameters, (w0 - w1) / lr, so it
    carries the rounding of a float32 parameter over the rate (6e-8 of
    its magnitude / 0.01); the change after three steps within 2e-3 (at
    these widths three steps move a matrix by a tenth of itself, so the
    first step's rounding grows) and the parameter's own rounding."""
    import paddle_tpu as paddle
    from paddle_tpu import layer as L

    lr, mu = 0.01, 0.9
    cell = _load("tests", "chipbench", "tiny", "workloads", CELL + ".json")
    pool = traffic.make_pool(tiny["inputs"], cell, 5)
    paddle.init(use_tpu=False, seed=5, compute_dtype="float32",
                matmul_precision="highest")
    try:
        want = common.train3(ref, tiny, 5,
                             [ref.batch_arrays(b, tiny) for b in pool],
                             lr, mu)
        L.reset_name_counters()
        cost = bench_model.build(tiny)
        names = bench_model.program_names(tiny)
        weights, _ = ref.init_weights(5, tiny)
        start = {k: np.asarray(v) for k, v in weights.items()}
        params = paddle.parameters.create(cost)
        params.update_from({names[k]: v for k, v in weights.items()})
        trainer = paddle.trainer.SGD(
            cost, params, paddle.optimizer.Momentum(learning_rate=lr,
                                                    momentum=mu))
        losses = []

        def collect(event):
            if isinstance(event, paddle.event.EndIteration):
                losses.append(event.cost)

        def read():
            return {k: np.array(trainer.parameters.get(n), copy=True)
                    for k, n in names.items()}

        trainer.train(lambda: iter(pool[:1]), event_handler=collect,
                      feed_pipeline=True)
        after1 = read()
        trainer.train(lambda: iter(pool[1:3]), event_handler=collect,
                      feed_pipeline=True)
        after3 = read()
    finally:
        paddle.init(use_tpu=False)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    norms = sorted(float(np.linalg.norm(v)) for v in want["grad1"].values())
    median = norms[len(norms) // 2]
    for leaf in names:
        first = (start[leaf] - after1[leaf]) / lr
        assert np.linalg.norm(first - want["grad1"][leaf]) \
            <= 1e-4 * max(np.linalg.norm(want["grad1"][leaf]), median) \
            + 2e-7 / lr * np.linalg.norm(start[leaf]), leaf
    # a key bias moves by round-off alone: left out
    moving = [k for k, v in want["grad1"].items()
              if np.linalg.norm(v) >= 1e-6 * median]
    assert sorted(set(names) - set(moving)) == ["l1.k_b", "l3.k_b"]
    for leaf in moving:
        moved = after3[leaf] - start[leaf]
        assert np.linalg.norm(moved - want["delta3"][leaf]) \
            <= 2e-3 * np.linalg.norm(want["delta3"][leaf]) \
            + 4e-7 * np.linalg.norm(start[leaf]), leaf
    program = {"losses": losses,
               "grad1": {k: (start[k] - after1[k]) / lr for k in names},
               "delta3": {k: after3[k] - start[k] for k in names},
               "state3": {}}
    # by the harness's own measure; its first gradient carries the
    # parameters' rounding over the rate (0.0024 is read, on a norm's bias)
    numbers = check.readings(program, want)
    assert numbers["grad1"] < 5e-3 and numbers["delta3"] < 1e-3


def _gauges():
    from paddle_tpu.observe import metrics as observe_metrics

    return observe_metrics.get_registry().snapshot()["gauges"]


def test_the_gauges_read_their_values_at_the_cells_shapes(cfg, cell):
    """The step traced at the cell's own shapes on abstract values, under
    the configuration's bfloat16 (nothing is computed): what lives across
    blocks is the memory [2, 4096, 5120] and the keys and values
    [2, 4096, 2 * 1280], bfloat16; of the 4 x 36 key blocks at or under
    the diagonal the window layer visits 15."""
    import paddle_tpu as paddle
    from paddle_tpu import layer as L
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
        jax.eval_shape(jax.grad(lambda p: jnp.mean(topo.apply(
            p, feed, mode="train")[0][cost.name])), params)
    finally:
        paddle.init(use_tpu=False)
    gauges = _gauges()
    assert gauges["paddle_tpu_shared_across_blocks_bytes"] == 125_829_120 \
        == 2 * 4096 * (5120 + 2560) * 2
    assert gauges["paddle_tpu_attention_key_blocks_visited"] == 15 + 3 * 36
    assert gauges["paddle_tpu_attention_key_blocks_possible"] == 4 * 36
    positions = 2 * 4096
    kept = bench_model.KEEP_LAYERS
    mamba_kept = sum(k == "mamba1" for k in KINDS[8 - kept:])
    assert gauges["paddle_tpu_recompute_kept_bytes"] == positions * 2 * (
        kept * (2 * 10240 + 2560) + mamba_kept * 2 * 5120)


def test_the_new_scopes_are_in_the_compiled_step(tiny):
    from paddle_tpu.topology import convert_feed

    cell = _load("tests", "chipbench", "tiny", "workloads", CELL + ".json")
    topo, cost, params, _ = _program(tiny, 3)
    feed = convert_feed(topo, traffic.make_pool(tiny["inputs"], cell, 3)[0])
    text = jax.jit(jax.grad(lambda p: jnp.mean(topo.apply(
        p, feed, mode="train")[0][cost.name]))).lower(params).compile(
            ).as_text()
    for scope in ("layer_norm", "mamba1", "selective_scan", "gmu",
                  "window_attention", "diff_attention", "cross_attention",
                  "gqa_attention", "gated_mlp", "block"):
        assert "paddle_tpu." + scope in text, scope


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
