"""The `laguna-xs.2` configuration's benchmark files: the configuration
against its manifest entry and the catalog's numbers, YaRN's frequencies
and the partial rotary turn against the `transformers` formula, the
head-wise gate, the expert layer with its shared expert against the plain
reference's (a share of the experts, and eight shares adding up to the
uncut layer with the shared expert counted once), the rows the grouped
products visit against a hand count of tiles, the tiny preset of the
program against the reference leaf by leaf (logits, loss, the first
gradient, and three Momentum steps through `SGD.train`), the operation
and parameter counts, the gauges at the cell's shapes, the scopes in the
compiled step, a rehearsal of the cell, the reader, what `from_config`
refuses, and the control at a tiny size."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, control, traffic
from chipbench.flops import laguna as flops
from chipbench.metrics import moe_rows_visited_pct
from chipbench.models import laguna as bench_model
from chipbench.reference import common
from chipbench.reference import laguna as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
CONFIG = "laguna-xs.2"
CELL = "laguna-xs.2-seq4096-bs4-train"
# config.json of poolside/Laguna-XS.2, the numbers that shape it (the
# catalog's entry)
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
    "num_hidden_layers": 40, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "rms_norm_eps": 1e-06,
    "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "sliding_window": 512, "partial_rotary_factor": 0.5,
    "moe_routed_scaling_factor": 2.5,
}
FULL_ROPE = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
             "original_max_position_embeddings": 4096, "beta_slow": 1,
             "beta_fast": 64, "attention_factor": 1.4158883083359672,
             "partial_rotary_factor": 0.5}
LAYER_TYPES = ["full_attention"] + ["sliding_attention"] * 3
HEADS = {"full_attention": 48, "sliding_attention": 64}


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
    # the chip's share: 32 of the 256 experts the router scores, an eighth
    # of the table and of the head, the first five layers: the dense one
    # and a whole period of one full attention layer to three window ones
    assert (cfg["num_experts"], cfg["num_experts_published"],
            cfg["first_expert"]) == (32, 256, 0)
    assert ref.experts_of(cfg) == (256, 32, 0)
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["layer_types"] == LAYER_TYPES * 10
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert cfg["num_attention_heads_per_layer"] == [
        HEADS[k] for k in cfg["layer_types"]]
    assert cfg["num_dense_layers"] == cfg["mlp_layer_types"].index("sparse")
    assert ref.layers_of(cfg) == [("full_attention", False),
                                  ("sliding_attention", True),
                                  ("sliding_attention", True),
                                  ("sliding_attention", True),
                                  ("full_attention", True)]
    assert cfg["rope_parameters"]["full_attention"] == FULL_ROPE
    assert cfg["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert cfg["model_type"] == "laguna" and cfg["gating"] is True
    assert cfg["tie_word_embeddings"] is False
    assert cfg["attention_bias"] is False
    assert cfg["moe_apply_router_weight_on_input"] is False
    assert cfg["deployment"] and cfg["precision"]["control"] == "fp8"
    assert cfg["precision"]["compute_dtype"] == "bfloat16"
    for item in ("gating", "scoring", "shared_expert", "num_dense_layers",
                 "attention", "rotary", "projections", "norms",
                 "tie_word_embeddings", "init", "optimizer", "recompute",
                 "routing_precision", "modelling_code"):
        assert cfg["assumed"][item], item


def test_the_cell_holds_its_traffic(cell):
    assert (cell["batch"], cell["pool_batches"], cell["chips"]) == (4, 3, 1)
    assert cell["lengths"] == {"min": 3072, "max": 4096}
    assert cell["trace"] == {"after_s": 3.0, "steps": 8}
    assert set(cell["limits"]) == {"grad1", "grad1_med", "delta3",
                                   "delta3_med"}
    # each limit with its reason beside it
    assert set(cell["limits"]) < set(cell["limits_why"])
    assert flops.row_lengths(cell) == [3072, 3413, 3755, 4096]
    manifest = _load("BENCHMARK.json")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "seq4096-bs4-train", 1)
    assert "8x" in entry["why"] and len(entry["why"]) <= 200
    # the other language models' traffic but for the batch
    lfm2 = _load("chipbench", "workloads",
                 "lfm2-8b-a1b-seq4096-bs2-train.json")
    for key in ("driver", "chips", "parallelism", "lengths", "pool_batches",
                "trace"):
        assert cell[key] == lfm2[key], key


def test_the_metric_is_appended_for_both_expert_cells():
    manifest = _load("BENCHMARK.json")
    assert manifest["per_layer"][-1] == {
        "name": "moe_rows_visited_pct", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "step program",
        "moves": "train_samples_per_s",
        "workloads": ["lfm2-8b-a1b-seq4096-bs2-train", CELL]}
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == CONFIG


def test_the_reference_imports_nothing_of_the_program():
    source = open(os.path.join(ROOT, "chipbench", "reference",
                               "laguna.py")).read()
    assert "paddle_tpu" not in source
    assert "from chipbench.reference import common" in source
    # every held expert over every token, no sort and no grouped product
    assert "lax.scan(one" in source
    for word in ("argsort", "ragged", "jnp.sort", "pallas"):
        assert word not in source, word


STEP_FLOPS = 29_569_272_791_040


def test_the_counts_are_pinned_at_the_cell_size(cfg, cell):
    per = flops.per_token_flops(cfg)
    swiglu = 6 * 2048 * 512
    assert per == {
        "full_attention": 2 * 2048 * (48 + 16) * 128 + 2 * 2048 * 48
        + 2 * 48 * 128 * 2048,
        "sliding_attention": 2 * 2048 * (64 + 16) * 128 + 2 * 2048 * 64
        + 2 * 64 * 128 * 2048,
        "dense": 6 * 2048 * 8192,
        "experts": 2 * 2048 * 256 + swiglu * 8 * 32 / 256 + swiglu,
        "head": 2 * 2048 * 12544}
    assert flops.window_keys_seen(4096, 512) == \
        512 * 513 // 2 + (4096 - 512) * 512
    assert flops.window_keys_seen(300, 512) == flops.keys_seen(300)
    assert flops.train_step_flops(cfg, cell) == STEP_FLOPS
    # the parameters the cut holds, by layer: the dense layer, three window
    # expert layers, the full expert layer, the table and the head
    count = ref.parameter_count(cfg)
    assert count == cfg["parameters"] == 691_623_936
    shapes = ref._shapes(cfg)
    per_layer = [sum(int(np.prod(shape)) for name, shape in shapes.items()
                     if name.startswith("l%d." % i)) for i in range(5)]
    assert per_layer == [79_794_176] + [142_217_216] * 3 + [133_795_840]
    assert count == sum(per_layer) + 2 * 12_544 * 2048 + 2048
    # the whole model from the same equations: the card's 33.4 B; without
    # the gate 33,437,681,664, with a gate a value 34.07 B, so the gate is
    # one a head
    whole = dict(cfg, num_hidden_layers=40, num_experts=256,
                 vocab_size=100_352)
    assert ref.parameter_count(whole) == cfg["parameters_published"] \
        == 33_442_596_864
    gates = sum(2048 * HEADS[k] for k in cfg["layer_types"])
    assert ref.parameter_count(whole) - gates == 33_437_681_664
    assert round((ref.parameter_count(whole) + gates * 127) / 1e9, 2) == \
        34.07


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
    the two agree. XLA counts the whole square of scores where the count
    takes what the causal mask and the window leave."""
    cfg = dict(tiny, num_experts=8, num_experts_per_tok=8)
    t = 64
    cell = {"batch": 1, "lengths": {"min": t, "max": t}}

    def every_expert(u, w, cfg_, quant=None, first=None):
        weights = ref.routing(u, w["router"], cfg_, quant)
        return sum(weights[..., e:e + 1] * ref._swiglu(u, w["w_in"][e],
                                                      w["w_out"][e], quant)
                   for e in range(w["w_in"].shape[0]))

    monkeypatch.setattr(ref, "routed", every_expert)
    monkeypatch.setattr(ref, "_QUERY_BLOCK", t)
    monkeypatch.setattr(
        ref, "_row_by_row", lambda fn, *rows: jax.tree.map(
            lambda x: x[None], fn(*rows)))
    weights, state = ref.init_weights(3, cfg)
    batch = (jnp.zeros((1, t), jnp.int32), jnp.zeros((1, t), jnp.int32),
             jnp.full((1,), t, jnp.int32))
    xla = _xla_flops(lambda w: ref.loss(w, state, batch, cfg)[0], weights)
    mine = flops.forward_flops(cfg, cell)
    masked = 0
    for kind, _ in ref.layers_of(cfg):
        seen = flops.keys_seen(t) if kind == "full_attention" else \
            flops.window_keys_seen(t, cfg["sliding_window"])
        masked += ref.heads_of(cfg, kind) * 4 * 16 * (t * t - seen)
    assert mine <= xla - masked <= 1.1 * mine, (mine, xla, masked)


# -- rotary positions -------------------------------------------------------

def _hf_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """`transformers`' `_compute_yarn_parameters`, line for line in
    numpy float32 (truncate true): the table the published model turns
    by."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(base) ** (np.arange(0, dim, 2).astype(np.float32)
                                     / np.float32(dim))
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return (inv_freq_interpolation * (1 - extrapolation_factor)
            + inv_freq_extrapolation * extrapolation_factor), (low, high)


@pytest.mark.parametrize("dim,base,factor,original,fast,slow,edges", [
    (64, 500000, 64, 4096, 64, 1, (5, 16)),       # Laguna-XS.2's full layers
    (64, 500000, 128, 8192, 32, 1, (9, 18)),      # Laguna-S-2.1's
    (8, 500000, 64, 4096, 64, 1, (0, 2)),         # the tiny preset's
])
def test_yarn_frequencies_are_the_published_formulas(dim, base, factor,
                                                     original, fast, slow,
                                                     edges):
    """The program's table and the reference's, each written apart, are
    the formula's to float32 rounding (rtol 1e-6: the power is taken in
    float32 on both sides, by numpy and by XLA): the fast pairs keep
    theta's frequency, the slow ones take it over `factor`."""
    from paddle_tpu.ops import attention as attention_ops

    want, got_edges = _hf_yarn(dim, base, factor, original, fast, slow)
    assert got_edges == edges
    program = attention_ops.yarn_inverse_frequencies(dim, base, factor,
                                                     original, fast, slow)
    reference, scale = ref.yarn(
        {"rope_theta": base, "factor": factor,
         "original_max_position_embeddings": original, "beta_fast": fast,
         "beta_slow": slow, "attention_factor": 0.1 * math.log(factor) + 1},
        dim)
    assert program.dtype == np.float32 and program.shape == (dim // 2,)
    np.testing.assert_allclose(program, want, rtol=1e-6)
    np.testing.assert_allclose(reference, want, rtol=1e-6)
    plain = np.float32(base) ** (-np.arange(0, dim, 2) / dim)
    low, high = edges
    np.testing.assert_allclose(program[:low], plain[:low], rtol=1e-6)
    np.testing.assert_allclose(program[high:], plain[high:] / factor,
                               rtol=1e-6)
    assert scale == pytest.approx(1.4158883083359672 if factor == 64
                                  else 1.4852030263919618)


def test_partial_rotary_turns_the_first_values_and_passes_the_rest():
    """YaRN over the first 64 of 128 values: the last 64 leave exactly as
    they came, unscaled; the first 64 are the reference's turn, their norm
    the attention factor times the input's, and position 0 is only
    scaled. The two tables are each other's to a float32 ulp or two, and
    an angle is the position times the table: atol 1e-4 over the first
    256 positions, 3e-3 up to 4,095 (an ulp of 1.0 times 4,095 rad, times
    the factor and values of 3 or 4)."""
    from paddle_tpu.ops import attention as attention_ops

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 4096, 2, 128)), jnp.float32)
    table = attention_ops.yarn_inverse_frequencies(64, 500000.0, 64, 4096,
                                                   64, 1)
    factor = FULL_ROPE["attention_factor"]
    turned = attention_ops.rotary(x, 500000.0, dims=64, inverse=table,
                                  factor=factor)
    np.testing.assert_array_equal(turned[..., 64:], x[..., 64:])
    want = ref.rotary(x, FULL_ROPE)
    np.testing.assert_allclose(turned[:, :256], want[:, :256], atol=1e-4)
    np.testing.assert_allclose(turned, want, atol=3e-3)
    np.testing.assert_allclose(
        jnp.linalg.norm(turned[..., :64], axis=-1),
        factor * jnp.linalg.norm(x[..., :64], axis=-1), rtol=1e-5)
    np.testing.assert_allclose(turned[:, 0, :, :64], factor * x[:, 0, :, :64],
                               rtol=1e-6)
    # value i turns with value i + 32 by position * table[i]
    angle = 7 * float(table[3])
    np.testing.assert_allclose(
        turned[0, 7, 1, 3], factor * (x[0, 7, 1, 3] * np.cos(angle)
                                      - x[0, 7, 1, 35] * np.sin(angle)),
        rtol=1e-5)


def test_plain_rotary_is_unchanged():
    """Without a table, a share or a factor the turn is the one lfm2's
    attention has taken since it came: theta^(-2i/D) over the whole head,
    written out here, and the keywords at their defaults change nothing,
    to the bit. The window layers' plain turn is the reference's."""
    from paddle_tpu.ops import attention as attention_ops

    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 9, 2, 8)),
                    jnp.float32)
    half = 4
    inverse = 1e6 ** (-np.arange(half) / half)
    angles = np.arange(9)[:, None] * inverse
    cos, sin = np.cos(angles)[None, :, None], np.sin(angles)[None, :, None]
    first, second = np.asarray(x[..., :half]), np.asarray(x[..., half:])
    want = np.concatenate([first * cos - second * sin,
                           second * cos + first * sin], axis=-1)
    plain = attention_ops.rotary(x, 1e6)
    np.testing.assert_allclose(plain, want, atol=2e-6)
    np.testing.assert_array_equal(
        plain, attention_ops.rotary(x, 1e6, dims=8, factor=1.0))
    np.testing.assert_allclose(
        attention_ops.rotary(x, 10000.0),
        ref.rotary(x, {"rope_theta": 10000, "rope_type": "default"}),
        atol=2e-6)


# -- the layers against the reference's ------------------------------------

SMALL = {"hidden_size": 32, "head_dim": 8, "num_key_value_heads": 2,
         "layer_types": ["full_attention", "sliding_attention"],
         "num_attention_heads_per_layer": [4, 6], "sliding_window": 5,
         "rope_parameters": {
             "full_attention": dict(FULL_ROPE),
             "sliding_attention": {"rope_type": "default",
                                   "rope_theta": 10000}},
         "num_experts_published": 16, "num_experts_per_tok": 4,
         "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6}


def _seq(seed, t=12, lengths=(12, 9), width=32):
    from paddle_tpu.core.sequence import SequenceBatch

    rng = np.random.default_rng(seed)
    data = jnp.asarray(rng.standard_normal((len(lengths), t, width)),
                       jnp.float32)
    return SequenceBatch(data, jnp.asarray(lengths, jnp.int32))


def _valid(x):
    return (np.arange(x.data.shape[1])[None, :]
            < np.asarray(x.lengths)[:, None])


def _attention_weights(seed, heads, scale=0.3):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"q": jax.random.normal(k[0], (32, heads * 8)) * scale,
            "k": jax.random.normal(k[1], (32, 16)) * scale,
            "v": jax.random.normal(k[2], (32, 16)) * scale,
            "o": jax.random.normal(k[3], (heads * 8, 32)) * scale,
            "g": jax.random.normal(k[4], (32, heads)) * scale}


def _attention(kind, x, w, gate="head"):
    from paddle_tpu import data_type
    from paddle_tpu import layer as L
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.topology import Topology

    L.reset_name_counters()
    data = L.data(name="x", type=data_type.dense_vector_sequence(32))
    rope = hybrid_lm._rope(SMALL["rope_parameters"][kind], 8)
    node = L.gqa_attention(
        input=data, heads=w["o"].shape[0] // 8, kv_heads=2, head_dim=8,
        rope=rope, gate=gate, block=4, name="mix",
        window=SMALL["sliding_window"] if kind != "full_attention" else None)
    topo = Topology(node)
    given = {k: v for k, v in w.items() if gate or k != "g"}
    assert {n: s.shape for n, s in topo.param_specs().items()} == {
        "mix." + k: v.shape for k, v in given.items()}
    with jax.default_matmul_precision("highest"):
        return topo.apply({"mix." + k: v for k, v in given.items()},
                          {"x": x}, mode="test")[0]["mix"].data


@pytest.mark.parametrize("kind,t,lengths", [
    ("full_attention", 12, (12, 9)), ("full_attention", 21, (21, 16)),
    ("sliding_attention", 12, (12, 9)), ("sliding_attention", 21, (21, 16))])
def test_gated_attention_is_the_references(kind, t, lengths):
    """Both kinds over the valid positions (atol 3e-6: float32 at
    `highest`, blocks of 4 keys against the reference's whole rows), the
    window of 5 keys crossing blocks."""
    w = _attention_weights(2, SMALL["num_attention_heads_per_layer"][
        SMALL["layer_types"].index(kind)])
    x = _seq(17, t=t, lengths=lengths)
    out = _attention(kind, x, w)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x.data, w, kind, SMALL)
    valid = _valid(x)
    np.testing.assert_allclose(out[valid], want[valid], atol=3e-6)


def test_the_head_gate_scales_each_head_by_its_own_sigmoid():
    """A gate whose logits are 40 passes every head whole (the ungated
    layer's output to float32 rounding), -40 shuts them all, and a gate
    open on head 0 alone leaves the output head 0's part of W_o."""
    w = _attention_weights(5, 4)
    x = _seq(19)
    valid = _valid(x)
    plain = _attention("full_attention", x, w, gate=None)
    ones = type(x)(x.data.at[..., :].set(1.0), x.lengths)
    for logit, want in ((40.0, plain), (-40.0, 0.0 * plain)):
        g = jnp.zeros((32, 4)).at[0].set(logit)
        np.testing.assert_allclose(
            _attention("full_attention", ones, dict(w, g=g))[valid],
            (_attention("full_attention", ones, w, gate=None)
             if logit > 0 else want)[valid], atol=1e-6)
    g = jnp.zeros((32, 4)).at[0].set(jnp.asarray([40.0, -40, -40, -40]))
    head0 = dict(w, o=w["o"].at[8:].set(0.0))
    np.testing.assert_allclose(
        _attention("full_attention", ones, dict(w, g=g))[valid],
        _attention("full_attention", ones, head0, gate=None)[valid],
        atol=1e-6)
    assert float(jnp.abs(plain[valid]).max()) > 0.1


def _moe_weights(seed, held):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"router": jax.random.normal(k[0], (32, 16)) * 0.5,
            "w_in": jax.random.normal(k[1], (held, 32, 48)) * 0.3,
            "w_out": jax.random.normal(k[2], (held, 24, 32)) * 0.3,
            "shared_in": jax.random.normal(k[3], (32, 40)) * 0.3,
            "shared_out": jax.random.normal(k[4], (20, 32)) * 0.3}


def _probe(x):
    """A cotangent that is zero on the padded positions: their shared
    expert's output is computed (every token crosses it) and is no token
    of a cost."""
    probe = np.random.default_rng(1).standard_normal(x.data.shape)
    return jnp.asarray(probe * _valid(x)[..., None], jnp.float32)


def _moe_layer(x, w, first):
    """(the value, the weights' gradients of sum(out * probe), the step's
    counters) of `layer.moe` with a shared expert over x, held from
    `first`."""
    from paddle_tpu import data_type
    from paddle_tpu import layer as L
    from paddle_tpu.topology import Topology

    L.reset_name_counters()
    node = L.moe(
        input=L.data(name="x", type=data_type.dense_vector_sequence(32)),
        experts_total=16, experts_held=w["w_in"].shape[0], first_held=first,
        top_k=4, width=24, scaling=2.5, use_bias=False, shared_width=20,
        name="moe")
    topo = Topology(node)
    assert {n: s.shape for n, s in topo.param_specs().items()} == {
        "moe." + k: v.shape for k, v in w.items()}
    probe = _probe(x)
    counts = {}

    def value(p, into=None):
        out = topo.apply({"moe." + k: v for k, v in p.items()}, {"x": x},
                         mode="train", counts=into)[0]["moe"].data
        return jnp.sum(out * probe), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.value_and_grad(value, has_aux=True)(w)
        value(w, counts)
    return out, grads, counts


def _moe_reference(x, w, first, part=ref.experts):
    cfg = dict(SMALL, num_experts=w["w_in"].shape[0], first_expert=first)
    probe = _probe(x)

    def value(p):
        out = part(x.data, p, cfg)
        return jnp.sum(out * probe), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.value_and_grad(value, has_aux=True)(w)
    return out, grads


@pytest.mark.parametrize("held,first", [(16, 0), (2, 0), (2, 6), (3, 13)])
def test_the_expert_layer_with_its_shared_expert_is_the_references(held,
                                                                   first):
    """Value over the valid positions and every weight's gradient (atol
    4e-6 and 3e-5: float32 at `highest`, the program's sorted sums against
    the reference's scan over experts, outputs of 5 or so under the 2.5
    scaling), all experts held and a share of them; the shared expert
    crosses every token, padding included."""
    x = _seq(3)
    w = _moe_weights(4, held)
    out, grads, counts = _moe_layer(x, w, first)
    want, want_grads = _moe_reference(x, w, first)
    valid = _valid(x)
    np.testing.assert_allclose(out[valid], want[valid], atol=4e-6)
    for leaf in w:
        np.testing.assert_allclose(grads[leaf], want_grads[leaf], atol=3e-5,
                                   err_msg=leaf)
    # rows here: the valid tokens' pairs whose expert is held; the plain
    # form observes no row tiles
    chosen = np.asarray(jax.lax.top_k(jax.nn.sigmoid(
        x.data @ w["router"]), 4)[1])
    here = (chosen >= first) & (chosen < first + held) & valid[..., None]
    assert int(counts["paddle_tpu_moe_rows_here"]) == here.sum()
    assert "paddle_tpu_moe_rows_visited" not in counts


def test_eight_shares_add_up_to_the_uncut_layer():
    """Guide section 4: with 16 experts over 8 shares of two, the routed
    parts of all shares and the shared expert counted once add up to the
    uncut reference layer (atol 4e-6: eight float32 sums); each share's
    experts' gradients are the uncut layer's slices, the router's add up,
    and every share's shared expert has the uncut layer's gradient."""
    x = _seq(11, lengths=(12, 12))
    whole = _moe_weights(12, 16)
    want, want_grads = _moe_reference(x, whole, 0)
    shared, _ = _moe_reference(
        x, whole, 0, part=lambda u, p, c: ref._swiglu(
            u, p["shared_in"], p["shared_out"], None))
    total, router = 0.0, 0.0
    for first in range(0, 16, 2):
        share = dict(whole, w_in=whole["w_in"][first:first + 2],
                     w_out=whole["w_out"][first:first + 2])
        out, grads, _ = _moe_layer(x, share, first)
        total, router = total + out - shared, router + grads["router"]
        for leaf in ("w_in", "w_out"):
            np.testing.assert_allclose(
                grads[leaf], want_grads[leaf][first:first + 2], atol=3e-5,
                err_msg="%s of the share from %d" % (leaf, first))
        for leaf in ("shared_in", "shared_out"):
            np.testing.assert_allclose(grads[leaf], want_grads[leaf],
                                       atol=3e-5, err_msg=leaf)
    np.testing.assert_allclose(total + shared, want, atol=4e-6)
    np.testing.assert_allclose(router, want_grads["router"], atol=3e-5)


def _tiles_by_hand(sizes, tile):
    """Rows the products' row tiles cover, group by group: from the tile
    of a group's first row to the tile of its last."""
    start, rows = 0, 0
    for size in sizes:
        if size:
            rows += ((start + size - 1) // tile - start // tile + 1) * tile
        start += size
    return rows


@pytest.mark.parametrize("sizes,rows,want", [
    ([448] * 32, 131_072, None),      # the cell at uniform routing
    ([900] * 8, 32_768, None),        # lfm2's cell at uniform routing
    ([0, 256, 0, 512, 1, 0], 2048, 4 * 256),
    ([5000] + [0] * 7, 8192, 20 * 256),
    ([0] * 4, 1024, 0),
])
def test_the_visited_rows_are_a_hand_count_of_tiles(sizes, rows, want):
    from paddle_tpu.ops import pallas_moe

    tile = pallas_moe._ROW_TILE
    _, steps = pallas_moe.groups(jnp.asarray(sizes, jnp.int32), rows=rows,
                                 visit_empty=False)
    assert int(steps) * tile == _tiles_by_hand(sizes, tile)
    assert want is None or int(steps) * tile == want
    if sizes == [448] * 32:
        # 80 tiles for 56 (142.9%): equal groups start at four offsets in
        # a tile; routing's uneven groups start anywhere, about 88 (156%)
        assert int(steps) == 80


def test_the_fused_layer_counts_its_visited_rows(monkeypatch):
    """`layer.moe` in the fused form (interpreted, tiles of 32 rows so
    that groups share them): the counter is the hand count of the tiles
    the routing's groups touch, and the output is the plain form's."""
    from paddle_tpu import data_type
    from paddle_tpu import layer as L
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.ops import moe as moe_ops
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops import pallas_moe
    from paddle_tpu.topology import Topology

    rng = np.random.default_rng(6)
    x = SequenceBatch(jnp.asarray(rng.standard_normal((2, 48, 128)),
                                  jnp.float32), jnp.asarray([48, 40]))
    w = {"router": jnp.asarray(0.3 * rng.standard_normal((128, 8)),
                               jnp.float32),
         "w_in": jnp.asarray(0.1 * rng.standard_normal((4, 128, 256)),
                             jnp.float32),
         "w_out": jnp.asarray(0.1 * rng.standard_normal((4, 128, 128)),
                              jnp.float32)}
    outs = {}
    jax.clear_caches()
    try:
        monkeypatch.setattr(pallas_moe, "_ROW_TILE", 32)
        for form in ("plain", "fused"):
            monkeypatch.setattr(pk, "_INTERPRET", form == "fused")
            assert moe_ops.experts_form(128, 128, 96, 2) == form
            L.reset_name_counters()
            node = L.moe(input=L.data(
                name="x", type=data_type.dense_vector_sequence(128)),
                experts_total=8, experts_held=4, first_held=2, top_k=2,
                width=128, use_bias=False, name="moe")
            counts = {}
            outs[form] = Topology(node).apply(
                {"moe." + k: v for k, v in w.items()}, {"x": x},
                mode="train", counts=counts)[0]["moe"].data
            outs[form + "_counts"] = {k: int(v) for k, v in counts.items()}
    finally:
        jax.clear_caches()
    valid = _valid(x).reshape(-1)
    chosen, _ = moe_ops.route(x.data.reshape(-1, 128), w["router"], None, 2)
    sizes = np.asarray(moe_ops.dispatch(chosen, jnp.asarray(valid), 2, 4)[2])
    fused = outs["fused_counts"]
    assert fused["paddle_tpu_moe_rows_visited"] == _tiles_by_hand(
        sizes.tolist(), 32) > sizes.sum()
    assert fused["paddle_tpu_moe_rows_here"] == sizes.sum()
    assert "paddle_tpu_moe_rows_visited" not in outs["plain_counts"]
    np.testing.assert_allclose(outs["fused"], outs["plain"], atol=1e-5)


# -- the whole model ---------------------------------------------------------

def _program(cfg, seed):
    """(topology, cost node, logits node, {program name: reference value},
    names)."""
    from paddle_tpu import layer as L
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.topology import Topology

    L.reset_name_counters()
    _, _, logits, cost = hybrid_lm.from_config(
        cfg, prefix=bench_model.PREFIX, keep_layers=bench_model.KEEP_LAYERS)
    names = bench_model.program_names(cfg)
    weights, _ = ref.init_weights(seed, cfg)
    return Topology(cost), cost, logits, \
        {names[k]: v for k, v in weights.items()}, names


def test_every_reference_leaf_has_its_place_in_the_program(tiny, cfg):
    topo, _, _, params, names = _program(tiny, 3)
    specs = topo.param_specs()
    assert set(specs) == set(names.values()) == set(params)
    for name, value in params.items():
        assert specs[name].shape == value.shape, name
    assert not [n for n, s in specs.items() if s.attr.is_static]
    blocks = [n for n in topo.nodes if n.layer_type == "recompute"]
    assert [n.name for n in blocks] == ["lm.l%d.block" % i for i in range(5)]
    # at the real widths too, from the shapes alone
    real = bench_model.program_names(cfg)
    assert set(real) == set(ref._shapes(cfg))
    assert len(set(real.values())) == len(real)
    assert real["head"] == "lm.head.w0" and real["emb"] == "lm.emb"
    assert real["l1.g"] == "lm.l1.mixer.g"
    assert real["l0.mlp_in"] == "lm.l0.mlp.w0" and "l1.mlp_in" not in real
    assert real["l4.shared_in"] == "lm.l4.moe.shared_in"
    shapes = ref._shapes(cfg)
    assert shapes["l1.q"] == (2048, 64 * 128) and shapes["l4.q"] == (
        2048, 48 * 128)
    assert shapes["l1.g"] == (2048, 64) and shapes["l0.g"] == (2048, 48)
    assert shapes["l4.w_in"] == (32, 2048, 1024)
    assert shapes["l4.router"] == (2048, 256)
    assert shapes["l4.shared_out"] == (512, 2048)


def test_the_tiny_program_follows_the_reference_leaf_by_leaf(tiny,
                                                             tiny_cell):
    """Logits over the valid positions, the loss and the first gradient,
    in float32 at `highest`, the gradient by the difference's norm over
    the reference's or the median leaf's, whichever is larger, as
    `check.leaf_gaps` takes it: every leaf within 1e-4 (float32 sums in
    another order through five layers)."""
    from paddle_tpu.topology import convert_feed

    pool = traffic.make_pool(tiny["inputs"], tiny_cell, 5)
    assert sorted(len(row[0]) for row in pool[0]) == [40, 45, 51, 56]
    with jax.default_matmul_precision("highest"):
        topo, cost, logits, params, names = _program(tiny, 5)
        feed = convert_feed(topo, pool[0])
        got = topo.apply(params, feed, mode="train",
                         outputs=[logits.name])[0][logits.name].data
        loss, grads = jax.value_and_grad(lambda p: jnp.mean(topo.apply(
            p, feed, mode="train")[0][cost.name]))(params)
        weights, state = ref.init_weights(5, tiny)
        batch = tuple(jnp.asarray(a) for a in ref.batch_arrays(pool[0], tiny))
        want_logits = ref.logits_of(weights, batch[0], tiny)
        want_loss, want = jax.value_and_grad(
            lambda w: ref.loss(w, state, batch, tiny)[0])(weights)
    valid = np.arange(batch[0].shape[1])[None, :] < np.asarray(
        batch[2])[:, None]
    t = batch[0].shape[1]
    np.testing.assert_allclose(got[:, :t][valid], want_logits[valid],
                               atol=2e-5)
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
    """The train driver's own first three steps at the tiny size: `SGD.train`
    with reader, `convert_feed` and the feeder, one call of one batch and
    one of two, against three plain Momentum steps of the reference (the
    tolerances are lfm2's, for the same reasons); the step's expert
    counters come back with the cost, one observation a step."""
    import paddle_tpu as paddle
    from paddle_tpu import layer as L
    from paddle_tpu.observe import metrics as observe_metrics

    lr, mu = 0.01, 0.9
    pool = traffic.make_pool(tiny["inputs"], tiny_cell, 5)
    registry = observe_metrics.get_registry()

    def observed():
        held = registry.snapshot()["histograms"]
        return held.get("paddle_tpu_moe_rows_here", {"count": 0, "sum": 0.0})

    paddle.init(use_tpu=False, seed=5, compute_dtype="float32",
                matmul_precision="highest")
    try:
        L.reset_name_counters()
        cost = bench_model.build(tiny)
        names = bench_model.program_names(tiny)
        weights, _ = ref.init_weights(5, tiny)
        want = common.train3(ref, tiny, 5,
                             [ref.batch_arrays(b, tiny) for b in pool],
                             lr, mu)
        start = {k: np.asarray(v) for k, v in weights.items()}
        params = paddle.parameters.create(cost)
        params.update_from({names[k]: v for k, v in start.items()})
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
               "state3": {}}
    numbers = check.readings(program, want)
    assert numbers["grad1"] < 5e-3 and numbers["delta3"] < 1e-3
    # three steps, three observations; of 192 tokens' 2 choices over 4
    # expert layers about a half falls on the 4 held of 8
    assert after["count"] - before["count"] == 3
    rows = (after["sum"] - before["sum"]) / 3
    assert 0.25 * 1536 < rows < 0.75 * 1536


def test_packed_rows_are_refused(tiny, tiny_cell):
    from paddle_tpu.core.sequence import PackedSequenceBatch
    from paddle_tpu.topology import convert_feed
    from paddle_tpu.utils.error import EnforceError

    topo, _, _, params, _ = _program(tiny, 3)
    feed = convert_feed(topo, traffic.make_pool(tiny["inputs"], tiny_cell,
                                                3)[0])
    packed = {k: PackedSequenceBatch(
        v.data, v.lengths, jnp.zeros(v.data.shape[:2], jnp.int32))
        for k, v in feed.items()}
    with pytest.raises(EnforceError, match="packed"):
        topo.apply(params, packed, mode="train")


def test_the_gauges_read_their_values_at_the_cells_shapes(cfg, cell):
    """The step traced at the cell's own shapes on abstract values, under
    the configuration's bfloat16 (nothing is computed): four expert
    layers of 32 held of 256, sorted buffers of 8 rows a position, and
    what the last KEEP_LAYERS blocks keep."""
    import paddle_tpu as paddle
    from paddle_tpu import layer as L
    from paddle_tpu.observe import metrics as observe_metrics
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
        assert out.shape == (4,)
    finally:
        paddle.init(use_tpu=False)
    gauges = observe_metrics.get_registry().snapshot()["gauges"]
    assert gauges["paddle_tpu_moe_experts_held"] == 32
    assert gauges["paddle_tpu_moe_experts_total"] == 256
    assert gauges["paddle_tpu_moe_rows_bound"] == 8 * 4 * 4096 * 4
    # on the CPU the plain form: no visited rows
    assert sorted(counts) == ["paddle_tpu_moe_expert_load_max",
                              "paddle_tpu_moe_rows_here"]
    positions, kept = 4 * 4096, bench_model.KEEP_LAYERS
    assert kept == 2
    assert gauges["paddle_tpu_recompute_kept_bytes"] == 2 * kept * (
        positions * 2048 + 8 * positions * 2 * 512)


def test_the_new_scopes_are_in_the_compiled_step(tiny, tiny_cell):
    from paddle_tpu.topology import convert_feed

    topo, cost, _, params, _ = _program(tiny, 3)
    feed = convert_feed(topo, traffic.make_pool(tiny["inputs"], tiny_cell,
                                                3)[0])
    text = jax.jit(jax.grad(lambda p: jnp.mean(topo.apply(
        p, feed, mode="train")[0][cost.name]))).lower(params).compile(
            ).as_text()
    for scope in ("rope", "attention_gate", "shared_expert",
                  "window_attention", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "gqa_attention",
                  "gated_mlp", "rmsnorm", "block"):
        assert "paddle_tpu." + scope in text, scope


def test_a_rehearsal_of_the_cell_is_correct(tiny_cell):
    import contextlib
    import io

    from chipbench import run as run_mod

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run_mod.main(["--workload", CELL, "--seed", str(2 ** 31 + 19),
                             "--seconds", "3", "--trace", "0",
                             "--rehearse", TINY]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == set(tiny_cell["limits"])


def _ctx(visited, rows):
    def held(total, count):
        return {"count": count, "sum": float(total)}

    hists = {"paddle_tpu_moe_rows_visited": visited,
             "paddle_tpu_moe_rows_here": rows}
    return {"registry_open": {k: held(5 * v, 5) for k, v in hists.items()
                              if v is not None},
            "registry_close": {k: held(15 * v, 15) for k, v in hists.items()
                               if v is not None}}


def test_the_reader_reads_the_windows_observations():
    """Groups of 448 rows visit 80 tiles of 256 a layer for 56: 142.9%;
    a program without the counter (the parent's, or the plain form) reads
    nothing and raises nothing."""
    here = 448 * 32 * 4
    visited = _tiles_by_hand([448] * 32, 256) * 4
    assert moe_rows_visited_pct.read(_ctx(visited, here)) == pytest.approx(
        100.0 * visited / here)
    assert 142 < moe_rows_visited_pct.read(_ctx(visited, here)) < 143
    assert moe_rows_visited_pct.read(_ctx(here, here)) == pytest.approx(100)
    assert moe_rows_visited_pct.read(_ctx(None, here)) is None
    assert moe_rows_visited_pct.read(_ctx(None, None)) is None
    assert moe_rows_visited_pct.read(_ctx(visited, 0)) is None


def test_the_harness_reads_the_metric_in_the_expert_cells_alone():
    from chipbench import run as run_mod

    manifest = _load("BENCHMARK.json")
    ctx = _ctx(2000, 1000)
    for entry in manifest["workloads"]:
        line = run_mod.read_metrics(
            [m for m in run_mod.metrics_of(manifest, "per_layer",
                                           entry["name"])
             if m["name"] == "moe_rows_visited_pct"], ctx)
        listed = entry["name"] in (CELL, "lfm2-8b-a1b-seq4096-bs2-train")
        assert (line == {"moe_rows_visited_pct": {
            "value": pytest.approx(200.0), "unit": "%"}}) == listed


def test_a_program_without_laguna_refuses_the_configuration(tiny,
                                                            monkeypatch):
    """The parent's `from_config`: the cell fails at once, before a
    parameter is made."""
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.utils.error import EnforceError

    monkeypatch.setattr(hybrid_lm, "MODEL_TYPES", {
        k: v for k, v in hybrid_lm.MODEL_TYPES.items() if k != "laguna"})
    with pytest.raises(EnforceError,
                       match="hybrid_lm builds no model_type 'laguna'"):
        hybrid_lm.from_config(tiny)


@pytest.mark.parametrize("change,words", [
    ({"num_attention_heads_per_layer": [6, 8, 8, 7, 6, 8, 8, 8]},
     "sliding_attention layers have 8 and 7 query heads"),
    ({"mlp_layer_types": ["dense", "sparse", "dense"] + ["sparse"] * 5},
     "dense layers after the first sparse one"),
    ({"gating": "per-head"}, "gating true"),
    ({"moe_apply_router_weight_on_input": True}, "weighs an expert's output"),
])
def test_from_config_refuses_what_it_cannot_build(tiny, change, words):
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.utils.error import EnforceError

    with pytest.raises(EnforceError, match=words):
        hybrid_lm.from_config(dict(tiny, **change))


def test_the_options_by_layer_kind(cfg):
    from paddle_tpu.models import hybrid_lm

    opts = hybrid_lm._laguna_options(cfg)
    full, window = opts["attention"], opts["sliding_attention"]
    assert (full["heads"], window["heads"]) == (48, 64)
    assert full["rope"]["dims"] == 64 and "dims" not in window["rope"]
    assert full["rope"]["factor"] == pytest.approx(1.4158883083359672)
    assert window["rope"] == {"theta": 10000.0}
    assert window["window"] == 512 and "window" not in full
    assert full["gate"] == window["gate"] == "head"
    assert opts["dense_layers"] == 1 and opts["tie_head"] is False
    assert opts["experts"] == {
        "experts_total": 256, "experts_held": 32, "first_held": 0,
        "top_k": 8, "width": 512, "scaling": 2.5, "use_bias": False,
        "shared_width": 512}
    assert opts["eps"] == 1e-6 and opts["mlp_size"] == 8192


# The tiny preset in float32 against its own fp8 and half of its batch; the
# cell's own limits come from the chip (PERF.md section 6).
TINY_LIMITS = {"loss1": 0.01, "grad1": 0.015, "grad1_med": 0.002,
               "delta3": 0.012, "delta3_med": 0.0015}


# The cell's readings on a TPU v5e at its own size (PERF.md section 6):
# (grad1, grad1_med, delta3, delta3_med) of the program, seven seeds (the
# seventh from an archive of the committed tree), and
# of chipbench/control.py's fp8 stand-in, three seeds.
CHIP_NUMBERS = ("grad1", "grad1_med", "delta3", "delta3_med")
CHIP_PROGRAM = [(0.002386, 0.001493, 0.002011, 0.001609),
                (0.002270, 0.001615, 0.002324, 0.001772),
                (0.002608, 0.001746, 0.002542, 0.001750),
                (0.002353, 0.001711, 0.002512, 0.001683),
                (0.003096, 0.001563, 0.002843, 0.001718),
                (0.003018, 0.001826, 0.002615, 0.001786),
                (0.002463, 0.001695, 0.002152, 0.001623)]
CHIP_CONTROL_FP8 = [(0.104663, 0.002981, 0.119960, 0.002451),
                    (0.190989, 0.008491, 0.242539, 0.004592),
                    (0.075528, 0.003498, 0.115578, 0.002367)]


@pytest.mark.parametrize("reading,correct",
                         [(r, True) for r in CHIP_PROGRAM]
                         + [(r, False) for r in CHIP_CONTROL_FP8])
def test_the_cells_limits_pass_the_program_and_hold_fp8_off(cell, reading,
                                                            correct):
    """Decided by the harness's own `check.decide` at the committed
    limits: every program seed passes and every fp8 seed fails."""
    numbers = dict(zip(CHIP_NUMBERS, reading))
    assert check.decide(numbers, cell["limits"])[1] is correct


def test_every_limit_lies_between_the_program_and_the_control(cell):
    for j, name in enumerate(CHIP_NUMBERS):
        program = max(r[j] for r in CHIP_PROGRAM)
        fp8 = min(r[j] for r in CHIP_CONTROL_FP8)
        assert program < cell["limits"][name] < fp8, name


@pytest.mark.parametrize("seed", [1, 2])
def test_control_and_half_batch_fail_a_limit(seed, tiny, tiny_cell):
    cell = dict(tiny_cell, name=CELL, limits=TINY_LIMITS)
    out = control.read_seed(cell, tiny, seed)
    assert set(out) == {"control_fp8", "half_batch"}
    for name, stood in out.items():
        assert stood["correct"] is False, (name, stood["numbers"])
