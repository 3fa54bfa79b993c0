"""The `granite-4.0-h-micro` configuration's benchmark files: the
configuration against its manifest entry, the operation count against
XLA's, the two token readers on a fake `ctx`, a rehearsal of the cell, and
the control at a tiny size."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import control
from chipbench.flops import granite_h_micro as flops
from chipbench.metrics import feed_padding_pct, train_tokens_per_s
from chipbench.reference import granite_h_micro as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
CONFIG = "granite-4.0-h-micro"
CELL = "granite-4.0-h-micro-seq4096-bs2-train"
# config.json of ibm-granite/granite-4.0-h-micro, the numbers that shape it
PUBLISHED = {
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "max_position_embeddings": 131072,
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "vocab_size": 100352,
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
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # layer_types is kept whole; the layers held are one period of it
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    held = kinds[:cfg["num_hidden_layers"]]
    assert held.count("mamba") == 9 and held[5] == "attention"
    assert cfg["tie_word_embeddings"] is True
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["deployment"] and cfg["assumed"]["init"]


def test_the_cell_is_the_traffic_the_issue_gives(cell):
    assert (cell["batch"], cell["pool_batches"], cell["chips"]) == (2, 3, 1)
    assert cell["lengths"] == {"min": 3072, "max": 4096}
    assert cell["trace"] == {"after_s": 3.0, "steps": 8}
    assert set(cell["limits"]) == {"grad1", "grad1_med", "delta3",
                                   "delta3_med"}
    assert flops.row_lengths(cell) == [3072, 4096]


def test_the_reference_imports_nothing_of_the_program():
    source = open(os.path.join(ROOT, "chipbench", "reference",
                               "granite_h_micro.py")).read()
    assert "paddle_tpu" not in source
    assert "from chipbench.reference import common" in source


def test_the_count_is_pinned_at_the_cell_size(cfg, cell):
    per = flops.per_token_flops(cfg)
    assert per == {"mamba": 154_402_816, "attention": 121_634_816,
                   "head": 51_380_224}
    assert flops.train_step_flops(cfg, cell) == 33_925_229_445_120
    # the parameters the cut holds: 772,160,448
    shapes = ref._shapes(cfg)
    count = 0
    for shape, _ in shapes.values():
        n = 1
        for s in shape:
            n *= s
        count += n
    assert count == 772_160_448


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["flops"]


def test_the_count_agrees_with_xla_on_the_reference_forward(monkeypatch):
    """XLA counts a loop's body once, so the reference's loops are opened
    for the count: one row, one block of queries, and the scan's products
    from a sum over all tokens at once (the same operations)."""
    cfg = dict(_load("tests", "chipbench", "tiny", "configs",
                     CONFIG + ".json"),
               hidden_size=128, shared_intermediate_size=256,
               num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=32, mamba_d_state=32,
               vocab_size=512, layer_types=["mamba", "attention"],
               num_hidden_layers=2)
    t = 64
    cell = {"batch": 1, "lengths": {"min": t, "max": t}}

    def all_tokens_at_once(x, dt, a, b_mat, c_mat, d_skip, quant):
        # dt x (x) B into a state and S C out of it, for every token
        heads = x.shape[2]
        per = heads // b_mat.shape[2]
        b_h, c_h = (jnp.repeat(v, per, axis=2) for v in (b_mat, c_mat))
        state = jnp.einsum("bthp,bthn->bthpn", dt[..., None] * x, b_h)
        return jnp.einsum("bthpn,bthn->bthp", state, c_h) \
            + d_skip[:, None] * x

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
    # half, and norms, gates, the convolution and the cost besides
    heads, hd = 4, 128 // 4
    square = 2 * 2 * heads * hd * t * t - 2 * 2 * heads * hd * t * (t + 1) // 2
    assert mine <= xla - square <= 1.1 * mine, (mine, xla, square)


HIST = ("paddle_tpu_train_step_tokens", "paddle_tpu_train_step_positions")


def _ctx(opened, closed, stamps=(0.0, 0.5, 1.0, 1.5, 2.0)):
    def hists(pairs):
        return {name: {"count": c, "sum": s}
                for name, (c, s) in zip(HIST, pairs) if c is not None}

    return {"registry_open": hists(opened), "registry_close": hists(closed),
            "stamps": list(stamps)}


def test_the_token_readers_read_the_windows_observations():
    # 3 steps before the window, 4 in it: 7,168 of 8,192 positions a step
    ctx = _ctx([(3, 3 * 7168.0), (3, 3 * 8192.0)],
               [(7, 7 * 7168.0), (7, 7 * 8192.0)])
    assert feed_padding_pct.read(ctx) == pytest.approx(12.5)
    # 4 steps in 2 s of 7,168 tokens
    assert train_tokens_per_s.read(ctx) == pytest.approx(14336.0)


@pytest.mark.parametrize("opened,closed", [
    ([(None, 0), (None, 0)], [(None, 0), (None, 0)]),   # the parent commit
    ([(3, 9.0), (3, 12.0)], [(3, 9.0), (3, 12.0)]),     # nothing observed
])
def test_the_token_readers_say_nothing_where_nothing_was_counted(opened,
                                                                 closed):
    ctx = _ctx(opened, closed)
    assert feed_padding_pct.read(ctx) is None
    assert train_tokens_per_s.read(ctx) is None


def test_a_rehearsal_of_the_cell_is_correct():
    import contextlib
    import io

    from chipbench import run as run_mod

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run_mod.main(["--workload", CELL, "--seed", str(2 ** 31 + 13),
                             "--seconds", "3", "--trace", "0",
                             "--rehearse", TINY]) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line) == ["rehearsal", "correct", "attempted", "failed",
                          "checks"]
    assert line["correct"] is True and line["failed"] == 0
    tiny = _load("tests", "chipbench", "tiny", "workloads", CELL + ".json")
    assert set(line["checks"]) == set(tiny["limits"])


# The tiny preset in float32 against its own fp8 and half of its batch: fp8
# reads 0.043 to 0.079 on the worst leaf of the change after three steps,
# half a batch 0.5 and more on the first gradient's; the cell's own limits
# come from the chip (PERF.md section 6).
TINY_LIMITS = {"loss1": 0.01, "grad1": 0.03, "grad1_med": 0.01,
               "delta3": 0.03, "delta3_med": 0.01}


@pytest.mark.parametrize("seed", [1, 2])
def test_control_and_half_batch_fail_a_limit(seed):
    cell = dict(_load("tests", "chipbench", "tiny", "workloads",
                      CELL + ".json"), name=CELL, limits=TINY_LIMITS)
    cfg = _load("tests", "chipbench", "tiny", "configs", CONFIG + ".json")
    out = control.read_seed(cell, cfg, seed)
    assert set(out) == {"control_fp8", "half_batch"}
    for name, stood in out.items():
        assert stood["correct"] is False, (name, stood["numbers"])
