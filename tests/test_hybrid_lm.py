"""`models/hybrid_lm.py` at a tiny size with both layer kinds: against the
plain reference in loss and every gradient leaf, with and without block
recompute and with what a block keeps for backward, the tied embedding,
the vocabulary slice, the padded tail, and through `SGD.train` with its
two always-on histograms. Then the `olmo_hybrid` layout at a tiny size:
its layer kinds, norms after the branches, the untied head, in how many
layers a block keeps its two values, and what `from_config` refuses.
Last, what a kind of mixer offers the block round it: a Mamba-2 layer's
first product, and nothing that moves a model without such a layer."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from chipbench import traffic
from chipbench.models import granite_h_micro as bench_model
from chipbench.reference import granite_h_micro as ref
from paddle_tpu import layer as L
from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.data import feeder as data_feeder
from paddle_tpu.layer import decoder
from paddle_tpu.models import hybrid_lm
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.topology import Topology, convert_feed
from paddle_tpu.utils.error import EnforceError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
CELL = "granite-4.0-h-micro-seq4096-bs2-train"


def _load(kind, name):
    with open(os.path.join(TINY, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def _float32_at_highest():
    L.reset_name_counters()
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture()
def cfg():
    return _load("configs", "granite-4.0-h-micro")


def _program(cfg, seed=3, recompute=True):
    """(cost node, topology, parameters from the reference's weights,
    reference weights, {reference name: program name})."""
    L.reset_name_counters()
    cost = hybrid_lm.from_config(cfg, recompute=recompute)[3]
    names = bench_model.program_names(cfg)
    weights, _ = ref.init_weights(seed, cfg)
    topo = Topology(cost)
    return cost, topo, {names[k]: v for k, v in weights.items()}, weights, \
        names


def _batch(cfg, seed=3):
    return traffic.make_pool(cfg["inputs"], _load("workloads", CELL),
                             seed)[0]


def _numbers_off(text):
    """Lowered text without the numbers of its private functions, which
    count the lowerings of the process."""
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


def _loss_and_grads(topo, cost, params, feed):
    return jax.value_and_grad(lambda p: jnp.mean(
        topo.apply(p, feed, mode="train")[0][cost.name]))(params)


def test_the_tiny_preset_keeps_both_layer_kinds(cfg):
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert {"mamba", "attention"} == set(kinds)
    cost, topo, params, weights, names = _program(cfg)
    specs = topo.param_specs()
    assert set(specs) == set(names.values())
    for k, v in weights.items():
        assert specs[names[k]].shape == v.shape, k
    blocks = [n for n in topo.nodes if n.layer_type == "recompute"]
    assert len(blocks) == len(kinds)


def test_loss_and_every_gradient_leaf_agree_with_the_reference(cfg):
    cost, topo, params, weights, names = _program(cfg)
    batch = _batch(cfg)
    lengths = sorted(len(row[0]) for row in batch)
    assert lengths[0] < lengths[1] and lengths[1] % cfg["mamba_chunk_size"]
    loss, grads = _loss_and_grads(topo, cost, params,
                                  convert_feed(topo, batch))
    arrays = tuple(jnp.asarray(a) for a in ref.batch_arrays(batch, cfg))
    want, want_grads = jax.value_and_grad(
        lambda w: ref.loss(w, {}, arrays, cfg)[0])(weights)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for k, g in want_grads.items():
        gap = np.linalg.norm(np.asarray(grads[names[k]]) - np.asarray(g)) \
            / np.linalg.norm(np.asarray(g))
        assert gap < 1e-4, (k, gap)


def test_gradients_are_equal_with_and_without_block_recompute(cfg):
    batch = _batch(cfg)
    out = []
    for recompute in (True, False):
        cost, topo, params, _, _ = _program(cfg, recompute=recompute)
        out.append(_loss_and_grads(topo, cost, params,
                                   convert_feed(topo, batch)))
    (loss_a, grads_a), (loss_b, grads_b) = out
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)
    assert set(grads_a) == set(grads_b)
    for k in grads_a:
        np.testing.assert_allclose(
            grads_a[k], grads_b[k], rtol=1e-4,
            atol=1e-5 * float(jnp.abs(grads_b[k]).max()))


def _recompute_everything(monkeypatch):
    """``hybrid_lm``'s blocks as they were before they kept anything."""
    block = L.recompute
    monkeypatch.setattr(L, "recompute",
                        lambda *a, keep=(), **kw: block(*a, **kw))


def test_what_a_block_keeps_changes_no_gradient(cfg, monkeypatch):
    feed = None
    out = []
    for everything in (False, True):
        if everything:
            _recompute_everything(monkeypatch)
        cost, topo, params, _, _ = _program(cfg)
        feed = feed or convert_feed(topo, _batch(cfg))
        out.append(_loss_and_grads(topo, cost, params, feed))
    (loss_a, grads_a), (loss_b, grads_b) = out
    assert float(loss_a) == float(loss_b)
    for k in grads_a:
        np.testing.assert_allclose(
            grads_a[k], grads_b[k], rtol=1e-5,
            atol=1e-6 * float(jnp.abs(grads_b[k]).max()))


def _dots(jaxpr):
    """The output shape of every dot_general, sub-programs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(tuple(eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _dots(sub)
    return out


def _mamba_layers(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count("mamba")


def _in_proj_width(cfg):
    """z, xBC and dt side by side: what ``mamba2``'s first product gives
    a position."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return 2 * inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] \
        + cfg["mamba_n_heads"]


def _kept_values(cfg):
    """What the preset's blocks keep a position: in every layer the MLP's
    first product and the residual stream, in a Mamba-2 layer the mixer's
    first product too."""
    return cfg["num_hidden_layers"] * (
        2 * cfg["shared_intermediate_size"] + cfg["hidden_size"]) \
        + _mamba_layers(cfg) * _in_proj_width(cfg)


def test_backward_makes_neither_kept_product_again(cfg, monkeypatch):
    """By layer: full recompute makes the MLP's first product, the mixer's
    output projection and the mixer's inside twice; a block that keeps the
    first product and the residual after the mixer makes only the mixer's
    inside twice, and of a Mamba-2 mixer's inside not its first product,
    which the block keeps too; no recompute makes nothing twice."""
    batch = _batch(cfg)
    dots = {}
    for how in ("none", "keep", "full"):
        if how == "full":
            _recompute_everything(monkeypatch)
        cost, topo, params, _, _ = _program(cfg, recompute=how != "none")
        feed = convert_feed(topo, batch)
        dots[how] = _dots(jax.make_jaxpr(jax.grad(lambda p: jnp.mean(
            topo.apply(p, feed, mode="train")[0][cost.name])))(params).jaxpr)
    assert len(dots["none"]) < len(dots["keep"]) < len(dots["full"])
    layers = cfg["num_hidden_layers"]
    rows, time = feed["tokens"].data.shape
    first_product = (rows, time, 2 * cfg["shared_intermediate_size"])
    assert [d.count(first_product) for d in dots.values()] \
        == [layers, layers, 2 * layers]
    mamba = _mamba_layers(cfg)
    in_product = (rows, time, _in_proj_width(cfg))
    assert [d.count(in_product) for d in dots.values()] \
        == [mamba, mamba, 2 * mamba]
    # what a layer no longer makes again: the MLP's first product and the
    # mixer's output projection (out_proj, or the attention layer's o),
    # and in a Mamba-2 layer the mixer's first product
    assert len(dots["full"]) - len(dots["keep"]) == 2 * layers + mamba


def test_without_recompute_the_program_is_what_it_was(cfg, monkeypatch):
    """Outside a checkpoint a name is the identity: ``recompute=False``
    lowers to the text it lowered to before the layers named anything."""
    batch = _batch(cfg)

    def lowered():
        cost, topo, params, _, _ = _program(cfg, recompute=False)
        feed = convert_feed(topo, batch)
        return _numbers_off(jax.jit(jax.grad(lambda p: jnp.mean(
            topo.apply(p, feed, mode="train")[0][cost.name]))
        ).lower(params).as_text())

    named = lowered()
    monkeypatch.setattr(decoder, "checkpoint_name", lambda x, name: x)
    assert lowered() == named


def _kept_bytes():
    return observe_metrics.get_registry().snapshot()["gauges"][
        "paddle_tpu_recompute_kept_bytes"]


def test_the_gauge_reads_what_the_traced_step_keeps(cfg):
    """layers x positions x (the MLP's first product + the residual
    stream) x the bytes of a value, and Mamba-2 layers x positions x the
    mixer's first product; 0 without recompute and for a model with no
    recomputed block (the ResNet preset)."""
    from chipbench.models import resnet50 as bench_resnet

    def trace(cost, topo, params, feed):
        jax.eval_shape(jax.grad(lambda p: jnp.mean(
            topo.apply(p, feed, mode="train")[0][cost.name])), params)

    program = _program(cfg)[:3]
    feed = convert_feed(program[1], _batch(cfg))
    rows, time = feed["tokens"].data.shape
    kept = rows * time * _kept_values(cfg) * 4
    trace(*program, feed)
    assert _kept_bytes() == kept
    trace(*_program(cfg, recompute=False)[:3], feed)
    assert _kept_bytes() == 0

    trace(*program, feed)
    assert _kept_bytes() == kept
    resnet = _load("configs", "resnet50")
    cost = bench_resnet.build(resnet)
    topo = Topology(cost)
    trace(cost, topo, topo.init_params(jax.random.PRNGKey(0)),
          {"image": jnp.zeros((2, 3 * resnet["im_size"] ** 2)),
           "label": jnp.zeros((2,), jnp.int32)})
    assert _kept_bytes() == 0


def test_the_tied_embedding_is_one_parameter_with_both_uses_gradients(cfg):
    cost, topo, params, _, _ = _program(cfg)
    feed = convert_feed(topo, _batch(cfg))
    tables = [s for s in topo.param_specs() if s.endswith(".emb")]
    assert tables == ["lm.emb"]
    _, tied = _loss_and_grads(topo, cost, params, feed)

    # the same model with a head of its own: two tables of equal values
    from paddle_tpu.attr import ParamAttr

    L.reset_name_counters()
    tokens, targets, logits, _ = hybrid_lm.from_config(cfg)
    head = L.lm_head(input=logits.inputs[0], vocab=cfg["vocab_size"],
                     param_attr=ParamAttr(name="lm.own_head"),
                     scale=1.0 / cfg["logits_scaling"])
    untied_cost = L.lm_cost(input=head, label=targets)
    untied = Topology(untied_cost)
    both = {**params, "lm.own_head": params["lm.emb"]}
    _, split = _loss_and_grads(untied, untied_cost, both, feed)
    np.testing.assert_allclose(tied["lm.emb"],
                               split["lm.emb"] + split["lm.own_head"],
                               rtol=1e-4, atol=1e-7)
    assert float(jnp.abs(split["lm.emb"]).max()) > 0
    assert float(jnp.abs(split["lm.own_head"]).max()) > 0


def test_logits_over_a_vocabulary_slice_are_the_whole_ones_columns(cfg):
    """A sliced vocabulary is the first rows of the table: what the chip's
    share computes is the whole model's logits at those columns."""
    whole, _ = ref.init_weights(5, cfg)
    kept = cfg["vocab_size"] // 2
    sliced_cfg = dict(cfg, vocab_size=kept)
    batch = [(row[0] % kept, row[1] % kept) for row in _batch(cfg)]
    L.reset_name_counters()
    logits = hybrid_lm.from_config(sliced_cfg)[2]
    names = bench_model.program_names(sliced_cfg)
    topo = Topology(logits)
    params = {names[k]: (v[:kept] if k == "emb" else v)
              for k, v in whole.items()}
    got = topo.apply(params, convert_feed(topo, batch),
                     mode="test")[0][logits.name]
    tokens, _, lengths = ref.batch_arrays(batch, cfg)
    want = ref.logits_of(whole, jnp.asarray(tokens), cfg)
    valid = (np.arange(tokens.shape[1])[None, :] < lengths[:, None])[..., None]
    np.testing.assert_allclose(
        np.where(valid, got.data[:, :tokens.shape[1]], 0),
        np.where(valid, want[..., :kept], 0), atol=2e-5)


def test_nothing_leaks_out_of_the_padded_tail(cfg):
    cost, topo, params, _, _ = _program(cfg)
    feed = convert_feed(topo, _batch(cfg))
    loss, grads = _loss_and_grads(topo, cost, params, feed)
    short = int(jnp.argmin(feed["tokens"].lengths))
    tail = int(feed["tokens"].lengths[short])
    assert tail < feed["tokens"].data.shape[1]
    moved = {k: SequenceBatch(v.data.at[short, tail:].set(7), v.lengths)
             for k, v in feed.items()}
    loss2, grads2 = _loss_and_grads(topo, cost, params, moved)
    assert float(loss2) == float(loss)
    for k in grads:
        np.testing.assert_array_equal(grads2[k], grads[k])


def test_nothing_leaks_back_in_time_through_the_whole_model(cfg):
    L.reset_name_counters()
    logits = hybrid_lm.from_config(cfg)[2]
    names = bench_model.program_names(cfg)
    topo = Topology(logits)
    params = {names[k]: v for k, v in ref.init_weights(6, cfg)[0].items()}
    feed = convert_feed(topo, _batch(cfg))
    at = 17
    out = topo.apply(params, feed, mode="test")[0][logits.name].data
    tokens = feed["tokens"]
    moved = {**feed, "tokens": SequenceBatch(
        tokens.data.at[:, at].set((tokens.data[:, at] + 1)
                                  % cfg["vocab_size"]), tokens.lengths)}
    out2 = topo.apply(params, moved, mode="test")[0][logits.name].data
    np.testing.assert_array_equal(out2[:, :at], out[:, :at])
    assert float(jnp.abs(out2[:, at:] - out[:, at:]).max()) > 1e-6


def test_experts_are_refused():
    with pytest.raises(EnforceError, match="expert"):
        hybrid_lm.from_config({"model_type": "granitemoehybrid",
                               "num_local_experts": 8, "vocab_size": 8,
                               "hidden_size": 8, "layer_types": [],
                               "num_hidden_layers": 0,
                               "rms_norm_eps": 1e-5})


def _histograms():
    return observe_metrics.get_registry().snapshot()["histograms"]


def _count(hist, name):
    return hist.get(name, {"count": 0, "sum": 0.0})


@pytest.mark.parametrize("feed_pipeline", [True, False])
def test_it_trains_through_sgd_train_and_counts_its_tokens(cfg,
                                                           feed_pipeline):
    paddle.init(use_tpu=False, seed=7)
    L.reset_name_counters()
    cost = hybrid_lm.from_config(cfg)[3]
    params = paddle.parameters.create(cost)
    before = {k: np.array(params.get(k)) for k in params.names()}
    trainer = paddle.trainer.SGD(
        cost, params, paddle.optimizer.Momentum(learning_rate=0.01,
                                                momentum=0.9))
    pool = traffic.make_pool(cfg["inputs"], _load("workloads", CELL), 7)
    costs = []

    def handler(event):
        if isinstance(event, paddle.event.EndIteration):
            costs.append(event.cost)

    names = ("paddle_tpu_train_step_tokens",
             "paddle_tpu_train_step_positions")
    start = [_count(_histograms(), n) for n in names]
    trainer.train(lambda: iter(pool), event_handler=handler,
                  feed_pipeline=feed_pipeline)
    end = [_count(_histograms(), n) for n in names]
    assert len(costs) == 3 and all(np.isfinite(costs))
    # a uniform guess over the vocabulary costs log(vocab)
    assert costs[0] == pytest.approx(np.log(cfg["vocab_size"]), rel=0.05)
    moved = [k for k in before
             if not np.array_equal(before[k], np.asarray(params.get(k)))]
    # every matrix moves; a scale whose update is under float32's step at
    # 1.0 may stand still for three steps
    assert {k for k in before if before[k].ndim == 2} <= set(moved)
    assert len(moved) >= 0.8 * len(before)
    rows = [len(r[0]) for r in pool[0]]
    padded = convert_feed(trainer.topology, pool[0])["tokens"].data.shape[1]
    tokens, positions = (e["sum"] - s["sum"] for s, e in zip(start, end))
    assert [e["count"] - s["count"] for s, e in zip(start, end)] == [3, 3]
    assert tokens == 3 * sum(rows)
    assert positions == 3 * len(rows) * padded
    # set as the trainer's step was traced: what its ten blocks keep
    assert _kept_bytes() == len(rows) * padded * _kept_values(cfg) * 4


def test_a_steps_tokens_are_its_widest_sequence_slots():
    lengths = jnp.asarray([5, 3], jnp.int32)
    seq = SequenceBatch(jnp.zeros((2, 8), jnp.int32), lengths)
    assert data_feeder.step_tokens({"tokens": seq, "targets": seq}) == (8, 16)
    narrow = SequenceBatch(jnp.zeros((2, 4), jnp.int32),
                           jnp.asarray([4, 1], jnp.int32))
    assert data_feeder.step_tokens({"a": narrow, "b": seq}) == (8, 16)
    assert data_feeder.step_tokens({"image": jnp.zeros((2, 3))}) == (None,
                                                                     None)


# -- the olmo_hybrid layout ---------------------------------------------------

OLMO_CELL = "olmo-hybrid-7b-seq4096-bs2-train"


@pytest.fixture()
def olmo():
    return _load("configs", "olmo-hybrid-7b")


def _olmo_program(cfg, seed=3, **kw):
    from chipbench.models import olmo_hybrid as olmo_model
    from chipbench.reference import olmo_hybrid as olmo_ref

    L.reset_name_counters()
    cost = hybrid_lm.from_config(cfg, **kw)[3]
    names = olmo_model.program_names(cfg)
    weights, _ = olmo_ref.init_weights(seed, cfg)
    return cost, Topology(cost), {names[k]: v for k, v in weights.items()}


def _olmo_feed(cfg, topo, seed=3):
    return convert_feed(topo, traffic.make_pool(
        cfg["inputs"], _load("workloads", OLMO_CELL), seed)[0])


def test_the_olmo_preset_is_built_from_the_table_of_mixers(olmo):
    kinds = olmo["layer_types"][:olmo["num_hidden_layers"]]
    assert kinds == ["linear_attention"] * 3 + ["full_attention"]
    assert set(hybrid_lm.MIXERS) >= {"mamba", "attention",
                                     "linear_attention", "full_attention"}
    cost, topo, params = _olmo_program(olmo)
    assert set(topo.param_specs()) == set(params)
    types = [n.layer_type for n in topo.nodes]
    assert types.count("recompute") == len(kinds)
    # an untied head: a table of its own beside the embedding's
    assert {"lm.emb", "lm.head.w0"} <= set(params)
    assert params["lm.l3.mixer.q_norm"].shape == (olmo["hidden_size"],)
    assert "lm.l0.mixer.A_log" in params and "lm.l3.mixer.A_log" not in params
    # no multiplier is 1's product: the stream is not scaled anywhere
    assert "slope_intercept" not in types


def test_the_olmo_norms_sit_after_the_branches(olmo):
    """With every norm's scale at 0 each branch adds nothing, whatever
    the mixers and MLPs give: the logits are those of the embedding
    alone."""
    L.reset_name_counters()
    logits = hybrid_lm.from_config(olmo)[2]
    topo = Topology(logits)
    _, _, params = _olmo_program(olmo)
    feed = _olmo_feed(olmo, topo)
    muted = {k: jnp.zeros_like(v) if ".norm1." in k or ".norm2." in k else v
             for k, v in params.items()}
    out = topo.apply(muted, feed, mode="test")[0][logits.name].data
    h = params["lm.emb"][feed["tokens"].data]
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)
                          + olmo["rms_norm_eps"])
    np.testing.assert_allclose(out, h @ params["lm.head.w0"].T, atol=1e-5)
    assert float(jnp.abs(
        topo.apply(params, feed, mode="test")[0][logits.name].data
        - out).max()) > 1e-3


@pytest.mark.parametrize("keep_layers", [None, 0, 3])
def test_in_how_many_layers_a_block_keeps_changes_no_gradient(olmo,
                                                              keep_layers):
    cost, topo, params = _olmo_program(olmo, recompute=False)
    feed = _olmo_feed(olmo, topo)
    want_loss, want = _loss_and_grads(topo, cost, params, feed)
    cost, topo, params = _olmo_program(olmo, keep_layers=keep_layers)
    loss, grads = _loss_and_grads(topo, cost, params, feed)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for k in want:
        np.testing.assert_allclose(
            grads[k], want[k], rtol=1e-4,
            atol=1e-5 * float(jnp.abs(want[k]).max()))
    # the last keep_layers blocks keep the MLP's first product and the
    # residual after the mixer; the gauge counts their bytes
    rows, time = feed["tokens"].data.shape
    layers = olmo["num_hidden_layers"]
    kept = (layers if keep_layers is None else keep_layers) * rows * time \
        * (2 * olmo["intermediate_size"] + olmo["hidden_size"]) * 4
    assert _kept_bytes() == kept
    blocks = [n for n in topo.nodes if n.layer_type == "recompute"]
    assert [n.name for n in blocks] == ["lm.l%d.block" % i
                                        for i in range(layers)]


@pytest.mark.parametrize("change,match", [
    ({"model_type": "llama"}, "model_type"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rotary"),
    ({"linear_num_key_heads": 1}, "key heads"),
    ({"layer_types": ["sliding_attention"] * 4}, "sliding_attention"),
])
def test_what_the_olmo_config_may_not_say(olmo, change, match):
    with pytest.raises(EnforceError, match=match):
        hybrid_lm.from_config({**olmo, **change})


def test_a_kind_without_its_options_is_refused():
    with pytest.raises(EnforceError, match="no mamba options"):
        hybrid_lm.hybrid_lm(vocab=8, hidden=8, layer_types=["mamba"],
                            mlp_size=8)


def test_the_olmo_model_trains_through_sgd_train(olmo):
    paddle.init(use_tpu=False, seed=7)
    L.reset_name_counters()
    cost = hybrid_lm.from_config(olmo, keep_layers=3)[3]
    params = paddle.parameters.create(cost)
    before = {k: np.array(params.get(k)) for k in params.names()}
    trainer = paddle.trainer.SGD(
        cost, params, paddle.optimizer.Momentum(learning_rate=0.01,
                                                momentum=0.9))
    pool = traffic.make_pool(olmo["inputs"], _load("workloads", OLMO_CELL),
                             7)
    costs = []
    names = ("paddle_tpu_train_step_tokens",
             "paddle_tpu_train_step_positions")
    start = [_count(_histograms(), n) for n in names]
    trainer.train(lambda: iter(pool), feed_pipeline=True,
                  event_handler=lambda e: costs.append(e.cost) if isinstance(
                      e, paddle.event.EndIteration) else None)
    end = [_count(_histograms(), n) for n in names]
    assert len(costs) == 3 and all(np.isfinite(costs))
    assert costs[0] == pytest.approx(np.log(olmo["vocab_size"]), rel=0.05)
    moved = [k for k in before
             if not np.array_equal(before[k], np.asarray(params.get(k)))]
    assert {k for k in before if before[k].ndim == 2} <= set(moved)
    rows = [len(r[0]) for r in pool[0]]
    padded = convert_feed(trainer.topology, pool[0])["tokens"].data.shape[1]
    tokens, positions = (e["sum"] - s["sum"] for s, e in zip(start, end))
    assert tokens == 3 * sum(rows)
    assert positions == 3 * len(rows) * padded
    assert _kept_bytes() == 3 * len(rows) * padded * (
        2 * olmo["intermediate_size"] + olmo["hidden_size"]) * 4


# -- what a mixer offers the block round it -----------------------------------

def _no_mixer_offers_a_name(monkeypatch):
    """``MIXERS`` as it was before a kind of mixer named a value for the
    block round it to keep."""
    monkeypatch.setattr(hybrid_lm, "MIXERS", {
        kind: mixer._replace(keeps=())
        for kind, mixer in hybrid_lm.MIXERS.items()})


def _trainers_step(build, cfg, cell, monkeypatch):
    """(The lowered text of ``SGD``'s train step over the preset's first
    batch, as the cell's model builds it; each block's ``keep``, layer by
    layer, nodes by their names.)"""
    keeps = []
    block = L.recompute

    def recording(*a, keep=(), **kw):
        keeps.append([k if isinstance(k, str) else k.name for k in keep])
        return block(*a, keep=keep, **kw)

    paddle.init(use_tpu=False, seed=7)
    L.reset_name_counters()
    with monkeypatch.context() as m:
        m.setattr(L, "recompute", recording)
        cost = build(cfg)
    trainer = paddle.trainer.SGD(
        cost, paddle.parameters.create(cost),
        paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9))
    feed = convert_feed(trainer.topology, traffic.make_pool(
        cfg["inputs"], _load("workloads", cell), 7)[0])
    text = trainer._train_step.lower(
        trainer._trainable, trainer._replica, trainer._static,
        trainer._state, trainer._opt_state, feed, trainer._rng).as_text()
    return _numbers_off(text), keeps


def test_a_model_with_no_mamba_layer_is_built_as_it_was(olmo, monkeypatch):
    """The Olmo preset as its cell builds it: every block's ``keep`` and
    the trainer's lowered step are what they are when no mixer offers a
    name."""
    from chipbench.models import olmo_hybrid as olmo_model

    text, keeps = _trainers_step(olmo_model.build, olmo, OLMO_CELL,
                                 monkeypatch)
    layers = olmo["num_hidden_layers"]
    kept = olmo_model.KEEP_LAYERS
    assert [k[1:] for k in keeps] == [[]] * (layers - kept) \
        + [[decoder.GATED_MLP_PRODUCT]] * kept
    assert [len(k) for k in keeps] == [0] * (layers - kept) + [2] * kept
    _no_mixer_offers_a_name(monkeypatch)
    text_before, keeps_before = _trainers_step(olmo_model.build, olmo,
                                               OLMO_CELL, monkeypatch)
    assert keeps == keeps_before
    assert text == text_before


def test_a_mamba_layer_keeps_its_first_product_by_its_kind(cfg, monkeypatch):
    """Granite's preset as its cell builds it: a Mamba-2 layer's block
    lists the mixer's name after the two every block lists, the attention
    layer's does not, and the trainer's step is another program than with
    no name offered (the guard above can tell)."""
    text, keeps = _trainers_step(bench_model.build, cfg, CELL, monkeypatch)
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    two = [decoder.GATED_MLP_PRODUCT]
    assert [k[1:] for k in keeps] == [
        two + [decoder.MAMBA_IN_PRODUCT] if kind == "mamba" else two
        for kind in kinds]
    _no_mixer_offers_a_name(monkeypatch)
    text_before, keeps_before = _trainers_step(bench_model.build, cfg, CELL,
                                               monkeypatch)
    assert [k[1:] for k in keeps_before] == [two] * len(kinds)
    assert [k[0] for k in keeps] == [k[0] for k in keeps_before]
    assert text != text_before


def test_the_table_of_mixers_says_what_each_kind_offers():
    offered = {kind: mixer.keeps for kind, mixer in hybrid_lm.MIXERS.items()}
    assert offered == {"mamba": (decoder.MAMBA_IN_PRODUCT,),
                       "mamba1": (decoder.MAMBA1_IN_PRODUCT,),
                       "attention": (), "full_attention": (),
                       "sliding_attention": (), "cross_attention": (),
                       "linear_attention": (), "gmu": (), "conv": ()}
    # what a kind hands to later layers, and what it reads of an earlier one
    assert {k: (m.makes, m.reads) for k, m in hybrid_lm.MIXERS.items()
            if m.makes or m.reads} == {
        "mamba1": ("memory", None), "full_attention": ("kv", None),
        "gmu": (None, "memory"), "cross_attention": (None, "kv")}


@pytest.mark.parametrize("which", ["granite", "olmo"])
def test_a_model_without_expert_layers_never_reaches_the_expert_kernels(
        which, cfg, olmo, monkeypatch):
    """Granite's and Olmo's presets as their cells build them: the
    trainer's lowered step is the same text whether an expert layer would
    take `ops/pallas_moe.py`'s kernels or not, so nothing of theirs runs
    the code that chooses (the byte-for-byte check against the parent
    commit is PERF.md's, PR 38)."""
    from chipbench.models import olmo_hybrid as olmo_model
    from paddle_tpu.ops import pallas_moe

    build, preset, cell = {
        "granite": (bench_model.build, cfg, CELL),
        "olmo": (olmo_model.build, olmo, OLMO_CELL)}[which]
    texts = []
    for fits in (False, True):
        monkeypatch.setattr(pallas_moe, "fits", lambda *a, fits=fits: fits)
        texts.append(_trainers_step(build, preset, cell, monkeypatch)[0])
    assert texts[0] == texts[1]
