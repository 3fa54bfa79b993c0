"""A decoder-only language model assembled from a list of layer kinds,
each a mixer followed by a feed-forward on one residual stream, with
values that a later layer reads of an earlier one beside the stream. The
mixer kinds are the keys of ``MIXERS``: Mamba-2 state-space layers and
position-free grouped-query attention (the ``granitemoehybrid`` layout
without experts:
IBM Granite 4.0-H, https://huggingface.co/ibm-granite/granite-4.0-h-micro),
Gated DeltaNet linear-attention layers and attention with normalised
queries and keys (``olmo_hybrid``: https://huggingface.co/allenai/Olmo-Hybrid-7B),
SambaY with differential attention (``phi4flash``:
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning,
arXiv:2507.06607): Mamba-1 layers beside window attention, one
full-attention layer, then Gated Memory Units and cross-attention; and
gated short convolutions beside rotary attention with per-head norms of
queries and keys (``lfm2_moe``: https://huggingface.co/LiquidAI/LFM2-8B-A1B);
full attention with partial YaRN rotary positions beside window attention
with plain ones, each kind with its own number of heads and every head
gated (``laguna``: https://huggingface.co/poolside/Laguna-XS.2).
The feed-forward is chosen layer by layer: one gated MLP (``dense``), or
sparse experts of which this chip holds some (``experts``), beside a
shared expert or not.

    h0 = embedding_multiplier * E[ids]
    norm "before":  h += residual_multiplier * mixer_i(N(h), shared)    mixer by layer_types[i]
                    h += residual_multiplier * FFN_i(N(h))
    norm "after":   h += residual_multiplier * N(mixer_i(h, shared))
                    h += residual_multiplier * N(FFN_i(h))
    logits = N(h) W^T / logits_scaling             W = E (tied) or the head's own table
    N = RMSNorm (norm_kind "rms": x / rms(x) * w) or LayerNorm ("layer":
        (x - mean) / sqrt(var + eps) * w + b)
    FFN_i "dense":    (silu(a) * b) W_2, [a, b] = u W_1, width mlp_size
    FFN_i "experts":  s = sigmoid(u W_r) over all experts, float32
                      chosen = top_k(s + expert_bias)     the bias selects and no more
                      w = s[chosen] / (sum s[chosen] + 1e-6) * scaling
                      sum over chosen e held here of w_e * expert_e(u), each a gated MLP
                      [+ shared(u), a gated MLP of shared_width, unweighted]
    conv mixer:       [B, C, x] = u W_in;  (C * conv1d_causal(B * x)) W_out    3 taps, no bias

A layer whose index in the whole model is under ``dense_layers`` has the
dense feed-forward, the others the experts' (all dense where
``dense_layers`` is None). The expert layer (``layer.moe``,
``ops/moe.py``) is told which experts it holds, ``held`` of ``total``
from ``first_held``: it routes over all of them and computes its own
experts' part; what the absent experts would add is left out, as their
chips would compute it. No pair is dropped under any imbalance.

The shared values, by the fourth and fifth fields of ``MIXERS``. A
``mamba1`` layer makes ``memory``, its scan output y before the gate,
[B, T, 2 * hidden]; a ``gmu`` layer reads it: (memory * silu(u W_1)) W_2.
A ``full_attention`` layer makes ``kv``, its keys and values after their
biases side by side, [B, T, 2 * kv_heads * head_dim]; a
``cross_attention`` layer reads it: a query projection of its own over
those keys and values. A reader takes the nearest earlier maker's value,
and a maker hands its value out only where a later layer reads it (so a
model without readers is built as it was). ``sliding_attention`` is
attention over the ``window`` keys that end with the query's own. With
``differential`` in its options attention pairs its heads and subtracts
two softmaxes, softmax(Q1 K1^T) - lambda softmax(Q2 K2^T), lambda
starting at 0.8 - 0.6 exp(-0.3 i) by the layer's index i in the whole
model (``layer_indices``, where ``layer_types`` is a cut of it).

Each layer is one ``layer.recompute`` block: backward keeps the layer's
input and the shared value it reads, and computes its inside again, but
for the values that are dear to make and small to hold. A block that
makes a shared value has two outputs, the stream and the value: the value
is made once, lives across the blocks that read it, and backward adds the
gradients of all its readers (and of its own layer's gate or attention)
into it. What a block keeps besides: the gated MLP's first product,
[B, T, 2 * mlp]: the largest product of a layer, 2.05 GFLOP saved per MB
kept at Granite 4.0-H Micro's widths. The residual stream after the
mixer, [B, T, hidden]: with it kept the mixer's output projection is dead
in the second forward, 4.1 GFLOP per MB. Together layers x positions x
(2 * mlp + hidden) values a step. And what the layer's kind of mixer
offers, the third field of ``MIXERS``: a Mamba-2 mixer's first product
(``MAMBA_IN_PRODUCT``, [B, T, 2 * inner + 2 * groups * state + heads],
before the split into z, xBC and dt; at Granite 4.0-H Micro's widths 8,512
values a position, 139 MB and 0.286 TFLOP a layer at 8,192 positions) and
a Mamba-1 mixer's (``MAMBA1_IN_PRODUCT``, [B, T, 4 * hidden], before the
split into x and z), each as dear per byte as the MLP's product; the
attention, Gated DeltaNet, Gated Memory Unit and short-convolution mixers
offer nothing. An expert block keeps, in the dense product's place, the
sorted rows' first grouped product (``MOE_PRODUCT``, [top_k * B * T,
2 * width]: the whole buffer, of which the groups fill the share of the
experts held). The scans, the convolutions, the norms, the gates, the
router and the sort are made again: they cost little to make.
``keep_layers`` says in how many of the layers, the last ones, a block
keeps anything: a choice by what the chip's memory leaves,
model by model. The last ones, because a step's memory peaks in the
backward of the first layers, when nearly every gradient is alive and
what the later layers kept has been used and freed.
"""

import collections
import math

from paddle_tpu import data_type
from paddle_tpu import layer as L
from paddle_tpu.attr import ParamAttr
from paddle_tpu.layer.decoder import (GATED_MLP_PRODUCT, MAMBA1_IN_PRODUCT,
                                      MAMBA_IN_PRODUCT, MOE_PRODUCT,
                                      lambda_init)
from paddle_tpu.ops import attention as attention_ops
from paddle_tpu.utils.error import enforce

# A layer kind: the mixer's layer; which of hybrid_lm's groups of options
# it takes; the names the mixer gives values that a block round it keeps
# where it keeps at all; the value it hands out to later layers and the
# value it reads from an earlier one (the layer's argument of that name).
Mixer = collections.namedtuple("Mixer", "layer options keeps makes reads",
                               defaults=((), None, None))
# "attention" and "full_attention" are one layer under two model types'
# names for it; so are "mamba" and "mamba1" but for the generation.
MIXERS = {
    "mamba": Mixer(L.mamba2, "mamba", (MAMBA_IN_PRODUCT,)),
    "attention": Mixer(L.gqa_attention, "attention"),
    "full_attention": Mixer(L.gqa_attention, "attention", makes="kv"),
    "linear_attention": Mixer(L.gated_delta_net, "linear_attention"),
    "mamba1": Mixer(L.mamba1, "mamba1", (MAMBA1_IN_PRODUCT,),
                    makes="memory"),
    "sliding_attention": Mixer(L.gqa_attention, "sliding_attention"),
    "cross_attention": Mixer(L.gqa_attention, "attention", reads="kv"),
    "gmu": Mixer(L.gmu, None, reads="memory"),
    "conv": Mixer(L.short_conv, "conv"),
}
NORMS = {"rms": L.rms_norm, "layer": L.layer_norm}


def _scaled(node, factor):
    return node if factor == 1.0 else L.slope_intercept(input=node,
                                                         slope=float(factor))


def _sources(layer_types):
    """{index of a layer that reads a value: index of the layer it reads
    it from, the nearest earlier one that makes it}."""
    made, out = {}, {}
    for i, kind in enumerate(layer_types):
        mixer = MIXERS[kind]
        if mixer.reads is not None:
            enforce(mixer.reads in made, "layer_types[%d] is %r and no "
                    "earlier layer makes the %s it reads", i, kind,
                    mixer.reads)
            out[i] = made[mixer.reads]
        if mixer.makes is not None:
            made[mixer.makes] = i
    return out


def hybrid_lm(vocab, hidden, layer_types, mlp_size, attention=None,
              mamba=None, embedding_multiplier=1.0, residual_multiplier=1.0,
              logits_scaling=1.0, eps=1e-5, initial_std=0.02,
              recompute=True, prefix="lm", linear_attention=None,
              norm="before", tie_head=True, keep_layers=None, mamba1=None,
              sliding_attention=None, norm_kind="rms", layer_indices=None,
              conv=None, experts=None, dense_layers=None):
    """Builds the model over two ``integer_value_sequence`` slots, tokens
    and targets. The options of each kind of mixer in ``layer_types``:
    ``attention`` (and ``sliding_attention``): heads, kv_heads, head_dim,
    and scale, block, qk_norm, bias, window, rope_theta or rope (rotary's
    keywords: theta, dims, inverse, factor), gate ("head"), differential
    (True: each layer's starting lambda follows its index);
    ``mamba``: heads, head_dim, state, conv_width, groups, chunk;
    ``mamba1``: state, conv_width, expand, dt_rank, chunk;
    ``linear_attention``: heads, key_dim, value_dim, conv_width,
    neg_eigval, chunk; ``conv``: conv_width. ``experts``: the options of
    ``layer.moe`` (experts_total, experts_held, first_held, top_k, width,
    normalize, scaling, use_bias, shared_width), the feed-forward of every
    layer whose index in the whole model is ``dense_layers`` or more
    (None: every layer has the gated MLP of ``mlp_size``). ``norm``:
    "before" each branch or "after" it; ``norm_kind``: "rms" or "layer" (LayerNorm with a bias), the final
    norm too. ``layer_indices``: the index each layer has in the whole
    model, where ``layer_types`` is a cut of it (its own position by
    default). ``tie_head=False`` gives the head a table of its own,
    ``<prefix>.head.w0``. ``recompute=False`` keeps every layer's inside
    for backward; otherwise a layer keeps its input and, in the last
    ``keep_layers`` layers (all of them by default), its MLP's first
    product, the residual stream after its mixer and what ``MIXERS`` says
    its kind of mixer offers (an expert layer its first grouped product
    in the MLP's place). Returns (tokens, targets, logits, cost)."""
    enforce(norm in ("before", "after"), "norm is %r, not before or after",
            norm)
    enforce(norm_kind in NORMS, "norm_kind is %r, not one of %s", norm_kind,
            ", ".join(sorted(NORMS)))
    make_norm = NORMS[norm_kind]
    options = {"attention": attention, "mamba": mamba,
               "linear_attention": linear_attention, "mamba1": mamba1,
               "sliding_attention": sliding_attention, "conv": conv,
               None: {}}
    tokens = L.data(name="tokens",
                    type=data_type.integer_value_sequence(vocab))
    targets = L.data(name="targets",
                     type=data_type.integer_value_sequence(vocab))
    table = ParamAttr(name=prefix + ".emb", initial_std=initial_std)
    matrix = ParamAttr(initial_std=initial_std)
    h = _scaled(L.embedding(input=tokens, size=hidden, param_attr=table,
                            name=prefix + ".embed"), embedding_multiplier)

    def branch(h, make, norm_name):
        """h + residual_multiplier * the branch, normalised on the side
        ``norm`` says."""
        if norm == "before":
            out = make(make_norm(input=h, eps=eps, name=norm_name))
        else:
            out = make_norm(input=make(h), eps=eps, name=norm_name)
        return L.addto(input=[h, _scaled(out, residual_multiplier)])

    if keep_layers is None:
        keep_layers = len(layer_types)
    if layer_indices is None:
        layer_indices = range(len(layer_types))
    for i, kind in enumerate(layer_types):
        enforce(kind in MIXERS, "layer_types[%d] is %r, not one of %s", i,
                kind, ", ".join(sorted(MIXERS)))
    sources = _sources(layer_types)
    shared = {}   # index of the layer that made it: the node handed out
    for i, kind in enumerate(layer_types):
        mixer = MIXERS[kind]
        enforce(options[mixer.options] is not None,
                "layer_types[%d] is %r and no %s options are given", i, kind,
                mixer.options)
        given = dict(options[mixer.options], eps=eps,
                     initial_std=initial_std)
        if given.get("differential"):
            given["differential"] = lambda_init(layer_indices[i])
        if i in sources:
            given[mixer.reads] = shared[sources[i]]
        hands_out = i in sources.values()
        if hands_out:
            given["hand_out"] = True
        name = "%s.l%d" % (prefix, i)
        entry = h
        made = []

        def mix(x):
            out = mixer.layer(input=x, name=name + ".mixer", **given)
            if hands_out:
                out, handed = out
                made.append(handed)
            return out

        after_mixer = branch(h, mix, name + ".norm1")
        sparse = dense_layers is not None \
            and layer_indices[i] >= dense_layers
        if sparse:
            enforce(experts is not None, "layer_types[%d] is past the %d "
                    "dense layers and no experts options are given", i,
                    dense_layers)
            h = branch(after_mixer, lambda x: L.moe(
                input=x, initial_std=initial_std, name=name + ".moe",
                **experts), name + ".norm2")
        else:
            h = branch(after_mixer, lambda x: L.gated_mlp(
                input=x, size=mlp_size, param_attr=matrix,
                name=name + ".mlp"), name + ".norm2")
        block = L.recompute(
            [h] + made if made else h,
            inputs=[entry] + ([shared[sources[i]]] if i in sources else []),
            enabled=recompute, name=name + ".block",
            keep=[after_mixer, MOE_PRODUCT if sparse else GATED_MLP_PRODUCT,
                  *mixer.keeps]
            if i >= len(layer_types) - keep_layers else [])
        if made:
            h, shared[i] = block
        else:
            h = block
    h = make_norm(input=h, eps=eps, name=prefix + ".final_norm")
    logits = L.lm_head(input=h, vocab=vocab,
                       param_attr=table if tie_head else matrix,
                       scale=1.0 / float(logits_scaling),
                       name=prefix + ".head")
    cost = L.lm_cost(input=logits, label=targets, name=prefix + ".cost")
    return tokens, targets, logits, cost


def _granite_options(cfg):
    enforce(not cfg.get("num_local_experts"),
            "hybrid_lm reads no granitemoehybrid expert keys yet "
            "(num_local_experts %r): layer.moe routes by sigmoid scores, "
            "beside a shared expert or not (shared_width), and has no "
            "softmax router, which granitemoehybrid's experts take",
            cfg.get("num_local_experts"))
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return dict(
        mlp_size=cfg["shared_intermediate_size"], eps=cfg["rms_norm_eps"],
        attention={"heads": heads, "kv_heads": cfg["num_key_value_heads"],
                   "head_dim": hidden // heads,
                   "scale": cfg["attention_multiplier"]},
        mamba={"heads": cfg["mamba_n_heads"], "head_dim": cfg["mamba_d_head"],
               "state": cfg["mamba_d_state"],
               "conv_width": cfg["mamba_d_conv"],
               "groups": cfg["mamba_n_groups"],
               "chunk": cfg["mamba_chunk_size"]},
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"])


def _olmo_hybrid_options(cfg):
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    enforce(cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"],
            "gated_delta_net has as many key heads as value heads (%r, %r)",
            cfg["linear_num_key_heads"], cfg["linear_num_value_heads"])
    enforce(cfg["rope_parameters"]["rope_theta"] is None,
            "hybrid_lm has no rotary positions (rope_theta %r)",
            cfg["rope_parameters"]["rope_theta"])
    return dict(
        mlp_size=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        attention={"heads": heads, "kv_heads": cfg["num_key_value_heads"],
                   "head_dim": hidden // heads, "qk_norm": True},
        linear_attention={"heads": cfg["linear_num_value_heads"],
                          "key_dim": cfg["linear_key_head_dim"],
                          "value_dim": cfg["linear_value_head_dim"],
                          "conv_width": cfg["linear_conv_kernel_dim"],
                          "neg_eigval": cfg["linear_allow_neg_eigval"]},
        norm="after", tie_head=cfg["tie_word_embeddings"])


def _phi4flash_options(cfg):
    """SambaY with differential attention. The config has no key for the
    Mamba-1 mixer's sizes: they are ``layer.mamba1``'s defaults, which
    are ``mamba_ssm``'s (state 16, 4 taps, expansion 2, dt rank hidden /
    16 rounded up), unless the configuration gives them under the
    ``mamba_*`` keys here."""
    enforce(not cfg.get("mlp_bias") and not cfg.get("lm_head_bias"),
            "hybrid_lm has no bias on the MLP or the head (mlp_bias %r, "
            "lm_head_bias %r)", cfg.get("mlp_bias"), cfg.get("lm_head_bias"))
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    attention = {"heads": heads, "kv_heads": cfg["num_key_value_heads"],
                 "head_dim": hidden // heads, "differential": True,
                 "bias": True}
    return dict(
        mlp_size=cfg["intermediate_size"], eps=cfg["layer_norm_eps"],
        attention=attention,
        sliding_attention=dict(attention, window=cfg["sliding_window"]),
        mamba1={option: cfg[key] for option, key in (
            ("state", "mamba_d_state"), ("conv_width", "mamba_d_conv"),
            ("expand", "mamba_expand"), ("dt_rank", "mamba_dt_rank"))
            if key in cfg},
        norm_kind="layer", tie_head=cfg["tie_word_embeddings"])


def _lfm2_moe_options(cfg):
    """LFM2's sparse models: gated short convolutions beside rotary
    attention whose queries and keys are RMS-normalised head by head;
    ``num_dense_layers`` leading layers with a gated MLP, sparse experts
    after. ``num_experts`` is what this chip holds of the
    ``num_experts_published`` the router scores (the same, where the
    configuration holds them all), from ``first_expert`` on. The scores
    are sigmoids (the config has no key for it)."""
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    enforce(not cfg.get("conv_bias"),
            "short_conv has no bias (conv_bias %r)", cfg.get("conv_bias"))
    return dict(
        mlp_size=cfg["intermediate_size"], eps=cfg["norm_eps"],
        attention={"heads": heads, "kv_heads": cfg["num_key_value_heads"],
                   "head_dim": hidden // heads, "qk_norm": "head",
                   "rope_theta": cfg["rope_theta"]},
        conv={"conv_width": cfg["conv_L_cache"]},
        dense_layers=cfg["num_dense_layers"],
        experts={"experts_total": cfg.get("num_experts_published",
                                          cfg["num_experts"]),
                 "experts_held": cfg["num_experts"],
                 "first_held": cfg.get("first_expert", 0),
                 "top_k": cfg["num_experts_per_tok"],
                 "width": cfg["moe_intermediate_size"],
                 "normalize": cfg["norm_topk_prob"],
                 "scaling": cfg["routed_scaling_factor"],
                 "use_bias": cfg["use_expert_bias"]},
        tie_head=cfg.get("tie_word_embeddings", True))


def _rope(params, head_dim):
    """``rotary``'s keywords from one layer kind's entry of
    ``rope_parameters`` (the ``transformers`` layout): the first
    ``partial_rotary_factor`` share of a head turns, by theta's plain
    frequencies (``rope_type`` default) or YaRN's, whose
    ``attention_factor`` multiplies cos and sin."""
    kind = params.get("rope_type", "default")
    enforce(kind in ("default", "yarn"), "hybrid_lm turns by plain or YaRN "
            "rotary positions, not rope_type %r", kind)
    dims = int(head_dim * params.get("partial_rotary_factor", 1.0))
    rope = {"theta": float(params["rope_theta"])}
    if dims != head_dim:
        rope["dims"] = dims
    if kind == "yarn":
        rope["inverse"] = attention_ops.yarn_inverse_frequencies(
            dims, rope["theta"], params["factor"],
            params["original_max_position_embeddings"],
            params.get("beta_fast", 32), params.get("beta_slow", 1))
        rope["factor"] = float(params.get(
            "attention_factor", 0.1 * math.log(params["factor"]) + 1.0))
    return rope


def _laguna_options(cfg):
    """Laguna: full attention layers beside window layers (``sliding_window``
    keys), each kind with its own number of query heads
    (``num_attention_heads_per_layer``, one number a kind) and its own
    rotary positions (``rope_parameters`` by kind), every head's output
    times its own sigmoid gate (``gating``); the leading ``dense`` entries
    of ``mlp_layer_types`` have a gated MLP, the rest sparse experts, sigmoid
    top-k normalised and scaled by ``moe_routed_scaling_factor``, beside a
    shared expert; an untied head. ``num_experts`` is what this chip holds
    of ``num_experts_published``, from ``first_expert`` on, as for
    ``lfm2_moe``. The config has no key for the scores' function or their
    normalisation: sigmoids, normalised."""
    enforce(cfg["gating"] is True, "hybrid_lm reads gating true as a gate "
            "a head, not %r", cfg["gating"])
    enforce(not cfg.get("attention_bias")
            and not cfg.get("moe_apply_router_weight_on_input"),
            "hybrid_lm has no attention bias and weighs an expert's output, "
            "not its input (attention_bias %r, "
            "moe_apply_router_weight_on_input %r)",
            cfg.get("attention_bias"),
            cfg.get("moe_apply_router_weight_on_input"))
    kinds = cfg["mlp_layer_types"]
    dense = next((i for i, k in enumerate(kinds) if k != "dense"),
                 len(kinds))
    enforce(all(k == "sparse" for k in kinds[dense:]), "mlp_layer_types "
            "lists dense layers after the first sparse one: %s", kinds)
    heads = {}
    for kind, count in zip(cfg["layer_types"],
                           cfg["num_attention_heads_per_layer"]):
        enforce(heads.setdefault(kind, count) == count, "%s layers have %d "
                "and %d query heads", kind, heads[kind], count)
    head_dim = cfg["head_dim"]

    def attention(kind):
        return {"heads": heads[kind], "kv_heads": cfg["num_key_value_heads"],
                "head_dim": head_dim, "gate": "head",
                "rope": _rope(cfg["rope_parameters"][kind], head_dim)}

    return dict(
        mlp_size=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        attention=attention("full_attention"),
        sliding_attention=dict(attention("sliding_attention"),
                               window=cfg["sliding_window"]),
        dense_layers=dense,
        experts={"experts_total": cfg.get("num_experts_published",
                                          cfg["num_experts"]),
                 "experts_held": cfg["num_experts"],
                 "first_held": cfg.get("first_expert", 0),
                 "top_k": cfg["num_experts_per_tok"],
                 "width": cfg["moe_intermediate_size"],
                 "scaling": cfg["moe_routed_scaling_factor"],
                 "use_bias": False,
                 "shared_width": cfg["shared_expert_intermediate_size"]},
        tie_head=cfg["tie_word_embeddings"])


# config.json's model_type: the options hybrid_lm takes from its keys
MODEL_TYPES = {"granitemoehybrid": _granite_options,
               "olmo_hybrid": _olmo_hybrid_options,
               "phi4flash": _phi4flash_options,
               "lfm2_moe": _lfm2_moe_options,
               "laguna": _laguna_options}


def from_config(cfg, recompute=True, prefix="lm", keep_layers=None):
    """The model of a config.json whose ``model_type`` is a key of
    ``MODEL_TYPES``, over the first ``vocab_size`` rows of the
    vocabulary: the first ``num_hidden_layers`` of its ``layer_types``
    or, where the configuration lists them under ``kept_layers``, the
    ``num_hidden_layers`` layers of those indices."""
    enforce(cfg["model_type"] in MODEL_TYPES,
            "hybrid_lm builds no model_type %r, only %s", cfg["model_type"],
            ", ".join(sorted(MODEL_TYPES)))
    kept = list(cfg.get("kept_layers", range(cfg["num_hidden_layers"])))
    enforce(len(kept) == cfg["num_hidden_layers"],
            "kept_layers lists %d layers and num_hidden_layers is %d",
            len(kept), cfg["num_hidden_layers"])
    return hybrid_lm(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layer_types=[cfg["layer_types"][i] for i in kept],
        layer_indices=kept, recompute=recompute, prefix=prefix,
        keep_layers=keep_layers, **MODEL_TYPES[cfg["model_type"]](cfg))
