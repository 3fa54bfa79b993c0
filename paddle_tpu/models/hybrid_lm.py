"""A decoder-only language model assembled from a list of layer kinds,
each a mixer followed by a gated MLP on one residual stream. The kinds
are the keys of ``MIXERS``: Mamba-2 state-space layers and position-free
grouped-query attention (the ``granitemoehybrid`` layout without experts:
IBM Granite 4.0-H, https://huggingface.co/ibm-granite/granite-4.0-h-micro),
Gated DeltaNet linear-attention layers and attention with normalised
queries and keys (``olmo_hybrid``: https://huggingface.co/allenai/Olmo-Hybrid-7B).

    h0 = embedding_multiplier * E[ids]
    norm "before":  h += residual_multiplier * mixer(RMSNorm(h))     mixer by layer_types[i]
                    h += residual_multiplier * MLP(RMSNorm(h))
    norm "after":   h += residual_multiplier * RMSNorm(mixer(h))
                    h += residual_multiplier * RMSNorm(MLP(h))
    logits = RMSNorm(h) W^T / logits_scaling          W = E (tied) or the head's own table

Each layer is one ``layer.recompute`` block: backward keeps the layer's
input and computes its inside again, but for the values that are dear to
make and small to hold. The gated MLP's first product, [B, T, 2 * mlp]:
the largest product of a layer, 2.05 GFLOP saved per MB kept at Granite
4.0-H Micro's widths. The residual stream after the mixer,
[B, T, hidden]: with it kept the mixer's output projection is dead in the
second forward, 4.1 GFLOP per MB. Together layers x positions x
(2 * mlp + hidden) values a step. And what the layer's kind of mixer
offers, the third field of ``MIXERS``: a Mamba-2 mixer's first product
(``MAMBA_IN_PRODUCT``, [B, T, 2 * inner + 2 * groups * state + heads],
before the split into z, xBC and dt), as dear per byte as the MLP's
product (2.05 GFLOP per MB; at Granite 4.0-H Micro's widths 8,512 values
a position, 139 MB and 0.286 TFLOP a layer at 8,192 positions); the
attention and Gated DeltaNet mixers offer nothing. The scan, the
convolution, the norms and the gates are made again: they cost little to
make. ``keep_layers`` says in how many of the layers, the last ones, a
block keeps anything: a choice by what the chip's memory leaves, model by
model. The last ones, because a step's memory peaks in the backward of the
first layers, when nearly every gradient is alive and what the later
layers kept has been used and freed.
"""

from paddle_tpu import data_type
from paddle_tpu import layer as L
from paddle_tpu.attr import ParamAttr
from paddle_tpu.layer.decoder import GATED_MLP_PRODUCT, MAMBA_IN_PRODUCT
from paddle_tpu.utils.error import enforce

# layer kind: (the mixer's layer, which of hybrid_lm's groups of options
# it takes, the names the mixer gives values that a block round it keeps
# where it keeps at all). "attention" and "full_attention" are one layer
# under the two model types' names for it.
MIXERS = {
    "mamba": (L.mamba2, "mamba", (MAMBA_IN_PRODUCT,)),
    "attention": (L.gqa_attention, "attention", ()),
    "full_attention": (L.gqa_attention, "attention", ()),
    "linear_attention": (L.gated_delta_net, "linear_attention", ()),
}


def _scaled(node, factor):
    return node if factor == 1.0 else L.slope_intercept(input=node,
                                                         slope=float(factor))


def hybrid_lm(vocab, hidden, layer_types, mlp_size, attention=None,
              mamba=None, embedding_multiplier=1.0, residual_multiplier=1.0,
              logits_scaling=1.0, eps=1e-5, initial_std=0.02,
              recompute=True, prefix="lm", linear_attention=None,
              norm="before", tie_head=True, keep_layers=None):
    """Builds the model over two ``integer_value_sequence`` slots, tokens
    and targets. The options of each kind of mixer in ``layer_types``:
    ``attention``: heads, kv_heads, head_dim, and scale, block, qk_norm;
    ``mamba``: heads, head_dim, state, conv_width, groups, chunk;
    ``linear_attention``: heads, key_dim, value_dim, conv_width,
    neg_eigval, chunk. ``norm``: "before" each branch or "after" it.
    ``tie_head=False`` gives the head a table of its own, ``<prefix>.head.w0``.
    ``recompute=False`` keeps every layer's inside for backward; otherwise
    a layer keeps its input and, in the last ``keep_layers`` layers (all
    of them by default), its MLP's first product, the residual stream
    after its mixer and what ``MIXERS`` says its kind of mixer offers.
    Returns (tokens, targets, logits, cost)."""
    enforce(norm in ("before", "after"), "norm is %r, not before or after",
            norm)
    options = {"attention": attention, "mamba": mamba,
               "linear_attention": linear_attention}
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
            out = make(L.rms_norm(input=h, eps=eps, name=norm_name))
        else:
            out = L.rms_norm(input=make(h), eps=eps, name=norm_name)
        return L.addto(input=[h, _scaled(out, residual_multiplier)])

    if keep_layers is None:
        keep_layers = len(layer_types)
    for i, kind in enumerate(layer_types):
        enforce(kind in MIXERS, "layer_types[%d] is %r, not one of %s", i,
                kind, ", ".join(sorted(MIXERS)))
        mixer, group, mixer_keeps = MIXERS[kind]
        enforce(options[group] is not None,
                "layer_types[%d] is %r and no %s options are given", i, kind,
                group)
        name = "%s.l%d" % (prefix, i)
        entry = h
        after_mixer = branch(h, lambda x: mixer(
            input=x, eps=eps, initial_std=initial_std, name=name + ".mixer",
            **options[group]), name + ".norm1")
        h = branch(after_mixer, lambda x: L.gated_mlp(
            input=x, size=mlp_size, param_attr=matrix, name=name + ".mlp"),
            name + ".norm2")
        h = L.recompute(
            h, inputs=[entry], enabled=recompute, name=name + ".block",
            keep=[after_mixer, GATED_MLP_PRODUCT, *mixer_keeps]
            if i >= len(layer_types) - keep_layers else [])
    h = L.rms_norm(input=h, eps=eps, name=prefix + ".final_norm")
    logits = L.lm_head(input=h, vocab=vocab,
                       param_attr=table if tie_head else matrix,
                       scale=1.0 / float(logits_scaling),
                       name=prefix + ".head")
    cost = L.lm_cost(input=logits, label=targets, name=prefix + ".cost")
    return tokens, targets, logits, cost


def _granite_options(cfg):
    enforce(not cfg.get("num_local_experts"),
            "hybrid_lm has no expert layer (num_local_experts %r)",
            cfg.get("num_local_experts"))
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return dict(
        mlp_size=cfg["shared_intermediate_size"],
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
        mlp_size=cfg["intermediate_size"],
        attention={"heads": heads, "kv_heads": cfg["num_key_value_heads"],
                   "head_dim": hidden // heads, "qk_norm": True},
        linear_attention={"heads": cfg["linear_num_value_heads"],
                          "key_dim": cfg["linear_key_head_dim"],
                          "value_dim": cfg["linear_value_head_dim"],
                          "conv_width": cfg["linear_conv_kernel_dim"],
                          "neg_eigval": cfg["linear_allow_neg_eigval"]},
        norm="after", tie_head=cfg["tie_word_embeddings"])


# config.json's model_type: the options hybrid_lm takes from its keys
MODEL_TYPES = {"granitemoehybrid": _granite_options,
               "olmo_hybrid": _olmo_hybrid_options}


def from_config(cfg, recompute=True, prefix="lm", keep_layers=None):
    """The model of a config.json whose ``model_type`` is a key of
    ``MODEL_TYPES``: the first ``num_hidden_layers`` of its
    ``layer_types`` over the first ``vocab_size`` rows of the
    vocabulary."""
    enforce(cfg["model_type"] in MODEL_TYPES,
            "hybrid_lm builds no model_type %r, only %s", cfg["model_type"],
            ", ".join(sorted(MODEL_TYPES)))
    return hybrid_lm(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        eps=cfg["rms_norm_eps"], recompute=recompute, prefix=prefix,
        keep_layers=keep_layers, **MODEL_TYPES[cfg["model_type"]](cfg))
