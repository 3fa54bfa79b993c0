"""A decoder-only language model assembled from a list of layer kinds:
Mamba-2 state-space layers and position-free grouped-query attention
layers, each followed by a gated MLP, pre-normalised with RMSNorm, on a
tied embedding (the ``granitemoehybrid`` layout without experts: IBM
Granite 4.0-H, https://huggingface.co/ibm-granite/granite-4.0-h-micro).

    h0 = embedding_multiplier * E[ids]
    h += residual_multiplier * mixer(RMSNorm(h))       mixer by layer_types[i]
    h += residual_multiplier * MLP(RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling

Each layer is one ``layer.recompute`` block: backward keeps the layer's
input and computes its inside again, but for two values that are dear to
make and small to hold. The gated MLP's first product, [B, T, 2 * mlp]:
the largest product of a layer, 2.05 GFLOP saved per MB kept at Granite
4.0-H Micro's widths. The residual stream after the mixer,
[B, T, hidden]: with it kept the mixer's output projection is dead in the
second forward, 4.1 GFLOP per MB. Together layers x positions x
(2 * mlp + hidden) values a step. The mixer's input projection is as dear
per byte as the MLP's product, and is made again for want of room (half
as many bytes again); so are the scan, the norms and the gates, which
cost little to make.
"""

from paddle_tpu import data_type
from paddle_tpu import layer as L
from paddle_tpu.attr import ParamAttr
from paddle_tpu.layer.decoder import GATED_MLP_PRODUCT
from paddle_tpu.utils.error import enforce


def hybrid_lm(vocab, hidden, layer_types, mlp_size, attention, mamba,
              embedding_multiplier=1.0, residual_multiplier=1.0,
              logits_scaling=1.0, eps=1e-5, initial_std=0.02,
              recompute=True, prefix="lm"):
    """Builds the model over two ``integer_value_sequence`` slots, tokens
    and targets. ``attention``: heads, kv_heads, head_dim, scale (and
    block); ``mamba``: heads, head_dim, state, conv_width, groups, chunk.
    ``recompute=False`` keeps every layer's inside for backward; otherwise
    a layer keeps its input, its MLP's first product and the residual
    stream after its mixer. Returns (tokens, targets, logits, cost)."""
    tokens = L.data(name="tokens",
                    type=data_type.integer_value_sequence(vocab))
    targets = L.data(name="targets",
                     type=data_type.integer_value_sequence(vocab))
    table = ParamAttr(name=prefix + ".emb", initial_std=initial_std)
    matrix = ParamAttr(initial_std=initial_std)
    h = L.slope_intercept(
        input=L.embedding(input=tokens, size=hidden, param_attr=table,
                          name=prefix + ".embed"),
        slope=float(embedding_multiplier))

    def residual(h, branch):
        return L.addto(input=[h, L.slope_intercept(
            input=branch, slope=float(residual_multiplier))])

    for i, kind in enumerate(layer_types):
        enforce(kind in ("mamba", "attention"),
                "layer_types[%d] is %r, not mamba or attention", i, kind)
        name = "%s.l%d" % (prefix, i)
        entry = h
        normed = L.rms_norm(input=h, eps=eps, name=name + ".norm1")
        if kind == "mamba":
            mixed = L.mamba2(input=normed, eps=eps, initial_std=initial_std,
                             name=name + ".mixer", **mamba)
        else:
            mixed = L.gqa_attention(input=normed, initial_std=initial_std,
                                    name=name + ".mixer", **attention)
        after_mixer = residual(h, mixed)
        h = residual(after_mixer, L.gated_mlp(
            input=L.rms_norm(input=after_mixer, eps=eps,
                             name=name + ".norm2"),
            size=mlp_size, param_attr=matrix, name=name + ".mlp"))
        h = L.recompute(h, inputs=[entry],
                        keep=[after_mixer, GATED_MLP_PRODUCT],
                        enabled=recompute, name=name + ".block")
    h = L.rms_norm(input=h, eps=eps, name=prefix + ".final_norm")
    logits = L.lm_head(input=h, vocab=vocab, param_attr=table,
                       scale=1.0 / float(logits_scaling),
                       name=prefix + ".head")
    cost = L.lm_cost(input=logits, label=targets, name=prefix + ".cost")
    return tokens, targets, logits, cost


def from_config(cfg, recompute=True, prefix="lm"):
    """The model of a ``granitemoehybrid`` config.json without experts:
    the first ``num_hidden_layers`` of its ``layer_types`` over the first
    ``vocab_size`` rows of the vocabulary."""
    enforce(not cfg.get("num_local_experts"),
            "hybrid_lm has no expert layer (num_local_experts %r)",
            cfg.get("num_local_experts"))
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return hybrid_lm(
        vocab=cfg["vocab_size"], hidden=hidden,
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
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
        logits_scaling=cfg["logits_scaling"], eps=cfg["rms_norm_eps"],
        recompute=recompute, prefix=prefix)
