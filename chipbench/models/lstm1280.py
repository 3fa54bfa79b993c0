"""The `lstm1280` configuration as the program builds it: the flagship
text classifier of `__graft_entry__._flagship` (embedding, two
`simple_lstm`, max over time, softmax fc) under its classification cost,
and where each of the reference's weights goes in it."""


def build(cfg):
    import __graft_entry__ as graft

    _, _, _, cost = graft._flagship(
        dict_size=cfg["dict_size"], emb=cfg["emb_size"],
        hidden=cfg["hidden_size"], classes=cfg["num_classes"])
    return cost


def program_names(cfg):
    """{reference name: program parameter name}; the layouts agree (gate
    order input, forget, candidate, output; 7H bias)."""
    return {
        "emb": "flag_emb.w0",
        "l1.proj": "flag_lstm1_transform.w0",
        "l1.rec": "flag_lstm1.w0",
        "l1.bias": "flag_lstm1.wbias",
        "l2.proj": "flag_lstm2_transform.w0",
        "l2.rec": "flag_lstm2.w0",
        "l2.bias": "flag_lstm2.wbias",
        "out.w": "flag_out.w0",
        "out.b": "flag_out.wbias",
    }
