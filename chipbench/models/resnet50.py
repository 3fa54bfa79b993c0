"""The `resnet50` configuration as the program builds it: the v2 layer
graph of `paddle_tpu.models.vision.resnet` under a classification cost,
and where each of the reference's weights goes in it."""

from chipbench.reference import resnet50 as ref


def build(cfg):
    from paddle_tpu import data_type, layer
    from paddle_tpu.graph import reset_name_counters
    from paddle_tpu.models import vision

    reset_name_counters()
    out = vision.resnet(depth=cfg["depth"], num_classes=cfg["num_classes"],
                        im_size=cfg["im_size"])
    label = layer.data(name="label",
                       type=data_type.integer_value(cfg["num_classes"]))
    return layer.classification_cost(input=out, label=label)


def program_names(cfg):
    """{reference name: program parameter name}, weights and running
    state alike. Both sides keep a convolution as [kh, kw, c_in, c_out]."""
    names = {"out.w": "res_out.w0", "out.b": "res_out.wbias"}
    for name, _, _, _ in ref.conv_names(cfg):
        if name == "stem":
            layer = "res_stem"
        else:
            block, part = name.split(".")
            stage, index = block[1:].split("b")
            layer = "res%s_%s_%s" % (stage, index, part)
        names[name + ".w"] = layer + "_conv.w0"
        names[name + ".bn.scale"] = layer + "_bn.w0"
        names[name + ".bn.bias"] = layer + "_bn.wbias"
        names[name + ".bn.mean"] = layer + "_bn.moving_mean"
        names[name + ".bn.var"] = layer + "_bn.moving_var"
    return names
