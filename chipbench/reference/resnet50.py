"""Plain reference of the `resnet50` configuration: bottleneck ResNet
(He et al. 2015, Table 1) as the v1 `model_zoo/resnet/resnet.py` lays it
out, written from the layer equations in float32 at `highest`.

Departures from the paper that the configuration states, kept here too:
the 3x3 stride-2 max pool after the stem rounds its output size up (the
v1 pooling layer's `ceil_mode`), so a 224 input gives 57x57 and the stages
run at 57, 29, 15, 8; stride 2 sits on the first 1x1 of a stage's first
block; batch norm uses the biased variance, eps 1e-5, and moves its
running statistics by 0.9 old + 0.1 batch. The input row is a flat
C*H*W vector (channel-major), as the v2 dense_vector slot carries it.
Each block is rematerialised in the backward pass (`jax.checkpoint`): the
same arithmetic, and float32 activations of 256 rows then fit one chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
WIDTHS = (64, 128, 256, 512)
BN_EPS = 1e-5
BN_KEEP = 0.9
# the last batch norm of a block starts at this scale (Goyal et al. 2017
# start it at 0): with 1 everywhere a 50-layer batch-norm net at random
# weights has exploding, ill-conditioned gradients, and bfloat16 and fp8
# both read tens of percent off float32, so nothing could tell them apart
LAST_BN_SCALE = 0.0625


def conv_names(cfg):
    """[(name, (kh, kw, c_in, c_out), stride, pad)] of every convolution, in
    forward order; each is followed by a batch norm `<name>.bn`."""
    out = [("stem", (7, 7, 3, 64), 2, 3)]
    c_in = 64
    for stage, (n, ch) in enumerate(zip(STAGES[cfg["depth"]], WIDTHS)):
        for i in range(n):
            stride = 2 if (i == 0 and stage > 0) else 1
            p = "s%db%d" % (stage + 2, i)
            if c_in != ch * 4 or stride != 1:
                out.append((p + ".sc", (1, 1, c_in, ch * 4), stride, 0))
            out.append((p + ".a", (1, 1, c_in, ch), stride, 0))
            out.append((p + ".b", (3, 3, ch, ch), 1, 1))
            out.append((p + ".c", (1, 1, ch, ch * 4), 1, 0))
            c_in = ch * 4
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, depth, classes):
    cfg = {"depth": depth}
    weights, state = {}, {}
    convs = conv_names(cfg)
    keys = jax.random.split(key, len(convs) + 1)
    for k, (name, shape, _, _) in zip(keys, convs):
        fan_in = shape[0] * shape[1] * shape[2]
        weights[name + ".w"] = jax.random.normal(k, shape, jnp.float32) \
            * np.float32(np.sqrt(2.0 / fan_in))
        c = shape[3]
        weights[name + ".bn.scale"] = jnp.full(
            (c,), LAST_BN_SCALE if name.endswith(".c") else 1.0, jnp.float32)
        weights[name + ".bn.bias"] = jnp.zeros((c,), jnp.float32)
        state[name + ".bn.mean"] = jnp.zeros((c,), jnp.float32)
        state[name + ".bn.var"] = jnp.ones((c,), jnp.float32)
    weights["out.w"] = jax.random.normal(
        keys[-1], (2048, classes), jnp.float32) * np.float32(2048 ** -0.5)
    weights["out.b"] = jnp.zeros((classes,), jnp.float32)
    return weights, state


def init_weights(seed, cfg):
    return _init(common.seed_key(seed), cfg["depth"], cfg["num_classes"])


def batch_arrays(samples, cfg):
    """(images [B, 3*H*W] float32, labels [B] int32) from per-sample
    tuples."""
    return (np.stack([np.asarray(s[0], np.float32) for s in samples]),
            np.asarray([s[1] for s in samples], np.int32))


def _conv_bn(x, w, s, new_s, name, stride, pad, relu, quant):
    y = jax.lax.conv_general_dilated(
        common.quantize(x, quant), common.quantize(w[name + ".w"], quant),
        window_strides=(stride, stride), padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=common.HIGHEST)
    y = common.quantize(y, quant)
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = (y - mean) * jax.lax.rsqrt(var + BN_EPS) * w[name + ".bn.scale"] \
        + w[name + ".bn.bias"]
    new_s[name + ".bn.mean"] = BN_KEEP * s[name + ".bn.mean"] \
        + (1.0 - BN_KEEP) * mean
    new_s[name + ".bn.var"] = BN_KEEP * s[name + ".bn.var"] \
        + (1.0 - BN_KEEP) * var
    y = common.quantize(y, quant)
    return jnp.maximum(y, 0.0) if relu else y


def _max_pool_3x3_s2_ceil(x):
    size = x.shape[1]
    out = -(-(size + 2 - 3) // 2) + 1
    high = max((out - 1) * 2 + 3 - size - 1, 1)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, high), (1, high), (0, 0)))


def loss(weights, state, batch, cfg, quant=None):
    """(mean cost, new running state) of one batch in train mode."""
    images, labels = batch
    size = cfg["im_size"]
    x = images.reshape(-1, 3, size, size).transpose(0, 2, 3, 1)
    new_s = {}
    x = _conv_bn(x, weights, state, new_s, "stem", 2, 3, True, quant)
    x = _max_pool_3x3_s2_ceil(x)
    c_in = 64
    for stage, (n, ch) in enumerate(zip(STAGES[cfg["depth"]], WIDTHS)):
        for i in range(n):
            stride = 2 if (i == 0 and stage > 0) else 1
            p = "s%db%d" % (stage + 2, i)
            project = c_in != ch * 4 or stride != 1
            names = [p + t for t in (".a", ".b", ".c")] \
                + ([p + ".sc"] if project else [])
            keys = [k for n_ in names for k in
                    (n_ + ".w", n_ + ".bn.scale", n_ + ".bn.bias")]
            skeys = [k for n_ in names for k in
                     (n_ + ".bn.mean", n_ + ".bn.var")]

            def block(x, w, s, p=p, stride=stride, project=project):
                out_s = {}
                sc = _conv_bn(x, w, s, out_s, p + ".sc", stride, 0, False,
                              quant) if project else x
                t = _conv_bn(x, w, s, out_s, p + ".a", stride, 0, True, quant)
                t = _conv_bn(t, w, s, out_s, p + ".b", 1, 1, True, quant)
                t = _conv_bn(t, w, s, out_s, p + ".c", 1, 0, False, quant)
                return common.quantize(jnp.maximum(t + sc, 0.0), quant), out_s

            x, out_s = jax.checkpoint(block)(
                x, {k: weights[k] for k in keys}, {k: state[k] for k in skeys})
            new_s.update(out_s)
            c_in = ch * 4
    x = jnp.mean(x, axis=(1, 2))
    logits = common.matmul(x, weights["out.w"], quant) + weights["out.b"]
    return common.softmax_cost(common.quantize(logits, quant), labels), new_s
