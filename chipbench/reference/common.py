"""What every plain reference shares: float32 at `highest`, the lower
precision a control computes in, and three Momentum steps written out.

Nothing here imports the program. A reference module gives
``init_weights(seed, cfg) -> (weights, state)``, ``batch_arrays(samples,
cfg)`` and ``loss(weights, state, batch, cfg, quant) -> (loss, new_state)``;
this file drives them.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def _to_bf16(x):
    # reduce_precision, not a cast there and back: XLA may drop such a pair
    # of casts (xla_allow_excess_precision), and the rounding with it
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _to_fp8(x, exponent_bits, mantissa_bits, top):
    """Rounded to an 8-bit float (e4m3, largest 240; e5m2, largest 57344)
    under one scale for the tensor (its largest magnitude maps to the
    type's largest), as fp8 training holds a matrix product's operand."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.reduce_precision(x * scale, exponent_bits,
                                    mantissa_bits) / scale


def _e4m3(x):
    return _to_fp8(_to_bf16(x), 4, 3, 240.0)


def _e5m2(x):
    return _to_fp8(x, 5, 2, 57344.0)


@jax.custom_vjp
def _as_fp8(x):
    return _e4m3(x)


_as_fp8.defvjp(lambda x: (_e4m3(x), None), lambda _, g: (_e5m2(g),))


def quantize(x, quant):
    """A value as the precision ``quant`` holds it, be it the operand of a
    matrix product or an activation kept between operations. ``None``:
    float32, the reference. ``"fp8"``: the control, the nearest precision
    below the configurations' bfloat16: float8 e4m3 forward and e5m2 for
    the gradient that flows back, one scale per tensor; sums are still
    taken in float32, as under the bfloat16 policy."""
    if quant is None:
        return x
    if quant == "fp8":
        return _as_fp8(x)
    raise ValueError("unknown precision %r" % (quant,))


def matmul(a, b, quant=None):
    return jnp.matmul(quantize(a, quant), quantize(b, quant),
                      precision=HIGHEST)


def softmax_cost(logits, labels):
    """Mean of -log(softmax(logits)[label] + 1e-8): the classification cost
    over a softmax-activated output, as the v2 layer defines it."""
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(z) / jnp.sum(jnp.exp(z), axis=-1, keepdims=True)
    picked = jnp.take_along_axis(p, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(-jnp.log(picked + 1e-8))


def train3(ref, cfg, seed, batches, lr, mu, quant=None, devices=None):
    """Three plain Momentum steps (v = mu v - lr g; p += v) from the seed's
    weights over ``batches``. Returns host numpy readings: each step's
    loss, the first gradient, the parameters' change and the running
    state's change after the three. With several ``devices`` the rows of a
    batch are spread over them and everything else is held whole on each:
    the same arithmetic over the whole batch, and the float32 activations
    of a four-chip cell's batch fit."""
    weights, state = ref.init_weights(seed, cfg)
    w0 = jax.tree.map(np.asarray, weights)
    s0 = jax.tree.map(np.asarray, state)
    rows = None
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.asarray(devices), ("rows",))
        rows = NamedSharding(mesh, PartitionSpec("rows"))
        whole = NamedSharding(mesh, PartitionSpec())
        weights, state = jax.device_put((weights, state), whole)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, v, s, batch):
        (loss, new_s), g = jax.value_and_grad(
            lambda w_: ref.loss(w_, s, batch, cfg, quant), has_aux=True)(w)
        v = jax.tree.map(lambda v_, g_: mu * v_ - lr * g_, v, g)
        w = jax.tree.map(lambda w_, v_: w_ + v_, w, v)
        return loss, g, w, v, new_s

    vel = jax.tree.map(jnp.zeros_like, weights)
    losses, grad1 = [], None
    for i, batch in enumerate(batches):
        batch = tuple(jnp.asarray(a) if rows is None
                      else jax.device_put(a, rows) for a in batch)
        loss, g, weights, vel, state = step(weights, vel, state, batch)
        losses.append(float(loss))
        if i == 0:
            grad1 = jax.tree.map(np.asarray, g)
        del g
    w3 = jax.tree.map(np.asarray, weights)
    s3 = jax.tree.map(np.asarray, state)
    return {
        "losses": losses,
        "grad1": grad1,
        "delta3": {k: w3[k] - w0[k] for k in w0},
        "state3": {k: s3[k] - s0[k] for k in s0},
    }
