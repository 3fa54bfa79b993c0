"""Plain reference of the `lstm1280` configuration: embedding, two
`simple_lstm` layers (a bias-free projection to 4H, then the v1 LSTM cell
with peephole connections), a max over time, a softmax classifier. Written
from the layer equations in float32 at `highest`, one `lax.scan` per layer.

The cell (v1 `LstmLayer`, gate order input, forget, candidate, output; the
7H bias holds the 4H gate bias and the three peephole vectors):
    z = x_t + h W
    i = sigmoid(z_i + c * p_i)     f = sigmoid(z_f + c * p_f)
    c' = f * c + i * tanh(z_g)     o = sigmoid(z_o + c' * p_o)
    h' = o * tanh(c')
State stays where a row's sequence has ended, and ended positions count as
nothing in the max over time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _init(key, vocab, emb, hidden, classes):
    k = jax.random.split(key, 9)
    h4 = 4 * hidden

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) \
            * np.float32(fan_in ** -0.5)

    weights = {
        "emb": normal(k[0], (vocab, emb), emb),
        "l1.proj": normal(k[1], (emb, h4), emb),
        "l1.rec": normal(k[2], (hidden, h4), hidden),
        "l1.bias": 0.1 * jax.random.normal(k[3], (7 * hidden,), jnp.float32),
        "l2.proj": normal(k[4], (hidden, h4), hidden),
        "l2.rec": normal(k[5], (hidden, h4), hidden),
        "l2.bias": 0.1 * jax.random.normal(k[6], (7 * hidden,), jnp.float32),
        "out.w": normal(k[7], (hidden, classes), hidden),
        "out.b": jnp.zeros((classes,), jnp.float32),
    }
    return weights, {}


def init_weights(seed, cfg):
    return _init(common.seed_key(seed), cfg["dict_size"], cfg["emb_size"],
                 cfg["hidden_size"], cfg["num_classes"])


def batch_arrays(samples, cfg):
    """(tokens [B, T] int32 zero-padded to the longest row, lengths [B],
    labels [B]) from per-sample (token list, label) tuples."""
    lengths = np.asarray([len(s[0]) for s in samples], np.int32)
    tokens = np.zeros((len(samples), int(lengths.max())), np.int32)
    for i, s in enumerate(samples):
        tokens[i, : lengths[i]] = s[0]
    return tokens, lengths, np.asarray([s[1] for s in samples], np.int32)


def _lstm(x_btd, mask_bt, w_proj, w_rec, bias, quant):
    hidden = w_rec.shape[0]
    gates = common.quantize(
        common.matmul(x_btd, w_proj, quant) + bias[: 4 * hidden], quant)
    p_i, p_f, p_o = jnp.split(bias[4 * hidden:], 3)
    w_rec_q = common.quantize(w_rec, quant)

    def cell(carry, xs):
        h, c = carry
        g_t, m_t = xs
        z = g_t + jnp.matmul(common.quantize(h, quant), w_rec_q,
                             precision=common.HIGHEST)
        z_i, z_f, z_g, z_o = jnp.split(z, 4, axis=-1)
        i = jax.nn.sigmoid(z_i + c * p_i)
        f = jax.nn.sigmoid(z_f + c * p_f)
        c_new = f * c + i * jnp.tanh(z_g)
        o = jax.nn.sigmoid(z_o + c_new * p_o)
        h_new = common.quantize(o * jnp.tanh(c_new), quant)
        m = m_t[:, None]
        h, c = jnp.where(m, h_new, h), jnp.where(m, c_new, c)
        return (h, c), h

    zeros = jnp.zeros((x_btd.shape[0], hidden), jnp.float32)
    _, h_tm = jax.lax.scan(cell, (zeros, zeros),
                           (jnp.swapaxes(gates, 0, 1),
                            jnp.swapaxes(mask_bt, 0, 1)))
    return jnp.swapaxes(h_tm, 0, 1) * mask_bt[..., None]


def loss(weights, state, batch, cfg, quant=None):
    """(mean cost, {}) of one batch."""
    tokens, lengths, labels = batch
    mask = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]
    x = common.quantize(weights["emb"], quant)[tokens]
    x = _lstm(x, mask, weights["l1.proj"], weights["l1.rec"],
              weights["l1.bias"], quant)
    x = _lstm(x, mask, weights["l2.proj"], weights["l2.rec"],
              weights["l2.bias"], quant)
    pooled = jnp.max(jnp.where(mask[..., None], x, -jnp.inf), axis=1)
    logits = common.matmul(pooled, weights["out.w"], quant) + weights["out.b"]
    return common.softmax_cost(common.quantize(logits, quant), labels), {}
