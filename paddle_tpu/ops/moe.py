"""A sparse expert layer's four pieces, for a chip that holds some of the
experts: routing over all of them, a dispatch that groups the (token,
choice) pairs by held expert, the experts' gated MLPs as grouped matrix
products, and the weighted combine.

    s = sigmoid(x W_r)                        [N, E] float32, E all experts
    chosen = top_k(s + bias)                  the bias selects and no more
    w = s[chosen] / (sum s[chosen] + 1e-6) * scaling
    out_n = sum over chosen e held here of w_ne * expert_e(x_n)

The chip holds experts ``first_held .. first_held + held - 1``. A pair
whose expert lies elsewhere adds nothing here: in a deployment that
expert's chip computes it and the exchange brings it back; on one chip
the layer runs without the exchange and the absent experts' part is left
out. **No pair is dropped**: the buffer of sorted rows has ``k * N`` rows,
which every routing fits, the pairs of held experts first (grouped by
expert) and the others after them. The grouped products
(``jax.lax.ragged_dot``; on the TPU one Mosaic call whose grid is the
tiles the groups fill) compute the rows inside the groups only; what the
rows after them hold is never read (:func:`gather_rows` and
:func:`combine` select, they do not multiply).

Gradients are autodiff's, through the grouped products to the experts'
weights and the rows, and through ``w`` to the router; the choice and the
bias carry none. The two gathers are permutations, so each one's backward
is the other's gather (``custom_vjp``) and no scatter-add runs.

Two forms of the gather, the experts and the combine
(:func:`experts_form`). On the TPU, where the widths and the positions
tile, the fused one: ``ops/pallas_moe.py``'s kernels, which read, write
and multiply only the rows the groups fill. Everywhere else the plain one
above, which is also the kernels' oracle. The router and the dispatch are
the same in both.
"""

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.dtype import upcast_f32
from paddle_tpu.ops import pallas_moe

NORM_EPS = 1e-6   # in the sum of a token's chosen scores


def route(x, w_router, bias, k, scaling=1.0, normalize=True):
    """(chosen [N, k] int32, weights [N, k] float32) of rows x [N, d]:
    sigmoid scores over all of ``w_router``'s experts in float32, the
    ``k`` largest of score + ``bias`` [E] (or of the score, with no
    bias), weighted by their own scores, which ``normalize`` divides by
    their sum."""
    with jax.named_scope("paddle_tpu.moe_router"):
        wide = upcast_f32(x).dtype
        scores = jax.nn.sigmoid(jnp.matmul(x, w_router,
                                           preferred_element_type=wide))
        select = scores if bias is None else \
            scores + jax.lax.stop_gradient(bias.astype(wide))
        _, chosen = jax.lax.top_k(jax.lax.stop_gradient(select), k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if normalize:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + NORM_EPS)
        return chosen.astype(jnp.int32), weights * scaling


def dispatch(chosen, valid, first_held, held):
    """Sorts the k * N (token, choice) pairs by held expert. ``chosen``
    [N, k]; ``valid`` [N] bool, False where a position is padding (its
    pairs go nowhere). Returns (order [k * N]: the pair at each sorted
    row, pairs of held experts first and grouped by expert; place
    [k * N]: each pair's sorted row; sizes [held] int32: the rows of each
    held expert; here [N, k] bool: whether a pair's expert is held)."""
    with jax.named_scope("paddle_tpu.moe_dispatch"):
        local = chosen - first_held
        here = (local >= 0) & (local < held) & valid[:, None]
        key = jnp.where(here, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        place = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
        return order, place, sizes, here


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gather_rows(x, order, place, k):
    """[k * N, d]: the row of x [N, d] behind each sorted pair (pair p is
    token p // k)."""
    return x[order // k]


def _gather_rows_fwd(x, order, place, k):
    return x[order // k], (place,)


def _gather_rows_bwd(k, residual, g):
    (place,) = residual
    # a token's k pairs lie at place[token * k + c]: their gradients' sum
    pairs = g[place].reshape(-1, k, g.shape[-1])
    return (jnp.sum(pairs, axis=1, dtype=upcast_f32(g).dtype).astype(g.dtype),
            None, None)


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def _unsort(y, order, place):
    """[k * N, d] by pair, from rows in sorted order."""
    return y[place]


_unsort.defvjp(lambda y, order, place: (y[place], (order,)),
               lambda residual, g: (g[residual[0]], None, None))


def _grouped(rows, weights, sizes):
    """rows [M, a] times weights [groups, a, b], each row by its group's
    matrix. bfloat16 operands have no higher precision to ask for, and
    XLA's TPU kernel refuses the request ("highest" set process-wide)."""
    precision = jax.lax.Precision.DEFAULT \
        if rows.dtype == jnp.bfloat16 else None
    return jax.lax.ragged_dot(rows, weights, sizes, precision=precision)


def experts(rows, sizes, w_in, w_out, kept=lambda product: product):
    """The held experts' gated MLPs over the sorted rows [k * N, d]:
    (silu(a) * b) W_out_e with [a, b] = row W_in_e, e the row's group;
    ``w_in`` [held, d, 2 * width] (gate then up), ``w_out`` [held, width,
    d], ``sizes`` [held] rows a group. Rows after the groups' sum are
    not computed and hold no value. ``kept`` names the first product for
    a recomputed block's policy."""
    with jax.named_scope("paddle_tpu.moe_experts"):
        product = kept(_grouped(rows, w_in, sizes))
        a, b = jnp.split(product, 2, axis=-1)
        return _grouped(jax.nn.silu(a) * b, w_out, sizes)


def combine(y, order, place, here, weights):
    """[N, d]: each token's sum over its pairs held here of weight *
    expert output; ``y`` [k * N, d] in sorted order. A pair not held
    adds zero whatever its row holds."""
    with jax.named_scope("paddle_tpu.moe_combine"):
        n, k = here.shape
        pairs = _unsort(y, order, place).reshape(n, k, y.shape[-1])
        pairs = jnp.where(here[..., None], pairs, 0)
        return jnp.einsum("nkd,nk->nd", upcast_f32(pairs),
                          weights).astype(y.dtype)


def experts_form(d, width, tokens, k):
    """"fused" where :func:`moe` over ``tokens`` rows routed ``k`` times runs
    ``ops/pallas_moe.py``'s kernels, "plain" where it runs the gathers,
    ``ragged_dot`` and the combine of this module: the backend and the
    shapes decide (``pallas_moe.fits``), nothing else."""
    return "fused" if pallas_moe.fits(d, width, tokens, k) else "plain"


def moe(x, valid, w_router, bias, w_in, w_out, k, first_held, scaling=1.0,
        normalize=True, kept=lambda product: product):
    """The layer over rows x [N, d]: (out [N, d], rows computed here
    (int32 scalar), the busiest held expert's rows (int32 scalar), the
    rows the grouped products' row tiles cover (int32 scalar; None in the
    plain form, whose products' tiling is XLA's)), in the form
    :func:`experts_form` chooses."""
    held = w_in.shape[0]
    chosen, weights = route(x, w_router, bias, k, scaling, normalize)
    order, place, sizes, here = dispatch(chosen, valid, first_held, held)
    total = jnp.sum(sizes)
    if experts_form(x.shape[1], w_out.shape[1], x.shape[0], k) == "fused":
        with jax.named_scope("paddle_tpu.moe_experts"):
            out, visited = pallas_moe.experts(x, order, place, sizes,
                                              weights, w_in, w_out, k, kept)
        return out, total, jnp.max(sizes), visited
    with jax.named_scope("paddle_tpu.moe_dispatch"):
        used = jnp.arange(order.shape[0]) < total
        rows = jnp.where(used[:, None], gather_rows(x, order, place, k), 0)
    y = experts(rows, sizes, w_in, w_out, kept)
    return combine(y, order, place, here, weights), total, jnp.max(sizes), \
        None
