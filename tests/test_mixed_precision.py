"""Mixed-precision policy (core/dtype.py compute_dtype): bfloat16 forward
compute with float32 master params — the TPU replacement for the reference's
single compiled `real` type (CMakeLists.txt WITH_DOUBLE) and round-1's
blanket bf16x3 matmul precision."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import data_type as dt
from paddle_tpu import layer as L
from paddle_tpu import optimizer as opt
from paddle_tpu.core import dtype as dtype_mod
from paddle_tpu.graph import reset_name_counters
from paddle_tpu.topology import Topology
from paddle_tpu.utils import flags


@pytest.fixture(autouse=True)
def _reset_policy():
    yield
    flags.set_flag("compute_dtype", "")


def _toy_cnn():
    reset_name_counters()
    img = L.data(name="image", type=dt.dense_vector(3 * 8 * 8))
    img.out_img_shape = (3, 8, 8)
    t = L.img_conv(input=img, filter_size=3, num_filters=8, padding=1,
                   act=None, bias_attr=False, name="mp_conv")
    t = L.batch_norm(input=t, name="mp_bn")
    t = L.fc(input=t, size=4, act=None, name="mp_fc")
    label = L.data(name="label", type=dt.integer_value(4))
    return L.classification_cost(input=t, label=label)


def test_forward_runs_bf16_params_stay_f32():
    cost = _toy_cnn()
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    feed = {"image": jnp.asarray(rng.randn(4, 192), jnp.float32),
            "label": jnp.asarray(rng.randint(0, 4, 4), jnp.int32)}

    dtype_mod.set_mixed_precision("bfloat16")
    values, state_updates = topo.apply_all(params, feed, mode="train")
    # conv output computed in bf16; cost upcast to f32; BN moving stats f32
    assert values["mp_conv"].dtype == jnp.bfloat16
    assert values[cost.name].dtype == jnp.float32
    for name, val in state_updates.items():
        assert val.dtype == jnp.float32, name
    # master params untouched
    assert all(v.dtype == jnp.float32 for v in params.values()
               if jnp.issubdtype(v.dtype, jnp.floating))


def test_grads_return_f32_and_track_f32_reference():
    cost = _toy_cnn()
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(1))
    rng = np.random.RandomState(1)
    feed = {"image": jnp.asarray(rng.randn(8, 192), jnp.float32),
            "label": jnp.asarray(rng.randint(0, 4, 8), jnp.int32)}

    def loss_fn(p):
        values, _ = topo.apply(p, feed, mode="test")
        return jnp.mean(values[cost.name])

    g32 = jax.grad(loss_fn)(params)
    dtype_mod.set_mixed_precision("bfloat16")
    gbf = jax.grad(loss_fn)(params)
    for name in g32:
        assert gbf[name].dtype == jnp.float32, name
        denom = np.maximum(np.abs(np.asarray(g32[name])), 5e-2)
        rel = np.abs(np.asarray(gbf[name]) - np.asarray(g32[name])) / denom
        assert rel.max() < 0.25, (name, rel.max())  # bf16 has ~8 mantissa bits


def test_training_step_converges_under_policy():
    dtype_mod.set_mixed_precision("bfloat16")
    cost = _toy_cnn()
    topo = Topology(cost)
    params = topo.init_params(jax.random.PRNGKey(2))
    optimizer = opt.Momentum(learning_rate=0.05, momentum=0.9)
    state = optimizer.init_state(params)
    rng = np.random.RandomState(2)
    x = rng.randn(16, 192).astype(np.float32)
    y = (x[:, :48].sum(axis=1) > 0).astype(np.int32)
    feed = {"image": jnp.asarray(x), "label": jnp.asarray(y)}

    @jax.jit
    def step(p, s):
        def loss_fn(pp):
            values, _ = topo.apply(pp, feed, mode="test")
            return jnp.mean(values[cost.name])

        loss, grads = jax.value_and_grad(loss_fn)(p)
        p2, s2 = optimizer.step(p, grads, s)
        return loss, p2, s2

    losses = []
    for _ in range(30):
        loss, params, state = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]


def test_bf16_replica_activation_guard():
    """The read replica activates only when the compute dtype differs
    from the f32 masters — an f32 compute override must NOT alias the
    donated master buffers into a second donated argument."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.parameters import Parameters
    from paddle_tpu.topology import Topology
    from paddle_tpu.utils import flags

    def build_trainer():
        from paddle_tpu.graph import reset_name_counters

        reset_name_counters()
        x = paddle.layer.data(name="x",
                              type=paddle.data_type.dense_vector(8))
        out = paddle.layer.fc(input=x, size=4,
                              act=paddle.activation.Softmax())
        lbl = paddle.layer.data(name="label",
                                type=paddle.data_type.integer_value(4))
        cost = paddle.layer.classification_cost(input=out, label=lbl)
        params = Parameters.create(Topology(cost))
        return paddle.trainer.SGD(
            cost, params, paddle.optimizer.Momentum(learning_rate=0.1,
                                                    momentum=0.9))

    old = flags.get_flag("compute_dtype")
    try:
        flags.set_flag("compute_dtype", "bfloat16")
        tr = build_trainer()
        assert tr._replica is not None
        flags.set_flag("compute_dtype", "float32")
        tr32 = build_trainer()
        assert tr32._replica is None
        # and the f32 path still trains (no duplicate-donation crash)
        rng = np.random.RandomState(0)
        batch = [(rng.randn(8).astype(np.float32), int(rng.randint(4)))
                 for _ in range(4)]
        tr32.train(lambda: iter([batch]), num_passes=1)
    finally:
        flags.set_flag("compute_dtype", old or "")


@pytest.mark.parametrize("hooked", [False, True])
def test_set_up_makes_the_replica_once_unless_a_hook_moved_the_masters(
        hooked, monkeypatch):
    """Without an update hook the replica made beside the masters is the
    one the first step reads (a second one, made while the first was
    alive, was 2 bytes a parameter of peak memory); with a pruning hook it
    is made again from the pruned masters."""
    import paddle_tpu as paddle
    from paddle_tpu import trainer as trainer_mod
    from paddle_tpu.attr import ParamAttr
    from paddle_tpu.parameters import Parameters

    made = []
    make = trainer_mod._make_replica
    monkeypatch.setattr(trainer_mod, "_make_replica",
                        lambda t: made.append(1) or make(t))
    flags.set_flag("compute_dtype", "bfloat16")
    reset_name_counters()
    x = paddle.layer.data(name="x", type=paddle.data_type.dense_vector(8))
    hook = opt.StaticPruningHook(0.5)
    out = paddle.layer.fc(
        input=x, size=4, act=paddle.activation.Softmax(), name="fc",
        param_attr=ParamAttr(update_hooks=[hook]) if hooked else None)
    lbl = paddle.layer.data(name="label",
                            type=paddle.data_type.integer_value(4))
    cost = paddle.layer.classification_cost(input=out, label=lbl)
    tr = paddle.trainer.SGD(
        cost, Parameters.create(Topology(cost)),
        paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9))
    assert len(made) == (2 if hooked else 1)
    for name, master in tr._trainable.items():
        np.testing.assert_array_equal(
            np.asarray(tr._replica[name], np.float32),
            np.asarray(master.astype(jnp.bfloat16), np.float32))
    if hooked:
        assert float(jnp.mean(tr._replica["fc.w0"] == 0)) == 0.5
