"""`chipbench/trace.py` on a small recorded trace, every number worked out
by hand; and the tiled LSTM kernel at the published shape (its cell
waits, PERF.md section 7) compiled for a described v5e chip,
so that Mosaic's verdict on b256/h1280/bf16 is guarded at no chip time."""

import os

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(os.path.join(HERE, "recorded_trace.json")),
                        kernel_marks=(trace.PALLAS_TARGET,))


def test_busy_is_a_union_per_device(reduced):
    # chip 0: [0,150) + [200,400) + [500,600); a sum of durations says 520
    assert reduced["per_device_busy_s"] == {
        "/device:TPU:0": pytest.approx(450e-9),
        "/device:TPU:1": pytest.approx(300e-9)}
    assert reduced["busiest"] == "/device:TPU:0"
    assert reduced["busy_s"] == pytest.approx(375e-9)


def test_window_and_idle_share(reduced):
    assert reduced["window_s"] == pytest.approx(700e-9)
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(1.0 - 375.0 / 700.0)


def test_kernel_and_exposed_collective_time(reduced):
    assert reduced["kernel_s"] == {trace.PALLAS_TARGET: pytest.approx(100e-9)}
    assert reduced["kernel_calls"] == {trace.PALLAS_TARGET: 1}
    assert reduced["collective_s"] == pytest.approx(120e-9)
    # [280,400) less the kernel's [280,300)
    assert reduced["collective_exposed_s"] == pytest.approx(100e-9)


def test_breakdown_names_ops_and_gaps(reduced):
    assert reduced["device_ops"][0] == ["fusion.1", pytest.approx(200e-9)]
    assert [n for n, _ in reduced["device_ops"]] == [
        "fusion.1", "all-reduce.1", "fusion.2", "jvp__.2 [pallas]"]
    assert reduced["idle_gaps"] == [
        ["chipbench.reader", pytest.approx(100e-9)],
        ["SGD.train", pytest.approx(50e-9)]]


def test_an_event_is_named_by_its_instruction_not_its_whole_text():
    text = ('%convolution_add_fusion.13 = bf16[256,57,57,64]{0,3,2,1} '
            'fusion(bf16[256,57,57,64]{0,3,2,1} %x), kind=kOutput')
    assert trace.short_name(text) == "convolution_add_fusion.13"
    assert trace.short_name("jit_train_step(7)") == "jit_train_step(7)"
    assert trace.is_collective("%all-reduce-start.3 = f32[8] all-reduce-start()")
    assert not trace.is_collective("%fusion.1 = f32[8] fusion(%all-reduce.1)")


def test_interval_helpers():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert trace.overlap([[0, 3], [5, 7]], [[2, 6]]) == 2
    with pytest.raises(ValueError):
        trace.traced_window({"planes": []})


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_tiled_lstm_compiles_for_v5e_at_the_published_size(one_chip):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu.ops import pallas_kernels as pk

    steps, rows, hidden, dt = 128, 256, 1280, jnp.bfloat16
    assert pk.lstm_mode(rows, hidden, dt) == "tiled"

    def shape(dims, dtype=dt):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def total(gates, mask, w, h0, c0, peep):
        h, _, c_f = pk.lstm_fused(gates, mask, w, h0, c0, peep)
        return jnp.sum(h.astype(jnp.float32)) \
            + jnp.sum(c_f.astype(jnp.float32))

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(jax.value_and_grad(total, argnums=(0, 2, 5))).lower(
            shape((steps, rows, 4 * hidden)), shape((steps, rows), jnp.float32),
            shape((hidden, 4 * hidden)), shape((rows, hidden)),
            shape((rows, hidden)), shape((3 * hidden,), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
