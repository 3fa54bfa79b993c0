"""Unit tests for paddle_tpu.utils (flags/stat/error/registry) and core
(place/ddim) — mirrors the granularity of paddle/utils tests and
paddle/platform/*_test.cc in the reference."""

import pytest

from paddle_tpu.utils import flags
from paddle_tpu.utils.error import EnforceError, enforce, layer_scope
from paddle_tpu.utils.registry import Registry
from paddle_tpu.utils.stat import StatSet
from paddle_tpu.core.ddim import DDim, make_ddim, flatten_to_2d
from paddle_tpu.core.place import CPUPlace, TPUPlace, default_place


def test_flags_define_get_set():
    flags.define_flag("test_only_flag", 42, "a test flag")
    assert flags.get_flag("test_only_flag") == 42
    flags.set_flag("test_only_flag", 7)
    assert flags.get_flag("test_only_flag") == 7
    flags.reset_flag("test_only_flag")
    assert flags.get_flag("test_only_flag") == 42
    with pytest.raises(flags.FlagError):
        flags.get_flag("no_such_flag")


def test_flag_type_coercion():
    flags.define_flag("test_bool_flag", True)
    flags.set_flag("test_bool_flag", "false")
    assert flags.get_flag("test_bool_flag") is False
    flags.set_flag("test_bool_flag", "1")
    assert flags.get_flag("test_bool_flag") is True


def test_enforce():
    enforce(True, "fine")
    with pytest.raises(EnforceError, match="boom 3"):
        enforce(False, "boom %d", 3)


def test_layer_scope_annotates_errors():
    with pytest.raises(EnforceError, match="fc1"):
        with layer_scope("fc1"):
            enforce(False, "shape mismatch")
    with pytest.raises(ValueError, match="conv2"):
        with layer_scope("net"):
            with layer_scope("conv2"):
                raise ValueError("bad kernel")


def test_registry():
    reg = Registry("widget")

    @reg.register("a", aliases=("alpha",))
    class A:
        pass

    assert reg.get("a") is A
    assert reg.get("alpha") is A
    assert "a" in reg
    with pytest.raises(EnforceError):
        reg.register("a", A)
    with pytest.raises(EnforceError):
        reg.get("missing")


def test_statset():
    from paddle_tpu.observe.spans import SpanTracer

    stats = StatSet("test")
    tracer = SpanTracer("t", stats=stats)
    with tracer.span("op"):
        pass
    with tracer.span("op"):
        pass
    info = stats.get("op")
    assert info.count == 2
    assert info.total >= 0
    d = stats.as_dict()
    assert d["op"]["count"] == 2


def test_ddim():
    d = make_ddim(2, 3, 4)
    assert d.rank == 3
    assert d.product() == 24
    assert d.slice(1, 3) == (3, 4)
    assert d.with_dim(0, 5) == (5, 3, 4)
    assert flatten_to_2d(d, 1) == (2, 12)
    assert flatten_to_2d(d, 2) == (6, 4)
    assert make_ddim([1, 2]) == DDim((1, 2))


def test_places():
    cpu = CPUPlace()
    assert cpu.jax_device().platform == "cpu"
    assert CPUPlace(0) == CPUPlace(0)
    assert CPUPlace(0) != TPUPlace(0)
    assert default_place() is not None


def test_convert_feed_declaration_order():
    """Default feeding must follow data-layer declaration order, not
    alphabetical (regression: ('word','label') got swapped)."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu import layer as L, data_type as dtp
    from paddle_tpu.topology import Topology, convert_feed

    w = L.data(name="zz_first", type=dtp.dense_vector(2))
    lab = L.data(name="aa_second", type=dtp.integer_value(3))
    cost = L.classification_cost(input=L.fc(input=w, size=3), label=lab)
    topo = Topology(cost)
    batch = [(np.ones(2, np.float32), 1), (np.zeros(2, np.float32), 2)]
    feed = convert_feed(topo, batch)
    np.testing.assert_array_equal(np.asarray(feed["aa_second"]), [1, 2])
    np.testing.assert_array_equal(np.asarray(feed["zz_first"]).shape, (2, 2))


def test_layer_error_context_names_offending_layer():
    """CustomStackTrace parity: a failing layer is named in the exception."""
    import numpy as np
    import pytest

    from paddle_tpu import layer as L, data_type as dt
    from paddle_tpu.topology import Topology

    x = L.data(name="ec_x", type=dt.dense_vector(4))
    h = L.fc(input=x, size=4, name="ec_fc")

    def boom(params, values, ctx):
        raise ValueError("kernel exploded")

    from paddle_tpu.layer.base import make_node

    bad = make_node("custom", boom, [h], name="ec_bad", size=4)
    topo = Topology(bad)
    import jax

    params = topo.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError) as ei:
        topo.apply(params, {"ec_x": np.zeros((2, 4), np.float32)})
    # python >= 3.11 attaches a PEP 678 note; 3.10 appends to args
    context = "".join(getattr(ei.value, "__notes__", [])) \
        + " ".join(str(a) for a in ei.value.args)
    assert "ec_bad" in context


def test_trap_fpe_flag_roundtrip():
    from paddle_tpu.utils import flags as fl

    original = fl.get_flag("trap_fpe")
    try:
        fl.set_flag("trap_fpe", True)
        assert fl.get_flag("trap_fpe") is True
    finally:
        fl.set_flag("trap_fpe", original)
