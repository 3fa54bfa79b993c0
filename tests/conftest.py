"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so sharding/collective paths are
exercised without TPU hardware (chip_smoke.py is the check on the chip
itself). Must set XLA flags before jax imports.
"""

import os

# Set the platform BEFORE anything imports jax: the whole suite runs on
# the CPU backend, eight virtual devices wide.
os.environ["JAX_PLATFORMS"] = "cpu"
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
os.environ.setdefault("PADDLE_TPU_LOG_LEVEL", "WARNING")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest

# Dynamic analysis gates (docs/analyze.md): the autouse thread-leak
# gate and the max_retraces compile-budget fixture apply to the WHOLE
# tier-1 suite. Imported into this namespace (rather than listed in
# pytest_plugins) so registration works from a non-rootdir conftest.
from paddle_tpu.analyze.pytest_plugin import (  # noqa: F401
    _max_retraces_fixture,
    _thread_leak_gate,
    _tree_analysis_fixture,
)
from paddle_tpu.analyze.pytest_plugin import (
    pytest_configure as _analyze_configure,
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: subprocess-heavy tests excluded from the tier-1 run "
        "(-m 'not slow'); run them with -m slow")
    _analyze_configure(config)


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(1234)


@pytest.fixture(autouse=True)
def _flag_guard():
    """Snapshot/restore the global flag registry around every test — e.g.
    the benchmark harness sets the bf16 mixed-precision policy globally,
    which must not leak into other tests' gradient-check tolerances."""
    from paddle_tpu.utils import flags

    snap = flags.all_flags()
    yield
    for name, value in snap.items():
        flags.set_flag(name, value, create=True)


@pytest.fixture
def rng():
    import jax

    return jax.random.PRNGKey(0)
