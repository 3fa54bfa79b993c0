"""What the persistent compile cache keeps (utils/compile_cache.py, "What
it keeps"): every program a process compiles, so that a second process on
the same machine reads set-up's small programs and compiles none; and
`Parameters.get_shape`, which set-up calls once a leaf, moves no bytes."""

import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parameters import Parameters
from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIR_FLAG = "jax_compilation_cache_dir"
KEEP_FLAG = "jax_persistent_cache_min_compile_time_secs"
KEEP_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"

# a process's set-up and its first step, at a size that compiles in seconds
CHILD = """
import json
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import activation as A, data_type as dt, layer as L
from paddle_tpu import optimizer as opt
from paddle_tpu.observe import metrics as observe_metrics
from paddle_tpu.parameters import Parameters
from paddle_tpu.utils import compile_cache

paddle.init(use_tpu=False)
x = L.data(name="x", type=dt.dense_vector(8))
lab = L.data(name="y", type=dt.integer_value(4))
hidden = L.fc(input=x, size=16, act=A.Tanh())
cost = L.classification_cost(input=L.fc(input=hidden, size=4), label=lab)
trainer = paddle.trainer.SGD(cost, Parameters.create(cost),
                             opt.Momentum(learning_rate=0.1))
rng = np.random.RandomState(0)
batch = [(rng.randn(8).astype(np.float32), int(rng.randint(4)))
         for _ in range(8)]
trainer.train(lambda: iter([batch]), event_handler=lambda e: None)
held = observe_metrics.get_registry().snapshot()["histograms"]
count = lambda name: held.get(name, {"count": 0})["count"]
print(json.dumps({
    **compile_cache.stats(),
    "backend": count("paddle_tpu_compile_backend_ms"),
    "retrieval": count("paddle_tpu_compile_cache_retrieval_ms")}))
"""


def _child(cache_dir, **env):
    """The last line a child printed: its `stats()` and the two counts."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "JAX_COMPILATION_CACHE_DIR": str(cache_dir), **env}
    done = subprocess.run([sys.executable, "-c", CHILD], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_second_process_reads_every_program_the_first_compiled(tmp_path):
    first = _child(tmp_path)
    assert first["dir"] == str(tmp_path)
    # the leaf initialisers, the trainer's eager calls, the step: all quick
    assert first["requests"] > 10 and first["hits"] == 0
    assert first["retrieval"] == 0
    assert first["entries"] == first["requests"]
    second = _child(tmp_path)
    assert second["requests"] == first["requests"]
    assert second["hits"] == second["requests"]
    assert second["retrieval"] == second["backend"] == second["requests"]
    assert second["entries"] == first["entries"]  # nothing written again


def test_an_explicit_threshold_in_the_environment_stands(tmp_path):
    # the control: at an hour a program, nothing of this set-up is kept
    kept = _child(tmp_path, **{KEEP_ENV: "3600"})
    assert kept["requests"] > 10
    assert kept["entries"] == 0 and kept["hits"] == 0


@pytest.mark.parametrize("placed", [False, True],
                         ids=["default_dir", "dir_from_the_environment"])
@pytest.mark.parametrize("explicit", [None, "2.5"],
                         ids=["threshold_unset", "threshold_explicit"])
def test_enable_keeps_quick_programs_wherever_the_cache_lives(
        monkeypatch, tmp_path, placed, explicit):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if explicit is None:
        monkeypatch.delenv(KEEP_ENV, raising=False)
    else:
        monkeypatch.setenv(KEEP_ENV, explicit)
    directory = compile_cache.enable()
    assert directory == (str(tmp_path) if placed
                         else os.path.join(REPO, ".jax_cache"))
    updates = dict(updates)
    # the directory is the user's where given; what is kept is ours,
    # unless the user said that too
    assert (DIR_FLAG in updates) == (not placed)
    if explicit is None:
        assert updates[KEEP_FLAG] == compile_cache.KEEP_FROM_SECS == 0
    else:
        assert KEEP_FLAG not in updates


def test_get_shape_reads_the_leafs_own_shape_and_moves_no_bytes():
    class Leaf:
        """A device array as `get_shape` may see it: it has a shape, and
        turning it into a host array is the copy that must not happen."""
        shape = (5120, 16)

        def __array__(self, *args, **kwargs):
            raise AssertionError("get_shape copied the leaf to the host")

    params = Parameters()
    params._values = {"on_device": Leaf(), "jax": jnp.zeros((3, 4)),
                      "list": [[1.0, 2.0]]}
    assert params.get_shape("on_device") == (5120, 16)
    with jax.transfer_guard("disallow"):  # says nothing on the CPU backend
        assert params.get_shape("jax") == (3, 4)
    assert params.get_shape("list") == (1, 2)
