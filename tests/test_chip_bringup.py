"""What keeps a run honest about its device (ISSUE 21): no silent CPU, one
compile cache placed from outside, one process per chip, and a smoke
script that refuses to start without a TPU. All CPU, all fast."""

import os
import subprocess
import sys

import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.core import place
from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_init_use_tpu_raises_without_a_tpu():
    with pytest.raises(paddle.EnforceError, match="no TPU device.*Cpu"):
        paddle.init(use_tpu=True)
    with pytest.raises(paddle.EnforceError, match="no TPU device"):
        paddle.TPUPlace(0).jax_device()
    # False really is the CPU, None takes what JAX runs on
    paddle.init(use_tpu=False)
    assert paddle.default_place() == paddle.CPUPlace()
    paddle.init()
    assert paddle.default_place() == paddle.CPUPlace()


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    placed = lambda: [u for u in updates
                      if u[0] == "jax_compilation_cache_dir"]
    # where JAX_COMPILATION_CACHE_DIR is set JAX reads it: place nothing
    # (what the cache keeps is set either way: test_compile_cache_keeps.py)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert placed() == []
    # where it is not: the fixed path in the checkout, never a temp dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable() == os.path.join(REPO, ".jax_cache")
    assert placed() == [("jax_compilation_cache_dir",
                         os.path.join(REPO, ".jax_cache"))]
    assert set(compile_cache.stats()) == {"dir", "entries", "requests",
                                          "hits"}


def test_children_that_need_the_chip_are_refused(monkeypatch):
    # children pinned to the CPU are always fine (this test process is)
    place.enforce_children_can_open_devices(4, "t")
    # a TPU host, children not pinned: one process at most
    monkeypatch.setattr(place, "host_tpu_chips", lambda: 4)
    place.enforce_children_can_open_devices(1, "t", env={})
    with pytest.raises(paddle.EnforceError,
                       match="4 processes on a TPU host"):
        place.enforce_children_can_open_devices(4, "t", env={})
    # a parent that has opened the TPU can start none at all
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(paddle.EnforceError, match="already opened the TPU"):
        place.enforce_children_can_open_devices(1, "t", env={})


def test_chip_smoke_refuses_the_cpu_before_any_stage():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout == ""  # no stage passed, no result line
