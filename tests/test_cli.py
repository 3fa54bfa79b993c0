"""CLI launcher tests (`paddle train` surface parity, TrainerMain.cpp jobs)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = '''
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import layer as L, data_type as dt, activation as A
from paddle_tpu import optimizer as opt

batch_size = 16

def cost():
    x = L.data(name="x", type=dt.dense_vector(6))
    y = L.data(name="y", type=dt.integer_value(3))
    h = L.fc(input=x, size=12, act=A.Tanh())
    out = L.fc(input=h, size=3)
    return L.classification_cost(input=out, label=y)

def optimizer():
    return opt.Momentum(learning_rate=0.1, momentum=0.9)

def _data(n, seed=0):
    def reader():
        rng = np.random.RandomState(seed)
        W = rng.randn(6, 3)
        for _ in range(n):
            x = rng.randn(6).astype(np.float32)
            yield x, int(np.argmax(x @ W))
    return reader

def train_reader():
    return _data(128)

def test_reader():
    return _data(48)
'''


def _run_cli(args, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["PADDLE_TPU_LOG_LEVEL"] = "WARNING"
    return subprocess.run([sys.executable, "-m", "paddle_tpu.cli"] + args,
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=REPO)


@pytest.fixture(scope="module")
def config_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("cli") / "config.py"
    path.write_text(CONFIG)
    return str(path)


def test_cli_train_and_checkpoint(config_file, tmp_path):
    save_dir = str(tmp_path / "ckpts")
    ckpt_dir = str(tmp_path / "step_ckpts")
    proc = _run_cli(["train", "--config", config_file, "--num-passes", "2",
                     "--save-dir", save_dir,
                     "--checkpoint-dir", ckpt_dir,
                     "--checkpoint-every", "4"])
    assert proc.returncode == 0, proc.stderr
    assert "test cost=" in proc.stdout
    assert any(d.startswith("pass-") for d in os.listdir(save_dir))
    # step-cadence checkpoints (async overlapped writer) committed too
    assert any(d.startswith("pass-") for d in os.listdir(ckpt_dir))
    # --resume restores the newest valid checkpoint and trains on
    proc = _run_cli(["train", "--config", config_file, "--num-passes", "2",
                     "--checkpoint-dir", ckpt_dir,
                     "--checkpoint-every", "4", "--resume"])
    assert proc.returncode == 0, proc.stderr
    assert "test cost=" in proc.stdout


def test_cli_time_job(config_file):
    proc = _run_cli(["time", "--config", config_file, "--iters", "3"])
    assert proc.returncode == 0, proc.stderr
    stats = json.loads(proc.stdout.strip().splitlines()[-1])
    assert stats["ms_per_batch"] > 0


def test_cli_checkgrad_job(config_file):
    proc = _run_cli(["checkgrad", "--config", config_file])
    assert proc.returncode == 0, proc.stderr
    assert "checkgrad PASSED" in proc.stdout


V1_CONFIG = '''
from paddle_tpu.config import (settings, outputs, define_py_data_sources2,
                               get_config_arg, AdamOptimizer)
from paddle_tpu import layer as L, data_type as dt, activation as A
import numpy as np

hidden = get_config_arg("hidden", int, 16)
settings(batch_size=10, learning_rate=5e-3, learning_method=AdamOptimizer())

x = L.data(name="x", type=dt.dense_vector(6))
y = L.data(name="y", type=dt.integer_value(2))
h = L.fc(input=x, size=hidden, act=A.Tanh())
out = L.fc(input=h, size=2, act=A.Softmax())
outputs(L.classification_cost(input=out, label=y))


def _reader(file_list, n=60):
    def reader():
        rng = np.random.RandomState(0)
        for _ in range(n):
            v = rng.randn(6).astype(np.float32)
            yield v, int(v.sum() > 0)
    return reader


define_py_data_sources2(train_list="train", test_list="test",
                        module="paddle_tpu_user_config", obj="_reader")
'''


def test_v1_style_config_trains(tmp_path, capsys):
    """A reference-style settings()/outputs()/data-sources config runs
    through the CLI (config_parser + trainer_config_helpers parity)."""
    from paddle_tpu import cli

    conf = tmp_path / "v1_conf.py"
    conf.write_text(V1_CONFIG)
    rc = cli.main(["train", "--config", str(conf),
                   "--config-args", "hidden=8", "--num-passes", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "test cost=" in out


def test_get_config_arg_types():
    from paddle_tpu import config as cfgmod

    cfgmod.reset()
    cfgmod.set_config_args("a=3,b=true,c=hi")
    assert cfgmod.get_config_arg("a", int) == 3
    assert cfgmod.get_config_arg("b", bool) is True
    assert cfgmod.get_config_arg("c") == "hi"
    assert cfgmod.get_config_arg("missing", int, 7) == 7
    cfgmod.reset()


def test_train_with_trainer_count_dp(config_file, tmp_path):
    """--trainer-count N builds an N-device data-parallel mesh for the
    train step (reference: --trainer_count spun N MultiGradientMachine
    worker threads). Runs on a 4-device virtual CPU mesh."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO
    env["PADDLE_TPU_LOG_LEVEL"] = "INFO"
    env["PADDLE_TPU_LOG_PERIOD"] = "1"
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "train",
         "--config", str(config_file), "--num-passes", "2",
         "--trainer-count", "4"],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    import re

    costs = [float(m) for m in
             re.findall(r"pass \d+ batch \d+ cost=([0-9.eE+-]+)",
                        proc.stdout + proc.stderr)]
    assert len(costs) >= 4
    assert costs[-1] < costs[0]


def test_trainer_count_too_large_fails_cleanly(config_file):
    proc = _run_cli(["train", "--config", str(config_file),
                     "--trainer-count", "64"])
    assert proc.returncode != 0
    assert "exceeds" in proc.stderr + proc.stdout
