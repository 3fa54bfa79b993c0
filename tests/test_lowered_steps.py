"""The language-model cells' train steps, pinned: the trainer's lowered
step of each configuration's tiny preset, as its cell builds it, hashed
in float32 and in bfloat16. A change that is meant to leave a model's
program alone (a new configuration beside it, a kernel it never reaches)
leaves these hashes as they are; one that changes a model's program
changes its pin, and says so.

On the CPU the expert layer takes its plain form (``pallas_moe.fits``
says no), so lfm2's step is pinned a second time in the fused form that
its cell runs on the chip: the tiny preset at widths that tile, under the
Pallas interpret flag, with row tiles of 32 (``fused=True``)."""

import hashlib
import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
CELLS = {"lfm2-8b-a1b": "lfm2-8b-a1b-seq4096-bs2-train",
         "phi-4-mini-flash-reasoning":
             "phi-4-mini-flash-reasoning-seq4096-bs2-train",
         "granite-4.0-h-micro": "granite-4.0-h-micro-seq4096-bs2-train",
         "olmo-hybrid-7b": "olmo-hybrid-7b-seq4096-bs2-train"}
# sha256 of each lowered step, numbers of private functions taken out
PINS = {
    ('granite-4.0-h-micro', 'float32'):
        '838f33b77397b0aad9f952db6813a268c52306c1a2b2e19e8ac2b9e81b8ff5e3',
    ('granite-4.0-h-micro', 'bfloat16'):
        '25d4017d4149ec46d902f5c5526ac88cd03c28e38e50f62ac5ee840132a77465',
    ('lfm2-8b-a1b', 'float32'):
        'fc0ca07312ea0a78059ab0ea370040972c81b449cefc4dae85261f80062d8482',
    ('lfm2-8b-a1b', 'bfloat16'):
        'e337dc39454e90a7f2c73d60f5dd648e9f08bfd7c6dc00b7a51f27059c70c1aa',
    ('olmo-hybrid-7b', 'float32'):
        'd952c1463a4815132d14d335e8d9c2790377339f022de11bbf322b823a53beb5',
    ('olmo-hybrid-7b', 'bfloat16'):
        '56fa3d05a8c619128c5ea9c6c5993c2f3f1ec024a347fabdf0c9d805df402f96',
    ('phi-4-mini-flash-reasoning', 'float32'):
        '6d00621732cc6420e2cb79f5da941475461f8c1449796381a7578034d3c21d31',
    ('phi-4-mini-flash-reasoning', 'bfloat16'):
        'd8d693ff3d905d3fd4a86c68d3d291cf9c57c3712df6c0fb9851df38babb99e7',
}
FUSED_PINS = {
    'float32':
        'ba087ddc07222c6e03dce5e7f88166b3756c69ca61d467b1ffafada07c6c6bae',
    'bfloat16':
        'a72bc929e281012056812a91670f7d2608bbae57544af5fc5ab361060f7503c1',
}


def _load(kind, name):
    with open(os.path.join(TINY, kind, name + ".json")) as f:
        return json.load(f)


def step_hash(config, dtype, fused=False):
    """sha256 of the text of ``SGD``'s train step over the preset's first
    batch, lowered on the CPU; with ``fused`` the expert layer's kernels
    in interpret mode, at hidden and expert widths of 128 and rows of up
    to 64 tokens."""
    import jax

    import paddle_tpu as paddle
    from chipbench import traffic
    from paddle_tpu import layer as L
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops import pallas_moe
    from paddle_tpu.topology import convert_feed

    cfg = _load("configs", config)
    cell = _load("workloads", CELLS[config])
    if fused:
        cfg.update(hidden_size=128, moe_intermediate_size=128)
        cell["lengths"] = {"min": 48, "max": 64}
    flags = pk._INTERPRET, pallas_moe._ROW_TILE
    model = importlib.import_module("chipbench.models." + cfg["model"])
    # what other tests of the process may have left set: 64-bit types
    # (checkgrad turns them on), the flags
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    paddle.init(use_tpu=False, seed=7, compute_dtype=dtype,
                matmul_precision="default", default_dtype="float32",
                trap_fpe=False)
    try:
        if fused:
            pk._INTERPRET, pallas_moe._ROW_TILE = True, 32
        L.reset_name_counters()
        cost = model.build(cfg)
        trainer = paddle.trainer.SGD(
            cost, paddle.parameters.create(cost),
            paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9))
        feed = convert_feed(trainer.topology, traffic.make_pool(
            cfg["inputs"], cell, 7)[0])
        text = trainer._train_step.lower(
            trainer._trainable, trainer._replica, trainer._static,
            trainer._state, trainer._opt_state, feed, trainer._rng).as_text()
    finally:
        pk._INTERPRET, pallas_moe._ROW_TILE = flags
        if fused:   # the kernels' jitted callers read the tile at trace time
            jax.clear_caches()
        paddle.init(use_tpu=False, compute_dtype="",
                    matmul_precision="highest")
        jax.config.update("jax_enable_x64", x64)
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("config", sorted(CELLS))
def test_the_lowered_step_is_its_pin(config, dtype):
    assert step_hash(config, dtype) == PINS[config, dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_fused_expert_step_is_its_pin(dtype):
    assert step_hash("lfm2-8b-a1b", dtype, fused=True) == FUSED_PINS[dtype]
