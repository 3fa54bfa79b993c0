"""`chipbench/run.py` end to end off the chip: it refuses a measured run
without a TPU, its rehearsal prints no device metric, the result line has
the contract's keys, and a run whose timed path is broken underneath
comes out not correct."""

import io
import json
import os
import contextlib

import pytest

from chipbench import run as run_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")
CELL = "resnet50-bs256-train"


def _rehearse(cell, seed, seconds=0.5):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0",
                           "--rehearse", TINY])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_a_measured_run_refuses_a_cpu_device(capsys):
    with pytest.raises(SystemExit) as exit_info:
        run_mod.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                      "--trace", "0"])
    assert exit_info.value.code not in (0, None)
    assert "TPU" in str(exit_info.value.code)
    assert capsys.readouterr().out == ""


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit):
        run_mod.main(["--workload", "no-such-cell", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_rehearsal_is_correct_and_prints_no_device_metric(seed):
    line = _rehearse(CELL, seed)
    assert list(line) == ["rehearsal", "correct", "attempted", "failed",
                          "checks"]
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 3
    cell = run_mod.load_json(os.path.join(TINY, "workloads", CELL + ".json"))
    assert set(line["checks"]) == set(cell["limits"])
    assert "state3" in line["checks"]
    for pair in line["checks"].values():
        assert pair["value"] <= pair["limit"]


def test_the_result_line_has_exactly_the_contracts_keys():
    manifest = run_mod.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = "resnet50-dp4-bs1024-train"
    stamps = [0.1 * i for i in range(60)]
    hist = {"paddle_tpu_data_feed_stall_ms": {"count": 10, "sum": 5.0},
            "paddle_tpu_data_feed_convert_ms": {"count": 10, "sum": 50.0}}
    hist2 = {k: {"count": 69, "sum": v["sum"] + 59.0}
             for k, v in hist.items()}
    ctx = {
        "cell": {"name": cell, "batch": 1024, "trace": {}},
        "cfg": run_mod.load_json(os.path.join(ROOT, "chipbench", "configs",
                                              "resnet50.json")),
        "chips": 4, "stamps": stamps, "samples_per_step": 1024,
        "setup_s": 21.5, "peak_bytes": 4_000_000_000,
        "registry_open": hist, "registry_close": hist2,
        "compiles_open": {"requests": 7}, "compiles_close": {"requests": 7},
        "device_kind": "TPU v5 lite", "traced_steps": 20,
        "memory_stats": {},
        "trace": {"busy_s": 1.8, "window_s": 2.0, "busiest_busy_s": 1.8,
                  "kernel_s": {}, "collective_s": 0.05,
                  "collective_exposed_s": 0.02,
                  "device_ops": [["fusion.1", 0.9]],
                  "idle_gaps": [["SGD.train", 0.01]]},
    }
    import importlib

    ctx["flops"] = importlib.import_module("chipbench.flops.resnet50")
    result = {"correct": True, "attempted": 62, "failed": 0,
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 4, "memory_peak_bytes": 4_000_000_000}}
    plain = run_mod.result_line(result, ctx, manifest, cell, traced=0)
    assert tuple(plain) == run_mod.RESULT_KEYS
    assert set(plain["metrics"]) == {"train_samples_per_s", "step_ms_p95",
                                     "setup_s"}
    assert plain["metrics"]["train_samples_per_s"]["value"] == \
        pytest.approx(10240.0)
    assert plain["metrics"]["train_samples_per_s"]["unit"] == "samples/s"
    assert set(plain["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    traced = run_mod.result_line(result, ctx, manifest, cell, traced=1)
    assert tuple(traced) == run_mod.RESULT_KEYS + ("breakdown",)
    per_layer = {m["name"] for m in manifest["per_layer"]
                 if cell in m.get("workloads", [cell])}
    assert "allreduce_exposed_ms_per_step" in per_layer
    assert set(traced["metrics"]) == per_layer
    assert traced["metrics"]["device_idle_pct"]["value"] == \
        pytest.approx(10.0)
    assert traced["metrics"]["feed_stall_ms_per_step"]["value"] == \
        pytest.approx(1.0)
    assert traced["metrics"]["allreduce_exposed_ms_per_step"]["value"] == \
        pytest.approx(1.0)
    assert traced["metrics"]["compiles_in_window"]["value"] == 0.0
    assert 0 < traced["metrics"]["step_mfu_pct"]["value"] < 100
    assert {"busy_s", "window_s"} <= set(traced["device"])
    # the one-chip cell's line has no collective metric
    one = run_mod.result_line(result, ctx, manifest, CELL, traced=1)
    assert "allreduce_exposed_ms_per_step" not in one["metrics"]
    # a run whose trace gave nothing reports no trace metric, and no 0
    silent = run_mod.result_line(result, {**ctx, "trace": None}, manifest,
                                 cell, traced=1)
    assert "device_idle_pct" not in silent["metrics"]
    assert "step_mfu_pct" in silent["metrics"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from paddle_tpu import optimizer

    def frozen(self, grad, slot, param, lr):
        return jnp.zeros_like(param), slot

    monkeypatch.setattr(optimizer.Momentum, "apply_update", frozen)
    line = _rehearse(CELL, 4)
    assert line["correct"] is False
    assert line["checks"]["delta3"]["value"] == pytest.approx(1.0)
    assert line["checks"]["grad1"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from paddle_tpu import topology

    whole = topology.convert_feed

    def half(topo, data_batch, feeding=None, max_len=None):
        return whole(topo, data_batch[: len(data_batch) // 2], feeding,
                     max_len=max_len)

    monkeypatch.setattr(topology, "convert_feed", half)
    line = _rehearse(CELL, 4)
    assert line["correct"] is False
    failed = [k for k, p in line["checks"].items()
              if not p["value"] <= p["limit"]]
    assert failed, line


def test_the_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    """What chip 0 would hold had the gradients not been summed: every
    chip's rows are chip 0's, so the mean is over a quarter of the batch."""
    from paddle_tpu import topology

    whole = topology.convert_feed

    def first_chip_only(topo, data_batch, feeding=None, max_len=None):
        quarter = data_batch[: len(data_batch) // 4]
        return whole(topo, quarter * 4, feeding, max_len=max_len)

    monkeypatch.setattr(topology, "convert_feed", first_chip_only)
    line = _rehearse("resnet50-dp4-bs1024-train", 4, seconds=3)
    assert line["correct"] is False
    failed = [k for k, p in line["checks"].items()
              if not p["value"] <= p["limit"]]
    assert failed, line
