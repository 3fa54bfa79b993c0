"""The eight per-layer metrics that split `setup_s`: each reads absolute sums
of what the program's histograms held when the window opened, a program
without them reads nothing, and on a rehearsal's `ctx` the four rows and
the remainder close on `setup_s`."""

import argparse
import importlib
import json
import os
import time

import pytest

from chipbench import run as run_mod
from chipbench import setup_phases

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")

# metric -> ({histogram: sign}, layer, source)
READERS = {
    "setup_build_s": ({"paddle_tpu_setup_import_ms": 1,
                       "paddle_tpu_setup_init_ms": 1,
                       "paddle_tpu_setup_params_create_ms": 1,
                       "paddle_tpu_setup_params_update_ms": 1,
                       "paddle_tpu_setup_trainer_prepare_ms": 1},
                      "entry", "program_span"),
    "setup_train_call_s": ({"paddle_tpu_train_enter_ms": 1,
                            "paddle_tpu_train_exit_ms": 1,
                            "paddle_tpu_train_sync_back_ms": -1},
                           "trainer loop", "program_span"),
    "setup_sync_back_s": ({"paddle_tpu_train_sync_back_ms": 1},
                          "trainer loop", "program_span"),
    "setup_steps_s": ({"paddle_tpu_data_feed_stall_ms": 1,
                       "paddle_tpu_train_dispatch_ms": 1,
                       "paddle_tpu_train_readback_ms": 1,
                       "paddle_tpu_train_handler_ms": 1},
                      "trainer loop", "program_span"),
    "setup_compile_trace_s": ({"paddle_tpu_compile_trace_ms": 1},
                              "entry", "program_counter"),
    "setup_compile_lower_s": ({"paddle_tpu_compile_lower_ms": 1},
                              "entry", "program_counter"),
    "setup_compile_backend_s": ({"paddle_tpu_compile_backend_ms": 1},
                                "entry", "program_counter"),
    "setup_unattributed_s": ({}, "entry", "host_clock"),
}
ROWS = ("setup_build_s", "setup_train_call_s", "setup_sync_back_s",
        "setup_steps_s")
ONE_CHIP = ["resnet50-bs256-train", "granite-4.0-h-micro-seq4096-bs2-train",
            "olmo-hybrid-7b-seq4096-bs2-train",
            "phi-4-mini-flash-reasoning-seq4096-bs2-train"]


def _read(metric, ctx):
    return importlib.import_module("chipbench.metrics." + metric).read(ctx)


def _registry():
    """Every histogram the eight read, each with a sum of its own (ms)."""
    names = sorted({h for signs, _, _ in READERS.values() for h in signs})
    return {name: {"count": 3 + i, "sum": 1000.0 * (i + 2) + 0.5}
            for i, name in enumerate(names)}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_setup_metric_reads_absolute_sums_at_the_windows_opening(metric):
    signs, _, _ = READERS[metric]
    held = _registry()
    # what the window added must not count: the close holds more
    ctx = {"registry_open": held, "setup_s": 60.0,
           "registry_close": {k: {"count": 99, "sum": 9e9} for k in held}}

    def expected(name):
        return sum(sign * held[h]["sum"]
                   for h, sign in READERS[name][0].items()) / 1e3

    if metric == "setup_unattributed_s":
        want = 60.0 - sum(expected(row) for row in ROWS)
    else:
        want = expected(metric)
    assert _read(metric, ctx) == pytest.approx(want)
    # a program without the histograms (the parent commit): nothing, no 0
    old = {k: v for k, v in held.items()
           if k in READERS["setup_steps_s"][0]}
    got = _read(metric, {**ctx, "registry_open": old})
    if metric == "setup_steps_s":  # its four are older than the spans
        assert got == pytest.approx(want)
    else:
        assert got is None
    assert _read(metric, {**ctx, "registry_open": {}}) is None


def test_a_histogram_that_observed_nothing_yet_counts_zero_beside_others():
    held = _registry()
    del held["paddle_tpu_setup_params_update_ms"]  # no user call of it
    ctx = {"registry_open": held, "setup_s": 60.0}
    assert _read("setup_build_s", ctx) == pytest.approx(sum(
        held[h]["sum"] for h in READERS["setup_build_s"][0]
        if h in held) / 1e3)
    assert _read("setup_unattributed_s", ctx) is not None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_the_harness_reads_a_setup_metric_through_an_appended_entry(metric):
    """`BENCHMARK.json` lists none of the eight yet (an appended entry fails
    the test that pins `per_layer`'s last seven by position); this is the
    entry a `benchmark` PR appends, and `setup_phases.py` reads through."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        accepted = json.load(f)
    manifest = setup_phases.with_entries(accepted)
    assert manifest["per_layer"][:-8] == accepted["per_layer"]
    assert [m["name"] for m in manifest["per_layer"][-8:]] == [
        e["name"] for e in setup_phases.ENTRIES]
    entry = {m["name"]: m for m in manifest["per_layer"]}[metric]
    _, layer, source = READERS[metric]
    assert entry == {"name": metric, "unit": "s", "better": "lower",
                     "source": source, "layer": layer, "moves": "setup_s",
                     "workloads": ONE_CHIP}
    # the harness finds it for those cells' traced lines and no other
    ctx = {"registry_open": _registry(), "setup_s": 60.0}
    for cell in accepted["workloads"]:
        line = run_mod.read_metrics(
            [m for m in run_mod.metrics_of(manifest, "per_layer",
                                           cell["name"])
             if m["name"] in READERS], ctx)
        assert (metric in line) == (cell["chips"] == 1)
        if metric in line:
            assert line[metric] == {"value": _read(metric, ctx), "unit": "s"}


def test_setup_phases_is_a_traced_run_with_the_eight_in_its_manifest(
        monkeypatch):
    seen = {}

    def fake_main(argv):
        seen["argv"] = argv
        seen["manifest"] = run_mod.load_cell(ONE_CHIP[0], run_mod.HERE)[2]
        return 0

    load_cell = run_mod.load_cell
    monkeypatch.setattr(run_mod, "main", fake_main)
    assert setup_phases.main(["--workload", ONE_CHIP[0], "--seed", "7",
                              "--seconds", "2"]) == 0
    assert seen["argv"][-2:] == ["--trace", "1"]
    assert seen["manifest"]["per_layer"][-8:] == setup_phases.ENTRIES
    assert run_mod.load_cell is load_cell


@pytest.mark.parametrize("cell_name", [
    "resnet50-bs256-train", "granite-4.0-h-micro-seq4096-bs2-train"])
def test_on_a_rehearsal_the_rows_and_the_remainder_close_on_setup_s(
        cell_name):
    from paddle_tpu.observe import metrics as observe_metrics

    # a measured run is a process of its own; here the registry has other
    # tests' observations, which the readers must not take for this run's
    earlier = observe_metrics.get_registry().snapshot()["histograms"]
    started = time.perf_counter()
    # the readers look at the window's opening only; it is long so that
    # no one slow step of a loaded machine closes it before its third stamp
    args = argparse.Namespace(workload=cell_name, seed=7, seconds=2.0,
                              trace=0, rehearse=TINY, work_dir=None)
    cell, cfg, _ = run_mod.load_cell(cell_name, TINY)
    driver = importlib.import_module("chipbench.drivers." + cell["driver"])
    ctx = driver.run(cell, cfg, args, started, rehearsal=True)["ctx"]
    zero = {"count": 0, "sum": 0.0}
    ctx["registry_open"] = {
        name: {key: h[key] - earlier.get(name, zero)[key] for key in zero}
        for name, h in ctx["registry_open"].items()}
    got = {metric: _read(metric, ctx) for metric in READERS}
    assert all(value is not None and value > 0 for value in got.values()), got
    assert sum(got[row] for row in ROWS) + got["setup_unattributed_s"] == \
        pytest.approx(ctx["setup_s"])
    # two `train` calls ended before the window, the third has begun
    held = ctx["registry_open"]
    assert held["paddle_tpu_train_enter_ms"]["count"] == 3
    assert held["paddle_tpu_train_exit_ms"]["count"] == 2
    assert held["paddle_tpu_train_sync_back_ms"]["count"] == 4
    assert held["paddle_tpu_setup_params_update_ms"]["count"] == 1
    # nothing nested counts twice: on the thread that compiled, the three
    # phases together fit into the time there was (the harness's own
    # compiles, outside the program's spans, are among them)
    compiled = sum(got["setup_compile_%s_s" % k]
                   for k in ("trace", "lower", "backend"))
    assert compiled <= ctx["setup_s"]
    assert got["setup_compile_trace_s"] <= \
        got["setup_build_s"] + got["setup_steps_s"]
