"""`BENCHMARK.json` keeps to the contract's shape, and every file it names
is there: a cell, a configuration or a metric is found by name alone."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def test_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names)), section
        assert all(NAME.match(n) for n in names), names
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_per_layer_metrics_move_a_metric_their_cells_report(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        layers.add(m["layer"])
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, "PERF.md's list of layers lacks %r" % layer
    mfu = [m for m in manifest["per_layer"] if "mfu" in m["name"].split("_")]
    assert mfu and all(m["unit"] == "%" for m in mfu)


def test_every_named_file_is_found(manifest):
    paths = manifest["paths"]
    assert manifest["command"][1].startswith(paths[0] + "/")
    configs = {}
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        cfg = _load(c["file"])
        configs[c["name"]] = cfg
        for kind in ("models", "reference", "flops"):
            key = {"models": "model"}.get(kind, kind)
            mod = importlib.import_module(
                "chipbench.%s.%s" % (kind, cfg[key]))
            assert mod is not None
        assert cfg["precision"]["control"]
    for w in manifest["workloads"]:
        cell = _load("chipbench/workloads/%s.json" % w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        assert w["config"] in configs
        assert importlib.import_module(
            "chipbench.drivers." + cell["driver"]).run
        assert cell["limits"], "a cell compares at least one number"
        tiny = _load("tests/chipbench/tiny/workloads/%s.json" % w["name"])
        assert tiny["config"] == cell["config"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        reader = importlib.import_module("chipbench.metrics." + m["name"])
        assert callable(reader.read)


def test_run_py_holds_no_table_of_cells_configs_or_metrics(manifest):
    source = open(os.path.join(ROOT, "chipbench", "run.py")).read()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            assert '"%s"' % entry["name"] not in source, entry["name"]
