"""The seven per-layer metrics that read the program's phase histograms:
each is the mean of its histogram's observations inside the window."""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

READERS = {
    "feed_read_ms_per_step": "paddle_tpu_data_feed_read_ms",
    "feed_host_ms_per_step": "paddle_tpu_data_feed_host_ms",
    "feed_place_ms_per_step": "paddle_tpu_data_feed_place_ms",
    "feed_backpressure_ms_per_step": "paddle_tpu_data_feed_backpressure_ms",
    "step_dispatch_ms_per_step": "paddle_tpu_train_dispatch_ms",
    "step_readback_ms_per_step": "paddle_tpu_train_readback_ms",
    "step_handler_ms_per_step": "paddle_tpu_train_handler_ms",
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_phase_metric_is_its_histograms_mean_in_the_window(metric):
    reader = importlib.import_module("chipbench.metrics." + metric)
    hist = READERS[metric]
    ctx = {"registry_open": {hist: {"count": 4, "sum": 10.0}},
           "registry_close": {hist: {"count": 24, "sum": 70.0}}}
    assert reader.read(ctx) == pytest.approx(3.0)
    # the histogram was made inside the window: all of it counts
    assert reader.read({"registry_open": {},
                        "registry_close": ctx["registry_close"]}) == \
        pytest.approx(70.0 / 24)
    # a program without the histogram (the parent commit), or a window in
    # which nothing was observed: nothing, and no 0
    others = {k: {"count": 9, "sum": 9.0} for k in READERS.values()
              if k != hist}
    assert reader.read({"registry_open": {}, "registry_close": others}) \
        is None
    assert reader.read({"registry_open": ctx["registry_open"],
                        "registry_close": ctx["registry_open"]}) is None


def test_the_manifest_lists_the_seven_on_the_one_chip_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert list(entries)[-7:] == [
        "feed_read_ms_per_step", "feed_host_ms_per_step",
        "feed_place_ms_per_step", "feed_backpressure_ms_per_step",
        "step_dispatch_ms_per_step", "step_readback_ms_per_step",
        "step_handler_ms_per_step"]
    for name in READERS:
        entry = entries[name]
        assert entry["unit"] == "ms" and entry["source"] == "program_span"
        assert entry["moves"] == "train_samples_per_s"
        assert entry["workloads"] == ["resnet50-bs256-train"]
        assert entry["layer"] == ("feed" if name.startswith("feed_")
                                  else "trainer loop")
        assert entry["better"] == ("higher" if "backpressure" in name
                                   else "lower")
