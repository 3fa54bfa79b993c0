"""Audited-bench-row invariants (benchmark/harness.sanitize_bench_row):
no emitted row may show wall_ms < device_ms or spread_pct > 100 — the
round-5 tagging row shipped spread_pct=15689 with wall 0.039 vs device
0.587 (VERDICT r5 weak #3)."""

import json

from benchmark.harness import sanitize_bench_row


def _r5_tagging_row():
    """The synthetic collapsed-wall sample: the actual broken r5 row."""
    return {
        "metric": "tagging_bilstm_crf_train_samples_per_sec_bs32",
        "value": 54515.5, "unit": "samples/s", "timing": "device",
        "repeats": 3, "spread_pct": 15689.0,
        "device_ms": 0.587, "wall_ms": 0.039, "wall_vs_baseline": 12.3,
    }


def test_collapsed_wall_demoted():
    rec = sanitize_bench_row(_r5_tagging_row())
    assert "wall_ms" not in rec
    assert "wall_vs_baseline" not in rec
    assert rec["wall_collapsed_ms"] == 0.039
    # the published value stays device-derived, untouched
    assert rec["value"] == 54515.5 and rec["device_ms"] == 0.587
    assert "collapsed chain" in rec["sanity_note"]


def test_excess_spread_demoted():
    rec = sanitize_bench_row(_r5_tagging_row())
    assert rec["spread_pct"] is None
    assert rec["spread_raw_pct"] == 15689.0


def test_invariant_holds_after_sanitize():
    rec = sanitize_bench_row(_r5_tagging_row())
    wall, dev = rec.get("wall_ms"), rec.get("device_ms")
    assert not (wall is not None and dev is not None and wall < dev)
    sp = rec.get("spread_pct")
    assert not (sp is not None and sp > 100.0)


def test_sane_rows_pass_through_unchanged():
    rec = {"metric": "resnet50_train_samples_per_sec_per_chip_bs64",
           "value": 2352.0, "unit": "samples/s", "spread_pct": 12.4,
           "device_ms": 27.2, "wall_ms": 29.1}
    out = sanitize_bench_row(dict(rec))
    assert out == rec  # no notes, nothing demoted


def test_wall_only_rows_untouched_by_device_rule():
    rec = {"metric": "m", "value": 9.5, "spread_pct": 14.0, "median": 9.9}
    out = sanitize_bench_row(dict(rec))
    assert out == rec


def test_bench_print_applies_sanitizer(capsys):
    import bench

    bench._print(_r5_tagging_row())
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert "wall_ms" not in rec and rec["spread_pct"] is None
    assert rec["wall_collapsed_ms"] == 0.039
    # don't pollute the module-level re-emission registry for other tests
    bench._EMITTED.pop(rec["metric"], None)
    if rec["metric"] in bench._EMIT_ORDER:
        bench._EMIT_ORDER.remove(rec["metric"])


# -- serving rows (benchmark/exp_serve.py): reject, don't demote -------------

import pytest


def _serving_row():
    """A sane exp_serve row: qps value + latency percentiles."""
    return {"metric": "serve_mlp_qps_c8", "value": 1234.5, "unit": "qps",
            "p50_ms": 4.2, "p99_ms": 9.8, "requests": 400, "batches": 71,
            "clients": 8, "max_batch": 32, "max_latency_ms": 5.0}


def test_serving_row_sane_passes_through():
    rec = _serving_row()
    out = sanitize_bench_row(dict(rec))
    assert out == rec  # untouched, no notes


def test_serving_row_p99_below_p50_rejected():
    """Percentiles of ONE latency sample are monotone in the quantile —
    p99 < p50 can only mean broken measurement code; such a row has no
    honest demoted form (contrast wall<device, where device survives)."""
    row = _serving_row()
    row["p99_ms"] = 1.0
    with pytest.raises(ValueError, match="p99_ms .* < p50_ms"):
        sanitize_bench_row(row)


def test_serving_row_nonpositive_qps_rejected():
    row = _serving_row()
    row["value"] = 0.0
    with pytest.raises(ValueError, match="qps"):
        sanitize_bench_row(row)
    with pytest.raises(ValueError, match="qps"):
        sanitize_bench_row({"metric": "m", "qps": -3.0})


def test_serving_fields_do_not_touch_training_rows():
    """A training row with neither percentiles nor a qps unit must be
    immune to the serving invariants (value 0 is demote-worthy there,
    not reject-worthy)."""
    rec = {"metric": "resnet50_ms", "value": 0.0, "unit": "ms/batch"}
    assert sanitize_bench_row(dict(rec)) == rec
