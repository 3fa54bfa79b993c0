"""The control of the comparison, at a size a test run can hold: the plain
reference computed in fp8 in the program's place comes out not correct
under the tiny preset's limits, on three seeds; so does half a batch; and
`control.py` exits 1 where a stand-in passes every limit."""

import json
import os

import pytest

from chipbench import check, control

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "chipbench", "tiny")


def _load(kind, name):
    with open(os.path.join(TINY, kind, name + ".json")) as f:
        return json.load(f)


# The tiny stand-ins of the cells' sizes. ResNet-50 at 8 rows of 32 x 32:
# the fp8 control reads 0.035 to 0.053 on the running statistics' worst leaf
# against a tiny limit of 0.01 and half a batch 0.56 to 0.61 on the first
# gradient's against 0.2. The LSTM (no cell yet, PERF.md section 7; its
# reference stays in use here) at 64 rows, hidden 64: fp8 reads 0.05 to 0.09
# on the first gradient's worst leaf. A cell's own limits come from the chip
# at its own size (PERF.md); these tests keep the control alive.
LSTM_CELL = {"name": "lstm1280-tiny", "chips": 1, "batch": 64,
             "pool_batches": 4, "lengths": {"min": 16, "max": 16},
             "limits": {"loss1": 0.01, "grad1": 0.035, "grad1_med": 0.008,
                        "delta3": 0.03, "delta3_med": 0.008}}


def _cell_and_cfg(config):
    if config == "lstm1280":
        return LSTM_CELL, _load("configs", "lstm1280")
    return (dict(_load("workloads", "resnet50-bs256-train"),
                 name="resnet50-bs256-train"), _load("configs", "resnet50"))


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("config", ["resnet50", "lstm1280"])
def test_control_and_half_batch_fail_a_limit(config, seed):
    cell, cfg = _cell_and_cfg(config)
    out = control.read_seed(cell, cfg, seed)
    assert set(out) == {"control_fp8", "half_batch"}
    for name, stood in out.items():
        assert set(stood) == {"correct", "checks", "numbers"}
        assert set(stood["checks"]) == set(cell["limits"])
        assert stood["correct"] is False, (name, stood["numbers"])


@pytest.mark.parametrize("passes,rc", [([], 0), (["half_batch"], 1)])
def test_control_exits_1_where_a_stand_in_passes_every_limit(
        monkeypatch, capsys, passes, rc):
    def canned(cell, cfg, seed, devices=None):
        return {name: {"correct": name in passes, "checks": {},
                       "numbers": {}}
                for name in control.stand_ins(cell, cfg)}

    monkeypatch.setattr(control, "read_seed", canned)
    assert control.main(["--workload", "resnet50-bs256-train", "--seeds",
                         "5,6", "--rehearse", TINY]) == rc
    streams = capsys.readouterr()
    lines = [json.loads(x) for x in streams.out.strip().splitlines()]
    assert [x["seed"] for x in lines] == [5, 6]
    assert all(x["half_batch"]["correct"] is bool(passes) for x in lines)
    assert ("half_batch on seed 6" in streams.err) is bool(passes)


def test_a_cell_on_four_chips_also_reads_one_chips_rows_alone():
    cell = {"batch": 1024, "chips": 4}
    cfg = {"precision": {"control": "fp8"}}
    assert control.stand_ins(cell, cfg) == {
        "control_fp8": ("fp8", 1024), "half_batch": (None, 512),
        "no_exchange": (None, 256)}


def test_worst_leaf_gap_is_a_gap_of_norms_against_the_larger_scale():
    import numpy as np

    ref = {"a": np.full(4, 2.0), "b": np.full(4, 1e-6), "c": np.full(4, 1.0)}
    same = {k: v.copy() for k, v in ref.items()}
    assert check.worst_leaf_gap(same, ref) == (0.0, "a")
    # b is all but zero: measured against the median leaf, not itself
    same["b"] = np.full(4, 3e-6)
    gap, where = check.worst_leaf_gap(same, ref)
    assert where == "b" and gap == pytest.approx(4e-6 / 2.0)
    # an unmoved leaf reads 1, a doubled one too
    assert check.worst_leaf_gap({**ref, "a": np.zeros(4)}, ref)[0] == 1.0
    assert check.worst_leaf_gap({**ref, "a": 2 * ref["a"]}, ref)[0] == 1.0
    assert check.moved_leaves(ref) == ["a", "c"]
    compared, ok = check.decide({"x": float("nan")}, {"x": 0.1})
    assert not ok and compared["x"]["limit"] == 0.1
