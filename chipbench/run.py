"""One cell, one run: `python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`.

Everything that belongs to one cell, configuration, driver or metric is a
file found by the name `BENCHMARK.json` gives: `workloads/<cell>.json`,
`configs/<config>.json` (which names its `models/`, `reference/` and
`flops/` modules), `drivers/<driver>.py`, `metrics/<metric>.py`. This file
holds no table of them.

`--rehearse DIR` is for debugging the harness off the chip: it takes the
cell's sizes from `DIR/workloads` and `DIR/configs` (the tiny presets of
`tests/chipbench/tiny`), runs on whatever device JAX has, and prints a
line with `"rehearsal": true` and no metric and no device: a rehearsal
proves control flow and can never pass for a measured run.
"""

import time

_STARTED = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name, data_dir):
    """(cell, configuration, manifest) by the names in `BENCHMARK.json`."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit("chipbench: no workload %r in BENCHMARK.json" % name)
    cell = load_json(os.path.join(data_dir, "workloads", name + ".json"))
    cfg = load_json(os.path.join(data_dir, "configs",
                                 entry["config"] + ".json"))
    for key in ("config", "traffic", "chips"):
        if key in cell and cell[key] != entry[key]:
            raise SystemExit("chipbench: %s of %s is %r in its file and %r "
                             "in BENCHMARK.json"
                             % (key, name, cell[key], entry[key]))
    cell = {**cell, "name": name, "chips": entry["chips"]}
    return cell, cfg, manifest


def metrics_of(manifest, section, cell_name):
    """The section's metrics that this cell reports: those that list it
    under `workloads`, and those that list nothing."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def read_metrics(entries, ctx):
    out = {}
    for entry in entries:
        reader = importlib.import_module("chipbench.metrics." + entry["name"])
        value = reader.read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def result_line(result, ctx, manifest, cell_name, traced, rehearsal=False):
    """The last line's object, without the numbers compared (they come
    last). A rehearsal's line has no metric and no device."""
    line = {k: result[k] for k in ("correct", "attempted", "failed")}
    if rehearsal:
        return {"rehearsal": True, **line}
    section = "per_layer" if traced else "end_to_end"
    line["metrics"] = read_metrics(
        metrics_of(manifest, section, cell_name), ctx)
    line["device"] = dict(result["device"])
    if traced and ctx["trace"] is not None:
        line["device"]["busy_s"] = ctx["trace"]["busy_s"]
        line["device"]["window_s"] = ctx["trace"]["window_s"]
        line["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                             "idle_gaps": ctx["trace"]["idle_gaps"]}
    return line


def main(argv=None, started=None):
    started = _STARTED if started is None else started
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", metavar="DIR", default=None)
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    args.work_dir = os.path.join(ROOT, ".chipbench_work")
    os.makedirs(args.work_dir, exist_ok=True)

    rehearsal = args.rehearse is not None
    cell, cfg, manifest = load_cell(
        args.workload, os.path.abspath(args.rehearse) if rehearsal else HERE)
    from chipbench import window

    driver = importlib.import_module("chipbench.drivers." + cell["driver"])
    result = driver.run(cell, cfg, args, started, rehearsal=rehearsal)
    ctx = result.pop("ctx")
    numbers = result.pop("numbers")
    compared = result.pop("compared")

    print("chipbench: %s seed %d: %d steps in a window of %.3f s, "
          "reference %.1f s"
          % (cell["name"], args.seed, len(ctx["stamps"]) - 1,
             ctx["stamps"][-1] - ctx["stamps"][0], ctx["reference_s"]),
          file=sys.stderr)
    gaps = window.gaps_ms(ctx["stamps"])
    longest = sorted(range(len(gaps)), key=gaps.__getitem__)[-5:][::-1]
    print("chipbench: the five longest steps, as step:ms: %s"
          % " ".join("%d:%.1f" % (i, gaps[i]) for i in longest),
          file=sys.stderr)
    print("chipbench: memory_stats of device 0 after the window: %s"
          % json.dumps(ctx["memory_stats"], sort_keys=True), file=sys.stderr)
    line = result_line(result, ctx, manifest, cell["name"], args.trace,
                       rehearsal)
    line["checks"] = compared
    others = {k: v for k, v in numbers.items() if k not in compared}
    print("chipbench: read beside the numbers compared: %s"
          % json.dumps(others, sort_keys=True), file=sys.stderr)
    for name, pair in compared.items():
        print("chipbench: compared %s = %r, limit %r"
              % (name, pair["value"], pair["limit"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
