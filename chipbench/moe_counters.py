"""One traced run of a cell whose line also holds the expert layers' two
metrics: `python chipbench/moe_counters.py --workload <cell> --seed <n>
--seconds <s>`.

`BENCHMARK.json` does not list `moe_rows_here_pct` and
`moe_expert_load_max_over_mean` yet, for the reason `setup_phases.py`
gives for its eight: an entry appended to `per_layer` fails a test that
pins that list's last seven by position. Until a `benchmark` PR appends
`ENTRIES`, this is how a builder reads them on the chip: `run.py` with
the entries added to the manifest in memory, always traced. The
benchmark's own runs never run this.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

CELLS = ("lfm2-8b-a1b-seq4096-bs2-train",)
ENTRIES = [{"name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": "step program",
            "moves": "train_samples_per_s", "workloads": list(CELLS)}
           for name, unit, better in (
               ("moe_rows_here_pct", "%", "lower"),
               ("moe_expert_load_max_over_mean", "ratio", "lower"))]


def with_entries(manifest):
    """The manifest with the two at the end of `per_layer`."""
    return {**manifest, "per_layer": manifest["per_layer"] + ENTRIES}


def main(argv=None):
    load_cell = run.load_cell

    def load_cell_with_entries(name, data_dir):
        cell, cfg, manifest = load_cell(name, data_dir)
        return cell, cfg, with_entries(manifest)

    run.load_cell = load_cell_with_entries
    try:
        argv = sys.argv[1:] if argv is None else list(argv)
        return run.main(argv + ["--trace", "1"])
    finally:
        run.load_cell = load_cell


if __name__ == "__main__":
    sys.exit(main())
