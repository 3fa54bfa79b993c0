"""One traced run of a cell whose line also holds the eight metrics that
split `setup_s`: `python chipbench/setup_phases.py --workload <cell> --seed
<n> --seconds <s>`.

`BENCHMARK.json` does not list the eight yet: an entry appended to
`per_layer` fails a test that pins that list's last seven by position, and
one put before them reads as a change to what was there. Until a
`benchmark` PR appends `ENTRIES`, this is how a builder reads them on the
chip. It is `run.py` with the entries added to the manifest in memory, and
always traced, since per-layer metrics appear in a traced line only. The
benchmark's own runs never run this.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402

ONE_CHIP = ("resnet50-bs256-train", "granite-4.0-h-micro-seq4096-bs2-train",
            "olmo-hybrid-7b-seq4096-bs2-train",
            "phi-4-mini-flash-reasoning-seq4096-bs2-train")
# (name, source, layer), in the order the entries should be appended
_EIGHT = (("setup_build_s", "program_span", "entry"),
          ("setup_train_call_s", "program_span", "trainer loop"),
          ("setup_sync_back_s", "program_span", "trainer loop"),
          ("setup_steps_s", "program_span", "trainer loop"),
          ("setup_compile_trace_s", "program_counter", "entry"),
          ("setup_compile_lower_s", "program_counter", "entry"),
          ("setup_compile_backend_s", "program_counter", "entry"),
          ("setup_unattributed_s", "host_clock", "entry"))
ENTRIES = [{"name": name, "unit": "s", "better": "lower", "source": source,
            "layer": layer, "moves": "setup_s", "workloads": list(ONE_CHIP)}
           for name, source, layer in _EIGHT]


def with_entries(manifest):
    """The manifest with the eight at the end of `per_layer`."""
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
