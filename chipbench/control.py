"""The upper readings of a training cell's comparison, at the cell's own
size: `python chipbench/control.py --workload <cell> --seeds 1,2,3`.

For each seed the plain reference follows the cell's first three batches,
and then stands in the program's place three times: computed in the
control precision the configuration names (the nearest below the one it
states), with half of every batch left out and the mean taken over the
rest, and, for a cell on several chips, with one chip's rows alone (what
that chip would hold had the exchange of gradients been left out). Each
is held to the cell's own limits by `check.decide`, as a run of the
program is, and has to come out not correct: the line of a seed gives,
for each stand-in, `correct`, the numbers compared beside their limits and
the other readings, and the exit code is 1 where any stand-in on any seed
passed every limit. A state left unchanged reads 1 by that measure and
needs no run. The benchmark's own runs never run this; `tests/chipbench`
runs it at a tiny size.
"""

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def stand_ins(cell, cfg):
    """{name: (quant, rows kept of each batch)} of what stands in."""
    rows = int(cell["batch"])
    out = {"control_" + cfg["precision"]["control"]:
           (cfg["precision"]["control"], rows),
           "half_batch": (None, rows // 2)}
    if int(cell["chips"]) > 1:
        out["no_exchange"] = (None, rows // int(cell["chips"]))
    return out


def read_seed(cell, cfg, seed, devices=None):
    """{stand-in name: {"correct", "checks", "numbers"}} for one seed:
    the stand-in's readings decided by the cell's limits."""
    from chipbench import check, traffic
    from chipbench.drivers.train import LEARNING_RATE as lr, MOMENTUM as mu
    from chipbench.reference import common

    ref = importlib.import_module("chipbench.reference." + cfg["reference"])
    pool = traffic.make_pool(cfg["inputs"], cell, seed)
    first = [ref.batch_arrays(pool[i % len(pool)], cfg) for i in range(3)]
    reference = common.train3(ref, cfg, seed, first, lr, mu, devices=devices)
    out = {}
    for name, (quant, keep) in stand_ins(cell, cfg).items():
        batches = [tuple(a[:keep] for a in b) for b in first]
        stood = common.train3(ref, cfg, seed, batches, lr, mu, quant=quant,
                              devices=devices)
        numbers = check.readings(stood, reference)
        compared, correct = check.decide(numbers, cell["limits"])
        out[name] = {"correct": correct, "checks": compared,
                     "numbers": numbers}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rehearse", metavar="DIR", default=None)
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from chipbench import run as run_mod
    from paddle_tpu.utils import compile_cache

    compile_cache.enable()
    cell, cfg, _ = run_mod.load_cell(
        args.workload,
        os.path.abspath(args.rehearse) if args.rehearse else HERE)
    devices = jax.devices()
    if args.rehearse is None and (devices[0].platform != "tpu"
                                  or len(devices) < cell["chips"]):
        raise SystemExit("chipbench: control of %s needs %d TPU chip(s), "
                         "jax.devices() is %r"
                         % (cell["name"], cell["chips"], devices))
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = read_seed(cell, cfg, seed, devices[: cell["chips"]])
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "platform": devices[0].platform, **out}))
        sys.stdout.flush()
        passed += ["%s on seed %d" % (name, seed)
                   for name, stood in out.items() if stood["correct"]]
    if passed:
        print("chipbench: passed every limit of %s, so the limits do not "
              "hold it off: %s" % (cell["name"], "; ".join(passed)),
              file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
