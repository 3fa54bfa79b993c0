"""The one traffic generator: a training cell's pool of host batches, made
from the seed and the numbers in `workloads/<cell>.json`.

A batch is a list of per-sample tuples, one column per entry of the
configuration's `inputs`, which is what a v2 reader yields. Every seed
gets the same sizes (batch, pool, the multiset of sequence lengths) in
another order, so the seed moves the values and never the work.
"""

import numpy as np


def _column(spec, rows, lengths, rng):
    kind = spec["kind"]
    if kind == "dense":
        return list(rng.standard_normal((rows, spec["dim"]), dtype=np.float32))
    if kind == "index":
        return [int(v) for v in rng.integers(0, spec["classes"], rows)]
    if kind == "index_sequence":
        return [rng.integers(0, spec["vocab"], n).astype(np.int32)
                for n in lengths]
    raise ValueError("unknown input kind %r" % (kind,))


def make_pool(inputs, workload, seed):
    """`pool_batches` batches of `batch` samples each. `lengths`
    (`{"min": a, "max": b}`) spreads sequence lengths evenly from a to b
    over the rows of a batch, shuffled by the seed; labels are uniform
    draws."""
    rng = np.random.default_rng(int(seed))
    rows = int(workload["batch"])
    span = workload.get("lengths")
    pool = []
    for _ in range(int(workload["pool_batches"])):
        lengths = None
        if span is not None:
            lengths = np.rint(np.linspace(span["min"], span["max"],
                                          rows)).astype(int)
            rng.shuffle(lengths)
        columns = [_column(spec, rows, lengths, rng)
                   for spec in inputs]
        pool.append(list(zip(*columns)))
    return pool
