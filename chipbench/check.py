"""The comparison that decides `correct` for a training cell: the timed
path's first three steps against the plain reference's.

Readings on both sides: each step's loss, the first gradient, the
parameters' change after three steps, the running state's change after
three. A tree of leaves is compared by its worst leaf: the gap between the
two sides' norms of that leaf over the reference's norm of it or of the
median leaf, whichever is larger (some gradients are all but zero).
"""

import math
import statistics

import numpy as np


def _norm(x):
    return float(np.linalg.norm(np.asarray(x, np.float64).ravel()))


def leaf_gaps(program, reference, leaves=None):
    """{leaf: gap} over `leaves` (default: all of the reference's)."""
    leaves = sorted(reference) if leaves is None else sorted(leaves)
    ref_norms = {k: _norm(reference[k]) for k in leaves}
    floor = statistics.median(ref_norms.values()) if leaves else 0.0
    gaps = {}
    for k in leaves:
        scale = max(ref_norms[k], floor)
        gaps[k] = abs(_norm(program[k]) - ref_norms[k]) / scale if scale > 0 \
            else (0.0 if _norm(program[k]) == 0 else math.inf)
    return gaps


def worst_leaf_gap(program, reference, leaves=None):
    """(gap, leaf name) of the worst leaf; a NaN gap is the worst there
    is."""
    worst, where = -1.0, None
    for k, gap in leaf_gaps(program, reference, leaves).items():
        if not gap <= worst:
            worst, where = gap, k
    return (worst, where) if where is not None else (None, None)


def median_leaf_gap(program, reference, leaves=None):
    gaps = list(leaf_gaps(program, reference, leaves).values())
    return statistics.median(gaps) if gaps else None


def moved_leaves(ref_grad1):
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's: the others move by round-off alone and are left out of
    the change after three steps."""
    norms = {k: _norm(v) for k, v in ref_grad1.items()}
    floor = 1e-3 * statistics.median(norms.values())
    return [k for k, n in norms.items() if n >= floor]


def readings(program, reference):
    """{short name: number} of every number compared. `program` and
    `reference` each hold `losses`, `grad1`, `delta3`, `state3`, keyed by
    the reference's leaf names."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out["loss%d" % (i + 1)] = abs(a - b) / abs(b)
    moved = moved_leaves(reference["grad1"])
    trees = [("grad1", None), ("delta3", moved)]
    if reference["state3"]:
        trees.append(("state3", None))
    for tree, leaves in trees:
        out[tree], out[tree + "_leaf"] = worst_leaf_gap(
            program[tree], reference[tree], leaves)
        out[tree + "_med"] = median_leaf_gap(
            program[tree], reference[tree], leaves)
    return out


def decide(numbers, limits):
    """({name: {"value", "limit"}}, correct): every limit has to find its
    number, finite and no larger than the limit."""
    compared, correct = {}, True
    for name, limit in sorted(limits.items()):
        value = numbers.get(name)
        ok = value is not None and math.isfinite(value) and value <= limit
        correct = correct and ok
        compared[name] = {"value": value, "limit": limit}
    return compared, correct
