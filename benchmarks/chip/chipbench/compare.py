"""The numbers that decide ``correct``, and their judgement against limits.

A fine-tune cell compares three numbers of the program with the plain
reference on the same weights and rows:

- ``loss_gap``: the largest relative gap of a step's loss, over the
  checked steps;
- ``grad_gap``: the first step's gradient as the optimizer gets it (read
  back from AdamW's first moment and the step's reported global norm),
  by the worst leaf: the gap between the two norms of a leaf, over the
  reference's norm of that leaf or of the median leaf, the larger;
- ``update_gap``: the same for each leaf's change over the checked steps;
- ``grad_gap_median``: the median leaf's gap of the first gradient, where
  the worst leaf swings with rounding from seed to seed.

A cell's limits file names the numbers it compares.

Leaves whose reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone and are left out of both
leaf numbers, by that rule and never by name.
"""
from __future__ import annotations

import statistics

NEGLIGIBLE = 1e-3


def leaf_gaps(prog: dict, ref: dict, keep) -> dict:
    """Each kept leaf's gap of norms, over the reference's norm of that
    leaf or of the median leaf, the larger."""
    med = statistics.median(ref[k] for k in keep)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def finetune_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``{"losses", "grad_norms", "delta_norms"}``."""
    losses = zip(prog["losses"], ref["losses"])
    g_med = statistics.median(ref["grad_norms"].values())
    keep = [k for k, v in ref["grad_norms"].items() if v >= NEGLIGIBLE * g_med]
    grad = leaf_gaps(prog["grad_norms"], ref["grad_norms"], keep)
    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r in losses),
        "grad_gap": max(grad.values()),
        "grad_gap_median": statistics.median(grad.values()),
        "update_gap": max(leaf_gaps(prog["delta_norms"], ref["delta_norms"], keep).values()),
    }


def judge(numbers: dict, limits: dict):
    """``(correct, [(name, value, limit)])``. A number that is missing,
    not finite, or over its limit is not correct."""
    rows, ok = [], True
    for name, spec in limits["numbers"].items():
        value = numbers.get(name)
        good = value is not None and value == value and value <= spec["limit"]
        ok &= good
        rows.append((name, value, spec["limit"]))
    return ok, rows
