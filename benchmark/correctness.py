"""The comparison that decides ``correct`` for a training cell.

Both sides hand in the same readings of the first optimizer steps: each
step's loss, the per-leaf norm of the first gradient as the optimizer
got it, and the per-leaf norm of the parameters' change over the steps.
Every number compared is a *gap*, and each has a limit of its own (from
``benchmark/limits/<cell>.json``; PERF.md gives the readings each limit
was set from).

A configuration may name a *twin*: the same program with its two bf16
lanes stated as float32, driven through its first step by the same
wiring and compared with the reference at those lanes (``twin_*``).  At
the stated bf16 lanes two sound float32 programs already differ by a few
thousandths in the gradient, which is as much as float32 parts turned to
bfloat16 or half of the pair batch left out would change it; at float32
lanes the program sits within 2e-5 of the reference and both stand a
hundred times clear or more.  The twin shares every line of the step with the
timed program but the lanes' dtype.
"""

from __future__ import annotations

import statistics

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: left out of the change
TINY_GRADIENT_SHARE = 1e-3


def worst_leaf_gap(got: dict, want: dict, leaves=None) -> float:
    """The worst leaf's gap between the two sides' norms, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    leaves = sorted(want) if leaves is None else leaves
    floor = statistics.median(want[k] for k in sorted(want))
    return max(abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
               for k in leaves)


def worst_leaf_difference(got: dict, want: dict, leaves=None) -> float:
    """The worst leaf's norm of the difference between the two sides'
    arrays, against the same denominator as :func:`worst_leaf_gap`: it
    sees what changes a leaf's direction and leaves its norm alone."""
    norms = {k: float(np.sqrt(np.sum(np.square(v, dtype=np.float64))))
             for k, v in want.items()}
    floor = statistics.median(norms[k] for k in sorted(norms))
    leaves = sorted(want) if leaves is None else leaves
    return max(float(np.sqrt(np.sum(np.square(
        got[k].astype(np.float64) - want[k].astype(np.float64)))))
        / max(norms[k], floor, 1e-30) for k in leaves)


def moved_leaves(ref_grad_norms: dict) -> list:
    floor = TINY_GRADIENT_SHARE * statistics.median(ref_grad_norms.values())
    return sorted(k for k, v in ref_grad_norms.items() if v >= floor)


def training_gaps(got: dict, want: dict) -> dict:
    if sorted(got["grad_norms"]) != sorted(want["grad_norms"]):
        raise ValueError("the two sides hold different parameters")
    gaps = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        gaps[f"loss_gap_step{i}"] = abs(a - b) / max(abs(b), 1e-30)
    gaps["grad_norm_gap"] = worst_leaf_gap(got["grad_norms"],
                                           want["grad_norms"])
    moved = moved_leaves(want["grad_norms"])
    gaps["change_norm_gap"] = worst_leaf_gap(
        got["change_norms"], want["change_norms"], moved)
    gaps["grad_difference"] = worst_leaf_difference(got["grads"],
                                                    want["grads"])
    return gaps


def twin_gaps(got: dict, want: dict) -> dict:
    """The twin's first step: its loss and its gradient."""
    gaps = training_gaps(got, want)
    return {"twin_loss_gap": gaps["loss_gap_step1"],
            "twin_grad_norm_gap": gaps["grad_norm_gap"],
            "twin_grad_difference": gaps["grad_difference"]}


def compare_training(got: dict, want: dict, limits: dict,
                     twin=None) -> list:
    """[(name, value, limit)] for every number the cell's limits name.
    ``twin`` is (got, want) of the twin's first step.  A number that is
    not finite can never be within its limit; a limit that names a
    number nobody read is an error."""
    gaps = training_gaps(got, want)
    if twin is not None:
        gaps.update(twin_gaps(*twin))
    return [(name, gaps[name], float(limit))
            for name, limit in limits.items()]


def all_within(checks) -> bool:
    return all(value == value and value <= limit
               for _, value, limit in checks)
