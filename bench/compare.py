"""The numbers that decide ``correct``: the program against the reference.

Both sides give, for the first steps of the same run: each step's loss,
each leaf's norm of the first clipped gradient, and each leaf's norm of
the weights' change after the last step.  A gap of norms is taken leaf by
leaf as |program - reference| over the larger of the reference's norm of
that leaf and of the median leaf, and the worst leaf counts.  Leaves whose
first gradient the reference puts under a thousandth of the median leaf's
move by rounding alone, and are left out of the change.
"""

from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE_GRAD = 1e-3


def _worst_gap(prog: np.ndarray, ref: np.ndarray) -> tuple[float, int]:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    denom = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    denom = np.where(denom > 0, denom, 1.0)
    gaps = np.abs(prog - ref) / denom
    if not np.all(np.isfinite(gaps)):
        return math.inf, int(np.argmax(~np.isfinite(gaps)))
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def numbers(prog: dict, ref: dict) -> dict:
    """{name: value} of the compared numbers, and {name: worst leaf index}."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not math.isfinite(loss_gap):
        loss_gap = math.inf
    grad_gap, grad_leaf = _worst_gap(prog["grad"], ref["grad"])
    counted = np.asarray(ref["grad"]) >= NEGLIGIBLE_GRAD * np.median(ref["grad"])
    idx = np.nonzero(counted)[0]
    change_gap, j = _worst_gap(np.asarray(prog["change"])[idx],
                               np.asarray(ref["change"])[idx])
    return ({"loss_gap": loss_gap, "grad_gap": grad_gap,
             "change_gap": change_gap},
            {"grad_gap": grad_leaf, "change_gap": int(idx[j]),
             "left_out": [int(i) for i in np.nonzero(~counted)[0]]})


def judge(values: dict, limits: dict) -> bool:
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)
