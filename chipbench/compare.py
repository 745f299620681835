"""The numbers compared with the reference, each a widest gap.

Norms are taken leaf by leaf: the gap between the program's norm of a leaf
and the reference's, over the larger of the reference's norm of that leaf
and of the median leaf (some gradients are all but zero)."""
from __future__ import annotations

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: its change is not compared
STILL_LEAF = 1e-3


def worst_leaf(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    denom = np.maximum(want, np.median(want))
    return float(np.max(np.abs(got - want) / denom))


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``losses``, ``grad`` and ``delta``; ``ref``
    also ``grad_raw``, the unclipped per-leaf norms."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape:
        return {"loss_gap": float("inf"), "grad_gap": float("inf"),
                "update_gap": float("inf")}
    moved = ref["grad_raw"] >= STILL_LEAF * np.median(ref["grad_raw"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": worst_leaf(prog["grad"], ref["grad"]),
        "update_gap": worst_leaf(np.asarray(prog["delta"])[moved],
                                 np.asarray(ref["delta"])[moved]),
    }


def judge(numbers: dict, limits: dict) -> bool:
    """Every number finite and within its limit."""
    return all(np.isfinite(v) and v <= limits[k] for k, v in numbers.items())
