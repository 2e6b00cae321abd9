"""The numbers that decide ``correct``, and their limits.

A run compares its program's first chunk of rounds with the plain
reference on the same weights, data and draws:

* ``loss_gap`` — the largest relative gap between the program's and the
  reference's ``train_loss``, over the chunk's rounds;
* ``change_gap`` — over the leaves, the largest gap between the norms of
  the program's and the reference's model change ``x_R - x_0``, as a share
  of the larger of the reference's norm for that leaf and its median leaf
  norm;
* ``kept_change_gap`` — the relative gap between the norms, over the
  whole model, of the change over the entries that the final model keeps
  (``x_R != 0``).  FedComLoc-Com ships TopK of the iterate, so most of
  ``x_R - x_0`` is the TopK's zeroing of ``x_0``; on the kept entries the
  change is what the local steps and the control variates moved.  Most
  leaves keep no local update at all in bf16, so a leaf's norm here counts
  a handful of entries; the whole model's is steady;
* ``cv_gap`` — the same whole-model gap for the control variates ``h``,
  where the reference moves them (a cohort of one never does: the server
  mean is the one client's iterate).

Leaves whose first gradient in the reference is under a thousandth of the
median leaf's move by round-off alone and are left out of the last three.
Each cell's limits are in ``limits/<cell>.json``; a number without a limit
there is reported and not judged.
"""

from __future__ import annotations

import numpy as np

#: a leaf whose first gradient is under this share of the median leaf's
#: moves by round-off alone and is left out of the norm gaps
ROUNDOFF_LEAF = 1e-3


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    prog = np.asarray(prog, np.float64)[keep]
    ref = np.asarray(ref, np.float64)[keep]
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref)
    with np.errstate(divide="ignore", invalid="ignore"):
        # a leaf that neither side moved has no gap
        return float(np.max(np.where(gap > 0, gap / scale, 0.0)))


def total_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    """|P - R| / R for the whole-model norms P and R of the kept leaves'
    per-leaf norms."""
    p = float(np.sqrt(np.sum(np.square(np.asarray(prog, np.float64)[keep]))))
    r = float(np.sqrt(np.sum(np.square(np.asarray(ref, np.float64)[keep]))))
    if r == 0:
        return 0.0 if p == 0 else float("inf")
    return abs(p - r) / r


def numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``loss`` (per round) and the per-leaf norms
    ``change``, ``kept_change`` and ``cv``; ``ref`` also ``grad0`` (per-leaf
    first-gradient norms).  Returns the numbers this pair can give."""
    out = {}
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    out["loss_gap"] = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    g0 = np.asarray(ref["grad0"], np.float64)
    keep = g0 >= ROUNDOFF_LEAF * np.median(g0)
    out["change_gap"] = norm_gap(prog["change"], ref["change"], keep)
    out["kept_change_gap"] = total_gap(prog["kept_change"],
                                       ref["kept_change"], keep)
    if np.any(np.asarray(ref["cv"])[keep] > 0):
        out["cv_gap"] = total_gap(prog["cv"], ref["cv"], keep)
    return out


def judge(nums: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every number with a limit; a number
    passes when it is finite and at most its limit."""
    return {name: {"value": nums.get(name, float("nan")),
                   "limit": spec["limit"]}
            for name, spec in limits.items()}


def passed(checks: dict) -> bool:
    return bool(checks) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
