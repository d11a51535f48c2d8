"""The comparison that decides ``correct`` for a training cell.

The program's first three steps (set-up drives them through the window's
own compiled step and loader) against the plain reference's three steps
on the same weights and batches:

- ``loss``: the widest relative gap of the three steps' losses;
- ``grad``: the first step's gradient as the optimizer got it, worked out
  from the program's first moment after one step (m / (1 - beta1); the
  first step is never clipped), against the reference's gradient, leaf by
  leaf;
- ``change``: the change of the float32 master weights over the three
  steps, as the fourth step would read them, against the reference's,
  leaf by leaf.

For the two per-leaf numbers each leaf's gap is |norm(program) -
norm(reference)| over the larger of the reference's norm of that leaf and
of the median leaf, and the worst leaf counts; ``grad_median`` and
``change_median`` are the median leaf's gap, steady where one small leaf's
noise swings the worst. Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out
of ``change``. A cell's limits name the numbers it compares.
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss", "grad", "change", "grad_median", "change_median")


def _gaps(p, r, keep) -> np.ndarray:
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    denom = np.maximum(r, np.median(r[keep]))
    return np.where(keep, np.abs(p - r) / np.maximum(denom, 1e-30), 0.0)


def leaf_gaps(prog: dict, ref: dict, beta1: float) -> dict:
    """Per-leaf gaps, in the order of the weight layout."""
    g_prog = np.asarray(prog["m_norms"], np.float64) / (1.0 - beta1)
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    moved = g_ref >= 1e-3 * np.median(g_ref)
    return {"grad": _gaps(g_prog, g_ref, np.ones_like(moved)),
            "change": _gaps(prog["change_norms"], ref["change_norms"],
                            moved)}


def numbers(prog: dict, ref: dict, beta1: float) -> dict:
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    gaps = leaf_gaps(prog, ref, beta1)
    moved = gaps["change"] > 0
    return {"loss": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad": float(gaps["grad"].max()),
            "change": float(gaps["change"].max()),
            "grad_median": float(np.median(gaps["grad"])),
            "change_median": float(np.median(gaps["change"][moved]))
            if moved.any() else 0.0}


def judge(nums: dict, limits) -> tuple:
    """(correct, {name: {"value", "limit"}}). Each number that ``limits``
    names must be finite and at most its limit; a number it does not name
    is reported with the limit None and not compared. Without limits the
    run is not correct."""
    out, ok = {}, bool(limits)
    for n in NAMES:
        v = nums.get(n, float("nan"))
        lim = (limits or {}).get(n)
        out[n] = {"value": v, "limit": lim}
        if lim is not None:
            ok = ok and math.isfinite(v) and v <= lim
    return ok, out
