"""The comparison that decides ``correct``.

The program's first call of ``train_one_round`` (16 optimizer steps here;
its state shows only at the call's end) against the plain reference's same
round. Each number has a limit of its own in ``limits/<cell>.json``; one
that the file leaves out is worked out and printed but not compared
(PERF.md says which and why):

``loss``   the round's loss, gap as a share of the reference's;
``count``  optimizer steps taken (exact);
``grad``   Adam's first moment after the call — the gradients as the
           optimizer got them, clipped — by the worst leaf;
``grad2``  the root of Adam's second moment, by the worst leaf;
``change`` the global adapters' change over the call, by the worst leaf,
           leaving out leaves whose reference gradient is under a
           thousandth of the median leaf's.

"By the worst leaf" is the gap between the two norms of a leaf (not the
norm of the difference) over the reference's norm of that leaf or of the
median leaf, whichever is larger.
"""
from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3  # of the median leaf's gradient


def _norms(tree: dict, root: bool = False) -> dict:
    out = {}
    for k, v in tree.items():
        v = np.asarray(v, np.float64)
        out[k] = float(np.sqrt(np.sum(np.abs(v)))) if root else float(
            np.sqrt(np.sum(v * v)))
    return out


def worst_leaf(got: dict, want: dict, keep=None) -> dict:
    """``{"gap", "leaf", "leaves"}`` of two ``{path: norm}`` dicts."""
    if set(got) != set(want):
        return {"gap": math.inf, "leaf": "leaf sets differ", "leaves": 0}
    keys = [k for k in want if keep is None or k in keep]
    median = float(np.median([want[k] for k in keys]))
    worst, at = 0.0, ""
    for k in keys:
        gap = abs(got[k] - want[k]) / max(want[k], median, 1e-300)
        if not gap <= worst:  # also catches nan
            worst, at = gap, k
    return {"gap": worst, "leaf": at, "leaves": len(keys)}


def numbers(got: dict, want: dict) -> dict:
    """``got``: ``{"loss", "mu", "nu", "count", "lora"}`` of the side under
    test; ``want``: the reference's ``state()`` with its ``loss``."""
    rms = want["grad_rms"]
    floor = NEGLIGIBLE * float(np.median(list(rms.values())))
    moved = {k for k, v in rms.items() if v >= floor}
    init = want["init_lora"]
    delta = lambda side: _norms(
        {k: np.asarray(side["lora"][k], np.float64) - init[k] for k in init})
    out = {
        "loss": {"gap": abs(got["loss"] - want["loss"]) / abs(want["loss"])},
        "count": {"gap": float(abs(got["count"] - want["count"]))},
        "grad": worst_leaf(_norms(got["mu"]), _norms(want["mu"])),
        "grad2": worst_leaf(_norms(got["nu"], root=True),
                            _norms(want["nu"], root=True)),
        "change": worst_leaf(delta(got), delta(want), keep=moved),
    }
    out["change"]["left_out"] = len(rms) - len(moved)
    return out


def judge(nums: dict, limits: dict) -> dict:
    """Each number beside its limit; ``correct`` when every one holds."""
    compared = {}
    for name, limit in limits["limits"].items():
        value = nums[name]["gap"]
        compared[name] = {"value": value, "limit": limit,
                          "ok": bool(value <= limit)}
        if "leaf" in nums[name]:
            compared[name]["leaf"] = nums[name]["leaf"]
    return {"correct": all(c["ok"] for c in compared.values()),
            "compared": compared}
