"""The comparison that decides ``correct``.

Each number is a gap between what the program produced and what the plain
reference produced from the same seed: a loss as a share of the
reference's, or, per parameter leaf, the gap between the two norms as a
share of the larger of that leaf's reference norm and the median leaf's;
the worst leaf counts, or for ``delta_median`` the median leaf. A cell's
limits file names the numbers it compares. Leaves whose first reference gradient is under a
thousandth of the median leaf's are left out of the leaf numbers: they move
by round-off alone.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

QUIET_LEAF = 1e-3


def relative(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def leaf_gaps(prog: dict, ref: dict, ref_grad: dict) -> dict:
    """Per-leaf gap of norms over the leaves the reference moves."""
    gmed = float(np.median(list(ref_grad.values())))
    keep = [k for k in ref if ref_grad[k] >= QUIET_LEAF * gmed]
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keep}


def training_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold ``loss`` (per compared step),
    ``grad`` (first-gradient norm per leaf) and ``delta`` (norm of each
    leaf's change after the compared steps)."""
    losses = [relative(a, b) for a, b in zip(prog["loss"], ref["loss"])]
    grad = leaf_gaps(prog["grad"], ref["grad"], ref["grad"])
    delta = leaf_gaps(prog["delta"], ref["delta"], ref["grad"])
    return {"loss1": losses[0], "loss": max(losses),
            "grad": max(grad.values()), "delta": max(delta.values()),
            "delta_median": float(np.median(list(delta.values()))),
            "_grad_leaf": max(grad, key=grad.get),
            "_delta_leaf": max(delta, key=delta.get)}


def limits_for(cell: str) -> dict:
    path = os.path.join(os.path.dirname(__file__), "limits", f"{cell}.json")
    with open(path) as f:
        return json.load(f)


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, checks): every number that has a limit must lie at or
    under it; a number that is not finite fails."""
    checks, ok = {}, True
    for name, spec in sorted(limits.items()):
        value = float(numbers[name])
        passed = bool(np.isfinite(value) and value <= spec["limit"])
        ok = ok and passed
        checks[name] = {"value": value, "limit": spec["limit"]}
    return ok, checks


def report(checks: dict) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    for name, c in checks.items():
        mark = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {mark}",
              file=sys.stderr, flush=True)
