"""The comparison that decides `correct`: the program's first steps against
the plain reference's, number by number, each against its limit.

Readings on each side::

    {"loss": [float per step],          # the loss each step reports
     "grad": {leaf: norm},              # the first gradient as the
                                        # optimizer gets it (clipped)
     "change": {leaf: norm}}            # |params after the steps - before|

Compared numbers (all relative, worst case):

- ``loss_gap``: max over the steps of |L - L_ref| / |L_ref|.
- ``grad_gap``: max over leaves of | |g| - |g_ref| | / max(|g_ref|, median
  leaf |g_ref|) -- a gap of norms, not the norm of a difference, against
  the larger of the leaf's own norm and the median leaf's, since some
  gradients are all but zero.
- ``change_gap``: the same for the parameters' change, over the leaves
  whose reference gradient is at least a thousandth of the median leaf's
  (a leaf whose gradient is nought to rounding moves by round-off alone).
"""
from __future__ import annotations

import math
import statistics

MOVED_FLOOR = 1e-3


def loss_gap(prog: list, ref: list) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def norm_gap(prog: dict, ref: dict, leaves=None) -> tuple:
    """(worst gap, its leaf) over ``leaves`` (default: all of ref's)."""
    leaves = list(ref) if leaves is None else list(leaves)
    med = statistics.median(ref[k] for k in ref)
    worst, at = 0.0, None
    for k in leaves:
        p = prog.get(k, float("nan"))
        gap = abs(p - ref[k]) / max(ref[k], med)
        if not gap <= worst:          # NaN counts as worst
            worst, at = gap, k
    return worst, at


def moved(ref_grad: dict) -> list:
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= MOVED_FLOOR * med]


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """(checks, correct): checks maps each compared number to
    {"value", "limit", ...}; correct when every value is finite and within
    its limit."""
    steps = len(ref["loss"])
    grad, grad_at = norm_gap(prog["grad"], ref["grad"])
    change, change_at = norm_gap(prog["change"], ref["change"],
                                 moved(ref["grad"]))
    checks = {
        "loss_gap": {"value": loss_gap(prog["loss"][:steps], ref["loss"]),
                     "limit": limits["loss_gap"]},
        "grad_gap": {"value": grad, "limit": limits["grad_gap"],
                     "leaf": grad_at},
        "change_gap": {"value": change, "limit": limits["change_gap"],
                       "leaf": change_at},
    }
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return checks, correct


def lines(checks: dict) -> list:
    """One line per compared number: name, value and limit."""
    out = []
    for name, c in checks.items():
        at = f" (leaf {c['leaf']})" if c.get("leaf") else ""
        out.append(f"check {name} {c['value']!r} limit {c['limit']!r}{at}")
    return out
