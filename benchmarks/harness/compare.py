"""The comparison that decides ``correct``: each number compared has a limit
of its own (data, in the cell's file), and every run prints each beside it."""

from __future__ import annotations

import math
from statistics import median


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple:
    """The worst leaf's gap between the program's norm and the reference's
    (not the norm of their difference), measured against the reference's norm
    of that leaf or of the median leaf, whichever is larger. Returns
    ``(gap, leaf name)``."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    med = median(ref.values())
    worst, where = 0.0, ""
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, med)
        if not gap <= worst:  # NaN counts as worst
            worst, where = gap, name
    return worst, where


def nearly_zero_gradient_leaves(ref_gnorm: dict) -> set:
    """Leaves whose gradient is nought to rounding in the REFERENCE: under a
    thousandth of the median leaf's. Adam moves them by round-off alone, so
    they are left out of the parameters' change (by this rule, not by name)."""
    med = median(ref_gnorm.values())
    return {k for k, g in ref_gnorm.items() if g < 1e-3 * med}


def train_checks(prog: dict, ref: dict, limits: dict) -> list:
    """``prog``/``ref``: ``{"losses": [..], "gnorm": {leaf: x}, "dnorm":
    {leaf: x}}`` of the same steps. One check a number that the cell's file
    gives a limit; a number without one is read all the same and carried with
    ``"limit": None`` (printed as "not compared": it has no upper reading, so
    a limit could only fail sound runs - PERF.md names it)."""
    numbers = []
    for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), start=1):
        numbers.append({"name": f"loss{i}_rel_gap",
                        "value": abs(p - r) / abs(r)})
    g, g_at = worst_leaf_gap(prog["gnorm"], ref["gnorm"])
    numbers.append({"name": "grad1_norm_gap", "value": g, "at": g_at})
    skip = nearly_zero_gradient_leaves(ref["gnorm"])
    d, d_at = worst_leaf_gap(prog["dnorm"], ref["dnorm"], skip)
    numbers.append({"name": "dparam_norm_gap", "value": d, "at": d_at})
    for n in numbers:
        n["limit"] = limits.get(n["name"])
    return numbers


def compared(checks: list) -> list:
    return [c for c in checks if c["limit"] is not None]


def verdict(checks: list) -> bool:
    checks = compared(checks)
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks)


def format_checks(checks: list) -> str:
    """Not compared first, so that the LAST lines are the numbers compared,
    each beside its limit."""
    lines = [f"read {c['name']}: {c['value']:.6g} (not compared)"
             for c in checks if c["limit"] is None]
    lines += [
        f"check {c['name']}: {c['value']:.6g} (limit {c['limit']:.6g})"
        + (f" at {c['at']}" if c.get("at") else "")
        + ("" if math.isfinite(c["value"]) and c["value"] <= c["limit"]
           else "  <-- FAILS")
        for c in compared(checks)]
    return "\n".join(lines)
