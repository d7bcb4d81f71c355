"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference, each number beside a limit of its own.

Limits live in ``cells/<cell>.json`` with the readings they were set from
(PERF.md section 2 lists them). An exact comparison has the limit 0.
"""
from __future__ import annotations

import math
from statistics import median


def worst_leaf_gap(program: dict, reference: dict) -> tuple:
    """Per-leaf norms from both sides: the widest gap between the program's
    norm and the reference's (not the norm of a difference), measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger, since some leaves' norms are all but zero."""
    med = median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, med)
        if worst == worst and (gap != gap or gap > worst):
            worst, where = gap, leaf  # a NaN is the worst there is, and stays
    return worst, where


def train_numbers(program: dict, reference: dict) -> dict:
    """``program`` / ``reference``: {"loss": [per step], "grad_norm": {leaf:
    norm}, "change_norm": {leaf: norm}}."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(program["loss"], reference["loss"]))
    g, g_leaf = worst_leaf_gap(program["grad_norm"], reference["grad_norm"])
    c, c_leaf = worst_leaf_gap(program["change_norm"], reference["change_norm"])
    return {"loss_gap": (loss_gap, f"steps 1..{len(reference['loss'])}"),
            "grad_norm_gap": (g, g_leaf), "change_norm_gap": (c, c_leaf)}


def judge(numbers: dict, limits: dict, out=print) -> bool:
    """Print each number beside its limit; true when every one is inside.
    A number with no limit, or a limit with no number, is a fault."""
    ok = set(numbers) == set(limits)
    if not ok:
        out(f"check: numbers {sorted(numbers)} but limits {sorted(limits)}")
    for name in sorted(set(numbers) & set(limits)):
        value, where = numbers[name]
        inside = math.isfinite(value) and value <= limits[name]
        ok = ok and inside
        out(f"check: {name} = {value:.6g} (limit {limits[name]:.6g}, at {where}) "
            f"{'ok' if inside else 'OUTSIDE'}")
    return ok
