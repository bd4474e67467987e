"""The comparison that decides `correct`: the program's first round against
the configuration's plain reference, number by number, each with a limit.
"""
from __future__ import annotations

import math
import statistics


def norm_gap(program: dict, reference: dict) -> tuple:
    """Worst leaf of | ||program|| - ||reference|| | over the larger of the
    reference's norm of that leaf and of the median leaf (some gradients are
    all but zero). Both arguments map leaf name -> norm. Returns (gap, leaf)."""
    floor = statistics.median(reference.values())
    worst, leaf = 0.0, ""
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, floor, 1e-30)
        if gap != gap:  # a NaN is the worst there is
            return gap, name
        if gap > worst:
            worst, leaf = gap, name
    return worst, leaf


def first_round_checks(program: dict, reference: dict, limits: dict) -> list:
    """`program` and `reference` each hold "loss", "update_norms" (leaf ->
    norm), "momentum_norms" (one such dict per worker) and "probe" (one
    array per worker: the momentum of the reference's PROBE_LEAF). Returns the list of
    {"name", "value", "limit", "ok", ...} that `correct` is the `all` of."""
    checks = [{"name": "loss_gap",
               "value": abs(program["loss"] - reference["loss"]),
               "program": program["loss"], "reference": reference["loss"]}]
    gap, leaf = norm_gap(program["update_norms"], reference["update_norms"])
    checks.append({"name": "update_gap", "value": gap, "leaf": leaf})
    gaps = [norm_gap(p, r) + (w,) for w, (p, r) in enumerate(
        zip(program["momentum_norms"], reference["momentum_norms"]))]
    gap, leaf, worker = max(gaps, key=lambda g: (math.isnan(g[0]), g[0]))
    checks.append({"name": "momentum_gap", "value": gap, "leaf": leaf,
                   "worker": worker})
    diffs = [_rel_diff(p, r) for p, r in zip(program["probe"], reference["probe"])]
    worst = max(range(len(diffs)), key=lambda w: (math.isnan(diffs[w]), diffs[w]))
    checks.append({"name": "probe_diff", "value": diffs[worst], "worker": worst})
    for c in checks:
        c["limit"] = limits[c["name"]]
    return [judged(c) for c in checks]


def _rel_diff(program, reference) -> float:
    import numpy as np
    p, r = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    return float(np.linalg.norm(p - r) / max(np.linalg.norm(r), 1e-30))


def judged(check: dict) -> dict:
    """A NaN never passes; a limit of 0 is an exact comparison."""
    check["ok"] = bool(check["value"] <= check["limit"])
    return check


def exact(name: str, value: float) -> dict:
    return judged({"name": name, "value": float(value), "limit": 0.0})
