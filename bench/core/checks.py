"""The comparisons that decide ``correct``, and their limits.

Each cell's limits live in ``bench/limits/<cell>.json`` ({number: limit}),
set from readings of sound runs and of the control on the chip (PERF.md
gives the readings).  A reading passes when it is at most its limit.
"""

from __future__ import annotations

import json
import statistics

from .spec import BENCH

# leaves whose reference gradient is under this share of the median
# leaf's move by round-off alone and are not compared
NEGLIGIBLE_LEAF = 1e-3


def leaf_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """The worst leaf's gap between the program's norm and the
    reference's, as a share of the reference's norm of that leaf or of
    the median leaf, whichever is larger; leaves in ``skip`` are not
    compared.  Returns (gap, leaf)."""
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k, r in ref.items():
        if k in skip:
            continue
        g = abs(prog[k] - r) / max(r, med)
        if g >= worst:
            worst, where = g, k
    return worst, where


def negligible_leaves(grad_ref: dict) -> list[str]:
    """Leaves whose reference gradient is under ``NEGLIGIBLE_LEAF`` of the
    median leaf's: they move by round-off alone, in the gradient and in
    the change, and are compared in neither."""
    med = statistics.median(grad_ref.values())
    return sorted(k for k, r in grad_ref.items() if r < NEGLIGIBLE_LEAF * med)


def load_limits(cell: str) -> dict:
    path = BENCH / "limits" / f"{cell}.json"
    return json.loads(path.read_text()) if path.exists() else {}


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and {number: {"value", "limit"}}; a number without a
    limit, or a run without numbers, is not correct."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in readings.items()}
    ok = bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return ok, checks
