"""The comparisons that decide `correct`: each yields a number, printed in
every run beside its limit (from the cell's workload file; the readings each
limit was set from are in PERF.md)."""

from __future__ import annotations

import math
import statistics


def relative_gap(program: float, reference: float) -> float:
    return abs(program - reference) / abs(reference)


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """The largest gap, over parameter leaves, between the program's norm
    and the reference's norm of that leaf (not the norm of a difference),
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger: some leaves' gradients are all but zero."""
    if set(program) != set(reference):
        raise ValueError("program and reference disagree on the leaves")
    floor = statistics.median(reference.values())
    worst, where = -1.0, ""
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, floor)
        if not math.isfinite(gap):
            gap = math.inf
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


class Verdict:
    """Collects the numbers compared; `correct` is all of them in limit."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value: float, limit: float, note: str = "") -> None:
        ok = bool(math.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": ok, "note": note})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)
