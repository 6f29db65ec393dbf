"""Percentiles the way the benchmark reports them."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics (numpy's
    default), without numpy so that tests of it stand alone."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
