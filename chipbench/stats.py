"""The benchmark's own arithmetic on samples."""

from __future__ import annotations

import math
import statistics


def percentile(xs, q: float) -> float:
    """The q-th percentile (0..100), linear between the closest ranks."""
    xs = sorted(float(x) for x in xs)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(xs) -> float:
    return percentile(xs, 50.0)


def quartile_spread(xs) -> float:
    """(Q3 - Q1) / median with ``statistics.quantiles(n=4)``: the spread
    the bounds are set from."""
    q1, _, q3 = statistics.quantiles([float(x) for x in xs], n=4)
    return (q3 - q1) / statistics.median(xs)
