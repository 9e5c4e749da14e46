"""Percentiles and spreads, as the benchmark and its records use them."""

from __future__ import annotations

import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile (Python's
    `statistics.quantiles(n=4)`) as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_range(values: Sequence[float]) -> float:
    """Range of the runs after leaving out the one farthest from the
    median, as a share of the median (how the driver reads tightness)."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))[:-1] or list(values)
    return (max(kept) - min(kept)) / med
