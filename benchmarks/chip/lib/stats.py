"""Percentiles and spreads, the same arithmetic in every PR."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default), on a copy."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def spread(values) -> float:
    """Distance between the quartiles over the median: what the driver
    reads as a metric's run-to-run spread."""
    iqr = percentile(values, 75.0) - percentile(values, 25.0)
    if iqr == 0:
        return 0.0          # also where the median is 0 (a count that is)
    return iqr / median(values)
