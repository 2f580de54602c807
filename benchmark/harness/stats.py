"""Percentile and spread arithmetic, in one place."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0..1) by linear interpolation between order
    statistics (numpy's default). Raises on an empty sample: a metric
    with nothing behind it is left out, not reported as 0."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile {q} outside [0, 1]")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 0.5)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median — the driver's
    measure of run-to-run noise."""
    m = median(values)
    return (percentile(values, 0.75) - percentile(values, 0.25)) / abs(m)
