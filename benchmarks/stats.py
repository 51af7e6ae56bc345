"""The benchmark's own arithmetic: percentiles, rates and spreads."""

from __future__ import annotations

import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the order statistics, as numpy's default does."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def rate(count: float, seconds: float) -> float:
    """``count`` per second over a window of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"a rate over {seconds} s")
    return count / seconds


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median: the spread the benchmark's bounds are set from
    (``statistics.quantiles(values, n=4)``, not numpy's)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
