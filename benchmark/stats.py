"""Percentile and spread arithmetic, the benchmark's own copy."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """q-th percentile (0-100) by linear interpolation between order
    statistics (numpy's default rule), of a non-empty sequence."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def iqr_share(values) -> float:
    """Distance between first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives
    them — the spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / abs(statistics.median(values))
