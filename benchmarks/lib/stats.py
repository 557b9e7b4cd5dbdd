"""Percentile arithmetic of the benchmark.  An empty sample raises: a
percentile of nothing is not 0 and not NaN."""

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default method), ``q`` in
    [0, 100]."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile wants 0 <= q <= 100, got {q}")
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if any(math.isnan(x) for x in xs):
        raise ValueError("percentile of a sample that holds NaN")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def mean(values: Sequence[float]) -> float:
    xs = [float(v) for v in values]
    if not xs:
        raise ValueError("mean of an empty sample")
    return sum(xs) / len(xs)
