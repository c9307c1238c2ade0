"""Order statistics the benchmark reports. Plain Python, no numpy, so
the arithmetic that decides a metric reads the same everywhere."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics, numpy's default rule."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def spread(values) -> float:
    """Distance between the quartiles over the median: the driver's
    measure of how far runs of one cell disagree."""
    return (percentile(values, 75.0) - percentile(values, 25.0)) / median(values)
