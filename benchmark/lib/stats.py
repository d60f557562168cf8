"""Percentile arithmetic of the benchmark.

A timing is reported as a median and as the highest percentile that still
has at least ten samples beyond it; the sample count is printed with it. A
named metric such as ``serve_ttft_p95_ms`` always reports its own
percentile, and :func:`describe` says on an earlier line whether the sample
supports it.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

LADDER = (50.0, 90.0, 95.0, 99.0, 99.9)
MIN_BEYOND = 10


def percentile(values: Sequence[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    rank = (len(xs) - 1) * p / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    if rank == lo:
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def highest_supported(n: int) -> Optional[float]:
    """Highest percentile of :data:`LADDER` with >= 10 samples beyond it."""
    best = None
    for p in LADDER:
        if n * (100.0 - p) / 100.0 >= MIN_BEYOND:
            best = p
    return best


def describe(name: str, values: Sequence[float], unit: str = "ms") -> str:
    """One printable line: count, median, highest supported percentile."""
    n = len(values)
    if not n:
        return f"{name}: no samples"
    top = highest_supported(n)
    tail = (f"p{top:g} {percentile(values, top):.3f}" if top is not None
            else "no percentile has ten samples beyond it")
    return (f"{name}: n={n} median {median(values):.3f} {unit}, {tail} "
            f"(highest supported), max {max(values):.3f}")


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median, quartiles as
    ``statistics.quantiles(values, n=4)`` gives them (the bound rule)."""
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
