"""Percentile arithmetic kept with the benchmark."""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between closest ranks."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(float(v) for v in values)
    rank = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def with_failed_as_worst(values: Iterable[Optional[float]], worst: float) -> list:
    """A latency per request attempted: a request without one (failed, refused or
    unfinished) takes ``worst``, which is never better than any latency measured."""
    got = [v for v in values if v is not None]
    worst = max([worst, *got])
    return [worst if v is None else v for v in values]


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, as the
    driver reads it (``statistics.quantiles(values, n=4)``)."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
