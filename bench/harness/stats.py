"""Tails and rates, always over every sample given.

Percentiles use the nearest-rank rule on the full sorted sample: no
bucketing, no bounded history, no interpolation.  A missing sample (a
request that failed or never produced its token) is ``math.inf``, so it
counts as missing every limit and lands at the top of the tail.
"""
from __future__ import annotations

import math
from typing import Iterable


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of every value."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"percentile rank {q} outside (0, 100]")
    rank = math.ceil(q / 100.0 * len(xs))
    return xs[max(rank, 1) - 1]


def rate(count: float, seconds: float) -> float:
    """Work per second over a whole window."""
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds
