"""Percentiles of a sample, as the benchmark reports them."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100), linear between order statistics.

    The same definition as numpy's default (``method="linear"``): rank
    ``q / 100 * (n - 1)`` in the sorted sample, interpolated.
    """
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)
