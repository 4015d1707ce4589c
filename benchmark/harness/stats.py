"""Medians, percentiles and the spread that bounds are set from.

Plain arithmetic on lists of floats, kept here so that every PR reduces its
samples the same way. ``spread`` is the driver's own measure: the distance
between the quartiles over the median.
"""

from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics; ``nan`` for no samples."""
    if not samples:
        return math.nan
    xs = sorted(samples)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 50.0)


def spread(samples: Sequence[float]) -> float:
    """(third quartile - first quartile) / median."""
    m = median(samples)
    return (percentile(samples, 75.0) - percentile(samples, 25.0)) / m
