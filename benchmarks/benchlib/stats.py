"""Percentile and spread arithmetic, kept with the benchmark."""

from __future__ import annotations

import math
import statistics
from typing import List, Sequence


def percentile(samples: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least p% of
    all samples at or below it."""
    if not samples:
        raise ValueError("no samples")
    s = sorted(samples)
    return s[max(0, min(len(s) - 1, math.ceil(p / 100.0 * len(s)) - 1))]


def iqr_share(values: List[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median: the spread the bounds are set from."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
