"""Percentiles and spreads, kept with the benchmark.

`percentile` is the nearest-rank rule the serving layer's own registry
uses (a copy, so no PR to the program can change what the benchmark
reads). `spread` is the quartile distance over the median by which
bounds are set.
"""
from __future__ import annotations

import math
import statistics


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile of an unsorted sample list (q in [0, 1]).
    Infinite samples (failed requests) sort last and count like any other."""
    if not samples:
        raise ValueError("percentile of no samples")
    ordered = sorted(samples)
    rank = min(len(ordered) - 1, max(0, int(q * len(ordered) + 0.5) - 1))
    return ordered[rank]


def spread(values) -> float:
    """(third quartile - first quartile) / median, as
    `statistics.quantiles(values, n=4)` gives the quartiles."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else math.inf
