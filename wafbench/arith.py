"""Metric arithmetic of the benchmark: percentiles, rates, spreads."""

from __future__ import annotations

import math
import statistics


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the sample at or below it. No interpolation: every value
    reported is a latency that some request really had."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def rate(count: int, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds


def quartile_spread(values: list[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver takes it (``statistics.quantiles(n=4)``): what
    the bounds in BENCHMARK.json were set from (PERF.md, section 2)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
