"""The benchmark's arithmetic: the spread a bound is set from, as the builder reads
it and as the driver's check does. Pure functions; nothing here touches JAX."""

from __future__ import annotations

import statistics


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the median,
    with the quartiles as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spread_without_farthest(values) -> float:
    """The spread with the run farthest from the median left out where that
    narrows it: how the driver's check reads a set for tightness."""
    med = statistics.median(values)
    farthest = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = [v for i, v in enumerate(values) if i != farthest]
    return min(spread(values), spread(rest)) if len(rest) >= 2 else spread(values)
