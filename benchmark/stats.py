"""The arithmetic of a run's numbers: medians, percentiles that refuse a
tail the sample cannot carry, and the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics


def median(values) -> float:
    values = list(values)
    if not values:
        raise ValueError("median of no readings")
    return float(statistics.median(values))


def percentile(values, p: float, min_beyond: int = 10) -> float:
    """The ``p``-th percentile (nearest rank, 50 <= p < 100) of ``values``.
    Refused when fewer than ``min_beyond`` samples lie beyond it: such a
    tail is a few requests, not a property of the system."""
    if not 50 <= p < 100:
        raise ValueError(f"percentile {p} outside [50, 100)")
    ordered = sorted(values)
    rank = math.ceil(len(ordered) * p / 100.0)
    beyond = len(ordered) - rank
    if beyond < min_beyond:
        raise ValueError(
            f"p{p:g} of {len(ordered)} samples has {beyond} beyond it; "
            f"{min_beyond} are needed"
        )
    return float(ordered[rank - 1])


def percentile_or_none(values, p: float, min_beyond: int = 10):
    """``percentile``, or None where it is refused: the reading is then
    left out of the line, as a reader's that found nothing to read."""
    try:
        return percentile(values, p, min_beyond)
    except ValueError:
        return None


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — the spread a bound is set from (``statistics.quantiles``)."""
    q1, _, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / statistics.median(values)
