"""Arithmetic of the measured window: percentiles and rates."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} is not in (0, 100]")
    ordered = sorted(values)
    rank = math.ceil(q / 100.0 * len(ordered))
    return float(ordered[rank - 1])


def rate(amount: float, start: float, end: float) -> float:
    """Amount per second over ``[start, end]``."""
    if end <= start:
        raise ValueError(f"empty window [{start}, {end}]")
    return amount / (end - start)


def stamped_window(stamps: Sequence[tuple]) -> tuple:
    """``stamps`` are ``(time, count)`` pairs taken after a cumulative count
    of work items was done.  Returns ``(items, seconds)`` between the first
    stamp and the last: the items finished after the first stamp."""
    if len(stamps) < 2:
        raise ValueError(f"need two stamps for a window, got {len(stamps)}")
    (t0, n0), (t1, n1) = stamps[0], stamps[-1]
    if t1 <= t0 or n1 <= n0:
        raise ValueError(f"stamps {stamps[0]} .. {stamps[-1]} span no work")
    return n1 - n0, t1 - t0
