"""Metric arithmetic on plain numbers (no clock, no jax)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q`` of
    the samples at or below it.  Raises on an empty sample — a metric with
    nothing behind it is left out, never reported as 0."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"q must be in (0, 1], got {q}")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def median(values) -> float:
    if not values:
        raise ValueError("median of an empty sample")
    return statistics.median(values)


def per_second(count: float, window_s: float) -> float:
    """A rate over all the work and all the time of the window."""
    if window_s <= 0:
        raise ValueError(f"window must be positive, got {window_s}")
    return count / window_s


def steps_per_second(calls_before: int, calls_after: int, engines: int,
                     window_s: float) -> float:
    """Step-entry calls inside the window, per engine, per second."""
    if engines <= 0:
        raise ValueError(f"engines must be positive, got {engines}")
    return per_second((calls_after - calls_before) / engines, window_s)


def ns_to_ms(ns: float) -> float:
    return ns / 1e6
