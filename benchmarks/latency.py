"""Percentiles of open-loop latencies. A request's latency runs from the
time it was DUE (not from when it was sent), so a stall charges every
request it delayed."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks of the sorted values (numpy's default method)."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def window_latencies(result: dict) -> tuple[list[float], int, int]:
    """(latencies in ms of the correct responses, attempted, failed) over
    the requests DUE inside the window of a loadgen result."""
    lat = [
        ms for ms, inside in zip(result["latency_ms"], result["in_window"]) if inside
    ]
    good = [ms for ms in lat if ms is not None]
    return good, len(lat), len(lat) - len(good)


def in_flight_at(result: dict, t: float) -> int:
    """Requests due at or before t (seconds after t0) and unanswered at t."""
    return sum(
        1 for due, done in zip(result["due"], result["done_at"])
        if due <= t and (done is None or done > t)
    )
