"""Percentile and rate arithmetic of the end-to-end metrics."""

from __future__ import annotations

import math

FAILED_MS = 600_000.0  # a failed request counts as slower than any


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it. No interpolation, so it is always a latency
    some request really had."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    k = max(1, math.ceil(q * len(s)))
    return s[k - 1]


def latencies_ms(records, t_start: float, t_end: float) -> list[float]:
    """Client-side latency of every request SENT in the window; a failed
    one counts as FAILED_MS."""
    return [
        (t1 - t0) * 1000.0 if status == 200 else FAILED_MS
        for _, t0, t1, status, _, _ in records
        if t_start <= t0 < t_end
    ]


def completed_rate(records, t_start: float, t_end: float) -> float:
    """Good replies whose last byte came inside the window, per second
    of the window."""
    done = sum(1 for _, _, t1, status, _, _ in records if status == 200 and t_start <= t1 < t_end)
    return done / (t_end - t_start)
