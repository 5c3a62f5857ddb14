"""Order statistics and window arithmetic shared by the harness and readers.

Percentiles use linear interpolation between order statistics (numpy's
default), over every sample given: a tail is the tail of all requests.
"""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values``, interpolated linearly
    between order statistics as numpy's default does; an infinite sample
    (a request with no result) makes any percentile that reaches it
    infinite.  NaN when empty."""
    v = np.sort(np.asarray(list(values), np.float64))
    if v.size == 0:
        return math.nan
    pos = q / 100.0 * (v.size - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi:
        return float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def whole_request_rate(t0: float, done_times, seconds: float):
    """Completed requests per second over a window of whole requests.

    The window opens at ``t0`` and closes at the first completion at or
    after ``t0 + seconds``; the rate counts every completion up to and
    including that instant and divides by the time from ``t0`` to it, so a
    batch that straddles the nominal end is neither cut nor rounded away.
    Returns ``(rate, n_completed, t_end)``; ``(nan, 0, nan)`` when nothing
    completed after the nominal end.
    """
    times = np.sort(np.asarray(list(done_times), np.float64))
    late = times[times >= t0 + seconds]
    if late.size == 0:
        return math.nan, 0, math.nan
    t_end = float(late[0])
    n = int(np.sum(times <= t_end))
    return n / (t_end - t0), n, t_end

