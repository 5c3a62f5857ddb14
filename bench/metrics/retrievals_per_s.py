"""Completed requests per second over the window of whole requests."""
from bench.stats import whole_request_rate


def read(run):
    w = run.window
    rate, n, _ = whole_request_rate(
        w.t0, [r.done for r in w.records if r.ok], run.seconds)
    return rate if n else None
