"""Median admit-to-complete time of the batch engine's batches."""
from bench.stats import percentile


def read(run):
    spans = [(d - a) * 1e3 for a, d in run.window.batches]
    return percentile(spans, 50) if spans else None
