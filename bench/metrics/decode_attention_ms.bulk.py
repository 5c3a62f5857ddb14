"""Device time per batch of the decode levels' attention over the cached
history."""
from bench.metrics._decoder import ATTENTION, ms_per_batch


def read(run):
    return ms_per_batch(run, lambda t: t.scope_s(ATTENTION))
