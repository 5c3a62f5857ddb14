"""Device time per batch of the decode levels' KV-cache writes."""
from bench.metrics._decoder import KV_WRITE, ms_per_batch


def read(run):
    return ms_per_batch(run, lambda t: t.scope_s(KV_WRITE))
