"""Device time per batch of the decode levels' weight-reading work: token
lookup, q/k/v and output projections, FFN and logits, each with its norm."""
from bench.metrics._decoder import WEIGHTS, ms_per_batch


def read(run):
    return ms_per_batch(run, lambda t: t.scope_s(WEIGHTS))
