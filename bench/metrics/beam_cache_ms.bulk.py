"""Device time of the M-fold beam cache (tile after prefill, gather at every
level) per batch.  Where the decoder's scopes are in the trace and these
are not, the cache is gone and the metric reads 0."""
from bench.metrics._trace import BEAM_CACHE, DECODER, traced_batches


def read(run):
    n = traced_batches(run)
    if run.trace is None or not n or run.trace.scope_s(DECODER) <= 0:
        return None
    return 1e3 * run.trace.scope_s(BEAM_CACHE) / n
