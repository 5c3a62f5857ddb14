"""Device time per batch of the decode levels that lies in none of their
named pieces: the compiler's copies and the layer loop's own ops."""
from bench.metrics._decoder import ANY_PIECE, LEVEL, ms_per_batch


def read(run):
    return ms_per_batch(
        run, lambda t: t.scope_s(LEVEL) - t.scope_s(ANY_PIECE))
