"""Device time of the constraint step per decode level per batch (the
paper's per-step unit)."""
from bench.metrics._trace import CONSTRAINT, traced_batches


def read(run):
    n = traced_batches(run)
    t = run.trace
    if t is None or not n:
        return None
    s = t.scope_s(CONSTRAINT)
    return 1e3 * s / (n * run.work.sid_length) if s > 0 else None
