"""Device time per batch of the decode levels' routed experts: dispatch,
grouped matmuls and combine (``bench/metrics/_experts.py``)."""
from bench.metrics._experts import decode_seconds
from bench.metrics._trace import traced_batches


def read(run):
    n = traced_batches(run)
    if run.trace is None or not n:
        return None
    s = decode_seconds(run.trace)
    return 1e3 * s / n if s > 0 else None
