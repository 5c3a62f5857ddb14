"""The routed experts' share of their roofline in the decode levels: their
least time (the held experts' weights that a level's ``batch * beam`` rows
reach, read once; the FLOPs of the expected routed rows) over their device
time in the decode levels per batch (``bench/metrics/_experts.py``)."""
from bench.counts.deepseek_v2 import least_seconds
from bench.metrics._experts import decode_seconds
from bench.metrics._trace import traced_batches


def read(run):
    n = traced_batches(run)
    if run.trace is None or not n or not run.peaks:
        return None
    s = decode_seconds(run.trace)
    if s <= 0:
        return None
    w = run.work
    level = least_seconds(w.dec.routed_work(run.slots * w.beam), run.peaks)
    return 100.0 * (w.sid_length - 1) * level / (s / n)
