"""Model FLOPs per request times completed requests per second, over the
chip's bf16 peak: the whole step's share of the peak."""
from bench.stats import whole_request_rate


def read(run):
    if not run.peaks:
        return None
    w = run.window
    rate, n, _ = whole_request_rate(
        w.t0, [r.done for r in w.records if r.ok], run.seconds)
    if not n:
        return None
    return 100.0 * run.work.flops() * rate / run.peaks["bf16_flops_per_s"]
