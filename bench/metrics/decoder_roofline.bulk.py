"""The decoder forward's share of its roofline: the least time of every pass
of a batch (weights read once per pass, each history once per level, each
beam's SID K/V once; FLOPs at the bf16 peak) over the device time spent in
the decoder's scopes per batch."""
from bench.metrics._trace import DECODER, traced_batches


def read(run):
    n = traced_batches(run)
    t = run.trace
    if t is None or not n or not run.peaks:
        return None
    spent = t.scope_s(DECODER) / n
    if spent <= 0:
        return None
    least = run.work.decoder_least_seconds(
        run.slots, run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
