"""Shared arithmetic of the trace readers."""

DECODER = r"(^|/)(prefill|decode_logits_L\d+)(/|$)"
BEAM_CACHE = r"(^|/)(cache_beam_tile|carry_gather_L\d+)(/|$)"
CONSTRAINT = r"(^|/)(constraint_topk_L\d+|constraint_mask_L\d+)(/|$)"


def traced_batches(run):
    """Batches of the closed loop that ran wholly inside the trace."""
    t0, t1 = run.window.trace_span
    return sum(1 for a, d in run.window.batches if a >= t0 and d <= t1)
