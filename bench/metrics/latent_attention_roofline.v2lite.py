"""The absorbed latent attention's share of its roofline in the decode
levels: the least time of each level's attention (each request's history
latents read once per layer, each beam's suffix latents once; the FLOPs
``2 H ctx (2 kv_lora + rope)`` per row and layer) over the device time in
scope ``attention`` under ``decode_logits_L*``, per batch."""
from bench.counts.deepseek_v2 import least_seconds
from bench.metrics._decoder import ATTENTION
from bench.metrics._trace import traced_batches


def read(run):
    n = traced_batches(run)
    t = run.trace
    if t is None or not n or not run.peaks or t.scope_s(ATTENTION) <= 0:
        return None
    w = run.work
    least = sum(least_seconds(w.dec.latent_attention_work(
        run.slots, w.beam, w.history, level), run.peaks)
        for level in range(1, w.sid_length))
    return 100.0 * least / (t.scope_s(ATTENTION) / n)
