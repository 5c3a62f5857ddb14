"""Scope patterns of the pieces of a decode level, and their reader.

``decode_step`` names each piece of its work (``embed``, ``qkv_proj``,
``kv_write``, ``attention``, ``out_proj``, ``ffn``, ``unembed``); beam
search runs it under ``decode_logits_L<n>``.  Each pattern asks for that
ancestor, so the prefill's layers, which carry the same names, do not
count.
"""
from bench.metrics._trace import traced_batches

LEVEL = r"(^|/)decode_logits_L\d+(/|$)"


def under_level(*names: str) -> str:
    """Ops of a decode level whose scope path holds one of ``names``."""
    return (r"(^|/)decode_logits_L\d+/(.*/)?(" + "|".join(names)
            + r")(/|$)")


WEIGHTS = under_level("embed", "qkv_proj", "out_proj", "ffn", "unembed")
ATTENTION = under_level("attention")
KV_WRITE = under_level("kv_write")
ANY_PIECE = under_level("embed", "qkv_proj", "kv_write", "attention",
                        "out_proj", "ffn", "unembed")


def ms_per_batch(run, seconds_of):
    """``seconds_of(trace)`` in ms per traced batch; None where the trace
    holds no named piece of a decode level (a program without them)."""
    n = traced_batches(run)
    t = run.trace
    if t is None or not n or t.scope_s(ANY_PIECE) <= 0:
        return None
    return 1e3 * seconds_of(t) / n
