"""A tiny cell of the same shape as the real ones, for tests on the CPU.

Its limits were read from the program at this size on the CPU: sound bf16
runs give a score gap of at most about 0.02 nats and a selection gap of at
most about 0.005; the float8 control gives about 0.14 and 0.06.
"""
import dataclasses

from bench import spec

CONFIG = {
    "decoder": {"base": "static-gr", "n_layers": 2, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                "vocab_size": 34, "tie_embeddings": True,
                "rope_theta": 10000.0, "norm_eps": 1e-05,
                "dtype": "bfloat16"},
    "reference": "dense_gqa", "vocab": 32, "sid_length": 4, "beam": 8,
    "history": 16, "dense_d": 2, "constraint_sids": 400,
    "requests_per_chip": {"batch": 2}, "check_requests": 4,
    "limits": {"violations": 0, "score_gap": 0.05, "select_gap": 0.02},
}
MIXES = {
    "bulk": {"engine": "batch", "loop": "closed", "outstanding_per_slot": 2},
}


def cell(mix: str, **decoder):
    """The benchmark's first cell, with the tiny configuration and ``mix``."""
    cfg = dict(CONFIG, decoder=dict(CONFIG["decoder"], **decoder))
    c = spec.load("gr3b-prod.bulk")
    return dataclasses.replace(c, config=cfg, traffic=MIXES[mix])
