"""Device time of the routed experts inside the decode levels.

The layer names its dispatch, grouped matmuls and combine ``moe_experts``,
but the TPU compiler lowers ``jax.lax.ragged_dot`` to kernels whose
``op_name`` is their own (``ragged-dot-none``, ``ragged-dot-metadata``),
without the scope path.  Those that run inside a decode level (within the
time of an op of a ``decode_logits_L*`` scope, such as the level's layer
loop) count with the level's ``moe_experts`` ops; the prefill's do not.
"""
import collections
import re

from bench.metrics._decoder import LEVEL, under_level

EXPERTS = re.compile(under_level("moe_experts"))
KERNEL = re.compile(r"^ragged-dot")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def decode_seconds(trace) -> float:
    """Seconds per chip of the routed experts in the decode levels."""
    level = re.compile(LEVEL)
    levels, kernels, mine = (collections.defaultdict(list) for _ in "abc")
    for o in trace.ops:
        iv = (o.start_ns, o.start_ns + o.dur_ns)
        if level.search(o.scope):
            levels[o.device].append(iv)
        if EXPERTS.search(o.scope):
            mine[o.device].append(iv)
        elif KERNEL.search(o.scope):
            kernels[o.device].append(iv)
    for dev, ks in kernels.items():
        spans = _union(levels[dev])
        mine[dev] += [k for k in ks
                      if any(s <= k[0] < e for s, e in spans)]
    n = len({o.device for o in trace.ops}) or 1
    return sum(e - s for iv in mine.values()
               for s, e in _union(iv)) * 1e-9 / n
