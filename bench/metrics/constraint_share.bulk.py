"""The constraint step's share of the device's busy time."""
from bench.metrics._trace import CONSTRAINT


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    s = t.scope_s(CONSTRAINT)
    return 100.0 * s / t.busy_s if s > 0 else None
