"""Every metric reader under bench/metrics/ on windows made by hand."""
import math

import pytest

from bench import spec, traffic, work
from bench.run import Run
from bench.tests import _tiny

READERS = sorted(p.stem for p in (spec.BENCH / "metrics").glob("*.py")
                 if not p.stem.startswith("_"))


def _metric(name):
    return spec.Metric(name, "", False, None, None).reader()


def _closed():
    recs = []
    for k in range(6):  # three batches of two, 1 s each, from t0 = 100
        for j in range(2):
            r = traffic.Record(2 * k + j, None, 100.0 + k)
            r.admitted, r.done = 100.0 + k, 101.0 + k
            r.sids = r.scores = 0
            recs.append(r)
    batches = [(100.0 + k, 101.0 + k) for k in range(6)]
    return traffic.Window(100.0, 106.0, recs, batches, 12, 0, (100.0, 106.0))


def _run(window, seconds):
    cell = _tiny.cell("bulk")
    return Run(cell, window, seconds, 12.5, 2, work.retrieval(cell.config),
               {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_a_number_or_nothing(name):
    v = _metric(name)(_run(_closed(), 5.0))
    assert v is None or (isinstance(v, float) and math.isfinite(v))


def test_closed_window_numbers():
    run = _run(_closed(), 5.0)
    assert _metric("retrievals_per_s")(run) == 2.0
    assert _metric("batch_ms.bulk")(run) == pytest.approx(1000.0)
    assert _metric("setup_s")(run) == 12.5
    mfu = _metric("mfu.bulk")(run)
    assert mfu == pytest.approx(100 * run.work.flops() * 2.0 / 197e12)
    assert _metric("decoder_roofline.bulk")(run) is None  # no trace


def test_trace_readers_on_a_traced_window():
    """Two whole batches inside the trace: the scope readers divide their
    device time by the batches and levels, and the shares stay in 0-100."""
    from bench import trace as T

    D = "/device:TPU:0"
    ms = 1_000_000
    ops = []
    for b in range(2):  # per batch: 400 ms decoder, 100 ms cache, 8 ms mask
        t = (100.0 + b) * 1e9
        ops += [T.Op(D, t, 400 * ms, "f", "jit(r)/prefill/dot"),
                T.Op(D, t + 400 * ms, 100 * ms, "g", "jit(r)/carry_gather_L1/x"),
                T.Op(D, t + 500 * ms, 8 * ms, "h",
                     "jit(r)/constraint_topk_L1/y")]
    run = _run(_closed(), 5.0)
    run.window.trace_span = (100.0, 102.0)
    run.trace = T.summarize(T.Trace(ops, []), run.window.trace_span)
    assert _metric("beam_cache_ms.bulk")(run) == pytest.approx(100.0)
    assert _metric("constraint_ms_per_step.bulk")(run) == pytest.approx(
        8.0 / run.work.sid_length)
    assert _metric("constraint_share.bulk")(run) == pytest.approx(
        100 * 8 / 508)
    assert _metric("device_idle_share.bulk")(run) == pytest.approx(
        100 * (1 - 1.016 / 2.0))
    least = run.work.decoder_least_seconds(2, 197e12, 819e9)
    assert _metric("decoder_roofline.bulk")(run) == pytest.approx(
        100 * least / 0.4)
