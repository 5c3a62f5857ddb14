"""Window arithmetic, percentiles, traffic schedules and work counts."""
import json
import math

import numpy as np
import pytest

from bench import spec, stats, traffic, work

PROD = json.loads((spec.BENCH / "configs" / "static-gr-3b.prod.json")
                  .read_text())
V32K = dict(PROD, vocab=32768,
            decoder=dict(PROD["decoder"], vocab_size=32770))


def test_percentile_interpolates_over_all_samples():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(range(101), 90) == 90.0
    assert math.isnan(stats.percentile([], 50))
    assert stats.percentile([1, 2, math.inf], 50) == 2.0
    assert stats.percentile([1, 2, math.inf], 90) == math.inf
    v = np.random.default_rng(0).random(37)
    assert stats.percentile(v, 37) == pytest.approx(np.percentile(v, 37))


def test_whole_request_rate_closes_on_the_first_late_completion():
    # batches of two finish every 1.0 s from t0 = 10
    done = [10 + k for k in range(1, 8) for _ in range(2)]
    rate, n, t_end = stats.whole_request_rate(10.0, done, 3.5)
    assert (n, t_end) == (8, 14.0) and rate == 8 / 4.0
    rate, n, _ = stats.whole_request_rate(10.0, done, 3.0)
    assert n == 6 and rate == 2.0
    assert stats.whole_request_rate(10.0, done, 30.0)[1] == 0


def test_histories_are_exact_for_a_seed():
    h = traffic.history(2**33 + 1, traffic.WINDOW_STREAM, 5, 256, 2048)
    assert h.dtype == np.int32 and h.shape == (256,)
    assert np.array_equal(
        h, traffic.history(2**33 + 1, traffic.WINDOW_STREAM, 5, 256, 2048))
    assert not np.array_equal(
        h, traffic.history(2**33 + 2, traffic.WINDOW_STREAM, 5, 256, 2048))


class _Engine:
    """Serves a queue like the batch engine: ``slots`` at a time, 0.01 s a
    batch, echoing each prompt's first token as its answer."""

    def __init__(self, slots):
        self.slots, self.prompts = slots, []

    def serve(self, q):
        import time

        out = {}
        while len(q):
            t0 = time.monotonic()
            batch = q.pop_batch(self.slots)
            time.sleep(0.01)
            for r in batch:
                self.prompts.append(r.prompt)
                out[r.rid] = {"sids": np.full((2, 3), r.prompt[0]),
                              "scores": np.zeros(2),
                              "queue_s": t0 - r.t_enqueue,
                              "latency_s": time.monotonic() - r.t_enqueue}
        return out


def test_closed_loop_keeps_the_queue_full_and_is_exact():
    from repro.serving.engine import RequestQueue

    eng = _Engine(2)
    w = traffic.closed_loop(eng, RequestQueue, {"outstanding_per_slot": 2},
                            slots=2, seed=9, seconds=0.1, width=8, vocab=50,
                            sid_length=3)
    # the engine always found four queued: it served whole pairs, in order
    assert len(eng.prompts) % 4 == 0 and w.failed == 0
    want = [traffic.history(9, traffic.WINDOW_STREAM, i, 8, 50)
            for i in range(len(eng.prompts))]
    assert all(np.array_equal(a, b) for a, b in zip(eng.prompts, want))
    # the window ends at the first batch done after 0.1 s, and holds it
    assert len(w.records) % 2 == 0 and len(w.batches) == len(eng.prompts) // 2
    assert w.t_end >= w.t0 + 0.1 and w.t_end == max(r.done for r in w.records)
    assert all(d - a == pytest.approx(0.01, abs=0.01) for a, d in w.batches)
    assert w.attempted == len(w.records)


def test_work_counts_match_the_hand_counts():
    r = work.retrieval(PROD)
    assert r.dec.params == pytest.approx(3.6e9, rel=0.01)
    assert r.dec.weight_bytes == pytest.approx(7.2e9, rel=0.01)
    # 256 x 26 layers x (K, V) x 8 heads x 128 x 2 bytes
    assert r.history_kv_bytes() == 256 * 26 * 2 * 8 * 128 * 2
    assert r.history_kv_bytes() == pytest.approx(27.3e6, rel=0.002)
    # (256 prompt + 7 levels x 70 beams) tokens x 2 x 3.6e9
    assert r.flops() == pytest.approx((256 + 7 * 70) * 2 * 3.6e9, rel=0.02)
    assert r.level_bytes(1, 2) == (r.dec.weight_bytes
                                   + 2 * (r.history_kv_bytes()
                                          + 70 * r.dec.kv_bytes_per_token))
    v = work.retrieval(V32K)
    assert v.dec.params - r.dec.params == (32770 - 2050) * 3072
    assert v.flops() > r.flops()


def test_prod_counts_are_the_exact_integers():
    """The counts of ``static-gr-3b.prod``, pinned: a change of the
    yardstick shows here before it shows in a metric."""
    r = work.retrieval(PROD)
    assert r.dec.params == 3_605_173_248
    assert r.dec.weight_bytes == 7_210_346_496
    assert r.dec.kv_bytes_per_token == 106_496
    assert r.history_kv_bytes() == 27_262_976
    assert r.prefill_flops() == 1_853_063_430_144
    assert r.flops() == 5_426_670_403_584
    assert r.level_bytes(1, 2) == 7_279_781_888
    assert r.prefill_bytes(2) == 7_264_872_448
    assert r.decoder_least_seconds(2, 197e12, 819e9) == 0.0814154780523969


def test_dense_weights_are_read_whole_whatever_the_rows():
    d = work.retrieval(PROD).dec
    assert {d.weight_bytes_read(n) for n in (1, 140, 512)} == {
        d.weight_bytes}


def test_least_time_is_the_larger_bound_of_each_pass():
    r = work.retrieval(PROD)
    t = r.decoder_least_seconds(2, 197e12, 819e9)
    passes = [max(2 * r.prefill_flops() / 197e12,
                  r.prefill_bytes(2) / 819e9)]
    passes += [max(2 * r.level_flops(l) / 197e12, r.level_bytes(l, 2) / 819e9)
               for l in range(1, 8)]
    assert t == pytest.approx(sum(passes))
    assert 0.06 < t < 0.1
