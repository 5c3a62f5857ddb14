"""Telemetry subsystem (DESIGN.md §9): metrics math, exposition formats,
engine instrumentation, and the off-hot-path guarantee.

The load-bearing claims:
  1. Registry primitives are correct (histogram bucket math + quantile
     interpolation, labeled counters/gauges, Prometheus text exposition,
     JSONL snapshots, the scrape endpoint).
  2. ``StepTimer`` separates warmup compilation from steady-state trials
     and flags retracing; the recompile monitor turns "hot swaps never
     recompile" into a counter that must read 0.
  3. Engines record per-request latency without changing results:
     retrieval through a fully-instrumented ``ServingEngine`` is
     bit-identical to calling the retriever directly (metrics cannot touch
     the jitted computation).
"""
import dataclasses
import json
import logging
import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import TransitionMatrix
from repro.constraints import (
    AsyncRefresher,
    CatalogDelta,
    ConstraintRegistry,
    ItemCatalog,
    category_allowlist,
    freshness_window,
)
from repro.decoding import DecodePolicy
from repro.models import transformer
from repro.observability import (
    MetricsRegistry,
    StepTimer,
    record_policy,
    start_http_server,
)
from repro.serving.engine import RequestQueue, ServingEngine
from repro.serving.generative_retrieval import GenerativeRetriever
from conftest import make_sids

L = 4


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------
def test_counter_labels_and_validation():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc(lane="0")
    c.inc(2, lane="1")
    c.inc(lane="1")
    assert c.value(lane="0") == 1 and c.value(lane="1") == 3
    assert c.total() == 4
    assert c.value(lane="missing") == 0
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same object; kind mismatch is an error
    assert reg.counter("reqs_total") is c
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")
    with pytest.raises(ValueError):
        reg.counter("bad name!")


def test_gauge_set_add():
    g = MetricsRegistry().gauge("depth")
    g.set(5, lane="a")
    g.add(-2, lane="a")
    assert g.value(lane="a") == 3


def test_histogram_bucket_math_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    h.observe(100.0)  # lands in the +Inf overflow bucket
    assert h.count() == 5
    assert h.sum() == pytest.approx(106.05)
    # cumulative counts per bucket edge: 1, 3, 4, 5
    # p50 -> rank 2.5 inside (0.1, 1.0]: linear interpolation within bucket
    q50 = h.quantile(0.5)
    assert 0.1 < q50 <= 1.0
    # p100 falls in the overflow bucket -> clamped to the top finite edge
    assert h.quantile(1.0) == pytest.approx(10.0)
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 1.0))


def test_histogram_quantile_interpolates_within_bucket():
    h = MetricsRegistry().histogram("x", buckets=(0.0, 10.0))
    for _ in range(100):
        h.observe(5.0)
    # all mass in (0, 10]: median interpolates to mid-bucket, not an edge
    assert 4.0 < h.quantile(0.5) < 6.0


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "total requests")
    c.inc(3, lane="a\\b\n\"q\"")
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    text = reg.render_prometheus()
    assert "# HELP req_total total requests" in text
    assert "# TYPE req_total counter" in text
    assert "# TYPE lat_seconds histogram" in text
    assert "# TYPE depth gauge" in text
    # label escaping: backslash, newline, quote
    assert 'lane="a\\\\b\\n\\"q\\""' in text
    # cumulative buckets and the +Inf edge equal to _count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text
    assert "depth 7" in text


def test_snapshot_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c_total").inc(2, k="v")
    reg.histogram("h_seconds").observe(0.25)
    p = tmp_path / "snap.jsonl"
    reg.write_snapshot(p)
    reg.write_snapshot(p)
    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert len(lines) == 2
    snap = lines[-1]
    assert snap["counters"]["c_total"] == {'{k="v"}': 2}
    (hrec,) = snap["histograms"]["h_seconds"].values()
    assert hrec["count"] == 1 and hrec["sum"] == pytest.approx(0.25)
    assert "p99" in hrec


def test_http_metrics_endpoint():
    reg = MetricsRegistry()
    reg.counter("up_total").inc()
    server, port = start_http_server(reg, port=0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "up_total 1" in body
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# timing + recompile detection
# ---------------------------------------------------------------------------
def test_step_timer_splits_warmup_and_steady_compiles():
    reg = MetricsRegistry()
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = np.arange(7, dtype=np.float32)  # fresh shape: first call compiles
    stats = StepTimer("t", reg, warmup=2, trials=5).measure(f, x)
    assert stats.trials == 5
    assert stats.warmup_compiles >= 1  # warmup absorbed the compile
    assert stats.steady_compiles == 0  # trials measured a stable executable
    assert 0 < stats.median < 1.0
    assert stats.p99 >= stats.p50
    assert reg.histogram("step_wall_seconds").count(step="t") == 5
    assert reg.counter("step_compiles_total").value(
        step="t", phase="warmup") >= 1
    s = stats.summary()
    assert s["steady_compiles"] == 0 and s["name"] == "t"


# ---------------------------------------------------------------------------
# profiler names: decoder scopes in the HLO, engine spans on the host
# ---------------------------------------------------------------------------
PIECES = ("embed", "qkv_proj", "kv_write", "attention", "out_proj", "ffn",
          "unembed")


def _level_op_names(hlo_text):
    """(opcode, op_name) of every HLO op whose op_name lies in a decode
    level."""
    out = []
    for line in hlo_text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name and re.search(r"(^|/)decode_logits_L\d+/", name.group(1)):
            opcode = re.search(r"= \S+ ([\w-]+)\(", line)
            out.append((opcode.group(1) if opcode else "", name.group(1)))
    return out


@pytest.mark.parametrize("defer", [False, True], ids=["eager", "deferred"])
def test_decode_level_ops_carry_a_piece_name(defer, rng):
    """Every matmul of a decode level is named by the piece of the layer
    it belongs to, and each of the seven names reaches the optimized HLO
    under ``decode_logits_L1``, where the trace readers of a decode level's
    pieces look.  The retriever's step writes the SID suffix, so the
    deferred write is compiled as the bare decode step inside a level's
    scope."""
    cfg = dataclasses.replace(smoke_config("static-gr"),
                              defer_cache_write=defer)
    params = transformer.init_params(cfg, jax.random.key(0))
    if defer:
        cache = jax.eval_shape(lambda: transformer.init_cache(cfg, 2, 12))

        def level(p, c, t):
            with jax.named_scope("decode_logits_L1"):
                return transformer.decode_step(p, c, t, cfg)

        lowered = jax.jit(level).lower(
            params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32))
    else:
        V = 16
        tm = TransitionMatrix.from_sids(make_sids(rng, 40, V, 3), V)
        gr = GenerativeRetriever(params, cfg, tm, sid_length=3, sid_vocab=V,
                                 beam_size=4)
        lowered = gr._retrieve_jit.lower(
            params, jax.ShapeDtypeStruct((2, 8), jnp.int32), gr.policy, None)
    ops = _level_op_names(lowered.compile().as_text())
    dots = [n for op, n in ops if op == "dot"]
    assert dots
    for n in dots:
        assert set(n.split("/")) & {"qkv_proj", "attention", "out_proj",
                                    "ffn", "unembed"}, n
    level_one = [p for p in (n.split("/") for _, n in ops)
                 if "decode_logits_L1" in p]
    for piece in PIECES:
        assert any(piece in n for n in level_one), piece


def test_batch_engine_host_spans_in_order(small_lm, rng, tmp_path):
    """One traced batch: the host plane holds the engine's phases, in the
    order they run, with the store install inside admission."""
    params, cfg = small_lm
    eng, _, _ = _build_engine(params, cfg, rng, batch_size=2)

    def one_batch():
        q = RequestQueue()
        for i in range(2):
            q.submit(rng.integers(0, cfg.vocab_size, (8,)), n_tokens=L,
                     constraint_id=i)
        return eng.serve(q)

    one_batch()  # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        assert all("sids" in r for r in one_batch().values())
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    spans.setdefault(e.name, (e.start_ns,
                                              e.start_ns + e.duration_ns))
    order = ["serve.admit", "retrieve.dispatch", "retrieve.readback",
             "serve.record"]
    assert set(order) <= set(spans), sorted(spans)
    starts = [spans[n][0] for n in order]
    assert starts == sorted(starts)
    for a, b in zip(order, order[1:]):
        assert spans[a][1] <= spans[b][0], (a, b)
    admit, install = spans["serve.admit"], spans["serve.install"]
    assert admit[0] <= install[0] and install[1] <= admit[1]


# ---------------------------------------------------------------------------
# policy plan + record_policy
# ---------------------------------------------------------------------------
def test_policy_plan_info_and_gauges(rng):
    sids = make_sids(rng, 300, 32, L)
    policy = DecodePolicy.static(TransitionMatrix.from_sids(sids, 32,
                                                            dense_d=2))
    info = policy.plan_info(beams=8)
    assert [r["level"] for r in info] == list(range(L))
    assert all(r["backend"] for r in info)
    for r in info:
        assert r["topk"] == policy.supports_topk_at(r["level"])
        if r["topk"]:
            assert r["candidate_width"] >= 1
    reg = MetricsRegistry()
    record_policy(reg, policy, beams=8)
    g = reg.gauge("decode_level_backend_info")
    assert g.value(level="0", backend=info[0]["backend"]) == 1
    last = info[L - 1]
    assert reg.gauge("decode_level_candidate_width").value(
        level=str(L - 1)) == last["candidate_width"]
    assert reg.gauge("decode_level_topk").value(
        level=str(L - 1)) == int(last["topk"])


# ---------------------------------------------------------------------------
# engine instrumentation
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("stablelm-12b")
    params = transformer.init_params(cfg, jax.random.key(0))
    return params, cfg


def _catalog(rng, cfg, n):
    sids = np.unique(make_sids(rng, n, cfg.vocab_size, L, clustered=True),
                     axis=0)
    m = sids.shape[0]
    return ItemCatalog(sids=sids, age_days=rng.uniform(0, 60, m),
                       category=rng.integers(0, 4, m))


def _build_engine(params, cfg, rng, *, headroom=0.5, n_items=250,
                  batch_size=4):
    cat = _catalog(rng, cfg, n_items)
    reg = ConstraintRegistry(cfg.vocab_size, headroom=headroom)
    reg.register("fresh", freshness_window(45))
    reg.register("cats", category_allowlist(0, 1, 2))
    store = reg.build(cat)
    retr = GenerativeRetriever(params, cfg, store, sid_length=L,
                               sid_vocab=cfg.vocab_size, beam_size=4)
    eng = ServingEngine(params, cfg, batch_size=batch_size, max_len=24,
                        retriever=retr, registry=reg)
    return eng, reg, cat


def test_engine_records_request_latency_metrics(small_lm, rng):
    params, cfg = small_lm
    eng, reg, _ = _build_engine(params, cfg, rng)
    q = RequestQueue()
    rids = [q.submit(rng.integers(0, cfg.vocab_size, (8,)), n_tokens=L,
                     constraint_id=i % 2) for i in range(6)]
    results = eng.serve(q)
    assert set(results) == set(rids)
    m = eng.metrics
    # every result carries its own measured latency split
    for r in results.values():
        assert r["latency_s"] >= r["queue_s"] >= 0.0
    # per-lane request counters add up; latency histograms saw every request
    c = m.counter("serving_requests_total")
    assert c.total() == 6
    assert c.value(lane="0") == 3 and c.value(lane="1") == 3
    h = m.histogram("serving_request_latency_seconds")
    assert h.count(lane="0") + h.count(lane="1") == 6
    assert m.histogram("serving_request_queue_seconds").count(lane="0") > 0
    assert m.counter("serving_batches_total").total() >= 2  # 6 reqs, batch 4
    assert m.counter("serving_decode_steps_total").total() > 0
    # occupancy of the LAST batch: 2 of 4 slots
    assert m.gauge("serving_batch_occupancy").value() == pytest.approx(0.5)
    # queue drained: every lane gauge reads 0
    assert m.gauge("serving_queue_depth").value(lane="0") == 0
    # the plan gauges were published at construction
    assert m.gauge("decode_level_topk").value(level="0") in (0, 1)
    # Prometheus rendering of live engine metrics does not blow up
    assert "serving_request_latency_seconds_bucket" in m.render_prometheus()


def test_engine_results_bit_identical_with_metrics_on(small_lm, rng):
    """Telemetry must not touch device work: engine == direct retriever."""
    params, cfg = small_lm
    eng, reg, _ = _build_engine(params, cfg, rng)
    prompts = [rng.integers(0, cfg.vocab_size, (8,)) for _ in range(4)]
    q = RequestQueue()
    rids = [q.submit(p, n_tokens=L, constraint_id=i % 2)
            for i, p in enumerate(prompts)]
    results = eng.serve(q)
    # direct path: same retriever, same store, no engine/metrics around it
    store, _ = reg.current()
    direct = GenerativeRetriever(params, cfg, store, sid_length=L,
                                 sid_vocab=cfg.vocab_size, beam_size=4)
    hist = np.zeros((4, 12), np.int32)
    for i, p in enumerate(prompts):
        hist[i, :8] = p
    cids = np.asarray([i % 2 for i in range(4)], np.int32)
    beams, scores = direct.retrieve(hist, constraint_ids=cids)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(results[rid]["sids"], beams[i])
        np.testing.assert_array_equal(results[rid]["scores"], scores[i])


def test_recompile_monitor_silent_across_hot_swaps(small_lm, rng):
    params, cfg = small_lm
    eng, reg, cat = _build_engine(params, cfg, rng, n_items=300)
    q = RequestQueue()
    for i in range(4):
        q.submit(rng.integers(0, cfg.vocab_size, (8,)), n_tokens=L,
                 constraint_id=i % 2)
    eng.serve(q)  # first batch: compiles are EXPECTED here
    for _ in range(2):  # two hot swaps, served with metrics enabled
        n = cat.sids.shape[0]
        rm = cat.sids[rng.choice(n, 10, replace=False)]
        add = _catalog(rng, cfg, 25)
        reg.swap_delta(CatalogDelta(
            added=add, removed_sids=rm))
        for i in range(4):
            q.submit(rng.integers(0, cfg.vocab_size, (8,)), n_tokens=L,
                     constraint_id=i % 2)
        eng.serve(q)
    m = eng.metrics
    assert eng.cold_swaps == 0
    # 2 churn swaps + the first batch's initial store install (None -> v1)
    assert m.counter("serving_hot_swaps_total").total() == 3
    # the monitored invariant: zero compiles outside expected windows
    assert m.counter("serving_recompiles_total").value(expected="false") == 0


def test_recompile_monitor_counts_cold_swap_as_expected(small_lm, rng):
    params, cfg = small_lm
    eng, reg, _ = _build_engine(params, cfg, rng, headroom=0.0, n_items=60,
                                batch_size=2)
    q = RequestQueue()
    for i in range(2):
        q.submit(rng.integers(0, cfg.vocab_size, (8,)), n_tokens=L,
                 constraint_id=i % 2)
    eng.serve(q)
    big = _catalog(rng, cfg, 1200)  # outgrows the zero-headroom envelope
    reg.swap(big)
    for i in range(2):
        q.submit(rng.integers(0, cfg.vocab_size, (8,)), n_tokens=L,
                 constraint_id=i % 2)
    eng.serve(q)
    m = eng.metrics
    # the cold swap recompiled, but inside an expected window
    assert eng.cold_swaps == 1
    assert m.counter("serving_cold_swaps_total").total() == 1
    assert m.counter("serving_recompiles_total").value(expected="false") == 0
    assert m.counter("serving_recompiles_total").value(expected="true") >= 1


def test_registry_publishes_headroom_and_utilization(small_lm, rng):
    params, cfg = small_lm
    eng, reg, _ = _build_engine(params, cfg, rng)
    m = reg.metrics
    assert 0 < m.gauge("constraint_envelope_states_used_frac").value() <= 1
    assert 0 < m.gauge("constraint_envelope_edges_used_frac").value() <= 1
    assert m.gauge("constraint_store_bytes").value() > 0
    assert m.gauge("constraint_slot_sids").value(slot="fresh") > 0
    util = m.gauge("constraint_slot_utilization_frac").value(slot="fresh")
    # the paper's actual<=u_max holds at production scale; toy tries carry
    # edge-slab padding that can nudge the ratio past 1, so just sanity-bound
    assert 0 < util < 4.0
    assert m.counter("constraint_swaps_total").value(
        kind="build", cold="true") == 1
    assert m.histogram("constraint_refresh_seconds").count(kind="build") == 1


def test_async_refresher_failure_logs_and_counts(rng, caplog):
    sids = np.unique(make_sids(rng, 100, 16, L), axis=0)
    n = sids.shape[0]
    cat = ItemCatalog(sids=sids, age_days=rng.uniform(0, 60, n),
                      category=rng.integers(0, 4, n))
    reg = ConstraintRegistry(16, headroom=0.5)
    reg.register("all", lambda c: np.ones(c.sids.shape[0], bool))
    reg.build(cat)
    bad = CatalogDelta(removed_sids=sids[:, :2])  # wrong SID width
    # arm caplog BEFORE submitting: the worker thread logs the failure
    # before it resolves the future
    with caplog.at_level(logging.ERROR, "repro.constraints.refresh"):
        with AsyncRefresher(reg) as ref:
            fut = ref.apply_delta_async(bad)
            with pytest.raises(ValueError):
                fut.result(timeout=60)
            assert ref.drain(timeout=60)
    assert ref.failed == 1 and ref.applied == 0
    assert isinstance(ref.last_error, ValueError)
    assert ref.metrics.counter("refresh_ops_total").value(
        kind="delta", outcome="failed") == 1
    assert any("refresh delta failed" in r.getMessage()
               for r in caplog.records)
