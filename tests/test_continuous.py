"""Continuous-batching engine: allocator/scheduler invariants, level-free
masking bit-identity, and the differential fuzz vs ``ServingEngine``.

The load-bearing assertion is the fuzz: per-request ``(sids, scores)`` out
of the step-boundary engine must equal the sequence-boundary engine's
output **bit-for-bit** — across mixed tenants, duplicate prompts (prefix
sharing), mid-flight admissions, and a registry hot-swap.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.constraints import (
    ConstraintRegistry,
    category_allowlist,
    freshness_window,
    synthetic_catalog,
)
from repro.core import TransitionMatrix
from repro.decoding import DecodePolicy
from repro.models import transformer
from repro.scenarios import gr_model_config
from repro.serving.continuous import (
    ContinuousServingEngine,
    PagedKVAllocator,
    PrefixShareTable,
    StepScheduler,
)
from repro.serving.engine import RequestQueue, ServingEngine
from repro.serving.generative_retrieval import GenerativeRetriever
from conftest import make_sids


# ---------------------------------------------------------------------------
# paged allocator: refcount invariant under arbitrary interleavings
# ---------------------------------------------------------------------------
def test_allocator_directed_errors():
    a = PagedKVAllocator(4)  # pages 1..3
    p = a.alloc(2)
    with pytest.raises(MemoryError):
        a.alloc(2)
    a.retain(p)
    a.release(p)
    a.check()
    a.release(p)
    with pytest.raises(ValueError):
        a.release([p[0]])  # double free
    with pytest.raises(ValueError):
        a.retain([p[0]])  # retain of unowned page
    a.check()
    assert a.n_free == 3 and a.n_referenced == 0


def test_allocator_property_random_interleavings():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.integers(0, 2 ** 16), min_size=1, max_size=120),
           st.integers(4, 24))
    def run(ops, n_pages):
        a = PagedKVAllocator(n_pages)
        model: dict[int, int] = {}  # page -> refcount (the oracle)
        held: list[int] = []
        for op in ops:
            kind = op % 3
            if kind == 0:  # alloc 1..2 pages
                n = 1 + (op // 3) % 2
                if n <= a.n_free:
                    for pg in a.alloc(n):
                        model[pg] = 1
                        held.append(pg)
                else:
                    with pytest.raises(MemoryError):
                        a.alloc(n)
            elif kind == 1 and held:  # retain a random held page
                pg = held[(op // 3) % len(held)]
                a.retain([pg])
                model[pg] += 1
                held.append(pg)
            elif kind == 2 and held:  # release a random held ref
                pg = held.pop((op // 3) % len(held))
                a.release([pg])
                model[pg] -= 1
                if model[pg] == 0:
                    del model[pg]
            a.check()
            assert a.n_referenced == len(model)
            for pg, c in model.items():
                assert a.refcount(pg) == c
        # full drain: never leaks
        for pg in held:
            a.release([pg])
        a.check()
        assert a.n_free == n_pages - 1 and a.n_referenced == 0

    run()


def test_prefix_share_table_refcounts_and_lru():
    a = PagedKVAllocator(8)
    t = PrefixShareTable(a, capacity=2)
    rows = [np.full(4, i, np.int32) for i in range(3)]
    pages = [a.alloc(2) for _ in range(3)]
    logits = [np.full(5, float(i), np.float32) for i in range(3)]
    t.insert(rows[0], pages[0], logits[0])
    t.insert(rows[1], pages[1], logits[1])
    assert a.refcount(pages[0][0]) == 2  # caller + table
    assert t.contains(rows[0]) and not t.contains(rows[2])
    hit = t.lookup(rows[0])
    assert hit is not None
    got_pages, got_logits = hit
    assert tuple(got_pages) == tuple(pages[0])
    np.testing.assert_array_equal(got_logits, logits[0])
    assert a.refcount(pages[0][0]) == 3  # lookup retained for the caller
    a.release(got_pages)
    # row0 was just used (MRU): inserting row2 evicts row1
    t.insert(rows[2], pages[2], logits[2])
    assert not t.contains(rows[1]) and t.contains(rows[0])
    assert a.refcount(pages[1][0]) == 1  # table's ref released on eviction
    # drop_all releases every table ref; caller refs survive
    t.drop_all()
    a.check()
    for pg in pages:
        a.release(pg)
    a.check()
    assert a.n_free == 7


# ---------------------------------------------------------------------------
# step scheduler: chunked prefill + deadline shedding
# ---------------------------------------------------------------------------
def test_scheduler_chunked_admission_caps_fresh_prefills():
    sched = StepScheduler(n_slots=6, sid_length=3, prefill_chunk=2)
    q = RequestQueue()
    for i in range(6):
        q.submit(np.full(4, i, np.int32), 3)
    admissions, fresh = sched.plan_admissions(q, lambda r: False)
    assert len(fresh) == 2 and len(admissions) == 2  # chunk caps the step
    assert len(q) == 4  # the rest waits for the next step boundary
    # share hits bypass the chunk: everything left admits in one step
    for slot, r, _ in admissions:
        sched.admit(slot, r)
    admissions2, fresh2 = sched.plan_admissions(q, lambda r: True)
    assert len(admissions2) == 4 and not fresh2
    assert all(hit for _, _, hit in admissions2)


def test_scheduler_deadline_shedding_preserves_survivors():
    sched = StepScheduler(n_slots=2, sid_length=3, prefill_chunk=1,
                          deadline_s=10.0)
    q = RequestQueue()
    r0 = q.submit(np.zeros(4, np.int32), 3, 0)
    r1 = q.submit(np.ones(4, np.int32), 3, 1)
    # age request r0 past the deadline without sleeping
    import time
    for lane in q._lanes.values():
        for req in lane:
            if req.rid == r0:
                req.t_enqueue = time.monotonic() - 99.0
    shed = sched.shed_expired(q)
    assert [r.rid for r in shed] == [r0]
    assert len(q) == 1
    survivor = q.pop()
    assert survivor.rid == r1  # rid and enqueue time survive the re-queue
    assert time.monotonic() - survivor.t_enqueue < 5.0


def test_scheduler_levels_and_eviction_order():
    sched = StepScheduler(n_slots=3, sid_length=2, prefill_chunk=3)
    q = RequestQueue()
    q.submit(np.zeros(4, np.int32), 2)
    admissions, fresh = sched.plan_admissions(q, lambda r: False)
    sched.admit(admissions[0][0], admissions[0][1])
    assert sched.n_live == 1 and sched.completed() == []
    sched.advance()
    assert sched.slots[admissions[0][0]].t_first is not None
    sched.advance()
    done = sched.completed()
    assert done == [admissions[0][0]]
    st = sched.evict(done[0])
    assert st.level == 2 and sched.n_live == 0
    assert sched.slots[done[0]].served == 1


# ---------------------------------------------------------------------------
# level-free + shared-mask bit-identity (unit scale)
# ---------------------------------------------------------------------------
def test_shared_mask_step_bitwise_vs_per_level(rng):
    vocab, L = 24, 3
    sids = make_sids(rng, 60, vocab, L)
    tm = TransitionMatrix.from_sids(sids, vocab, dense_d=0)
    policy = DecodePolicy.static(tm)
    assert policy.supports_level_free
    B, M = 4, 3
    nodes = jnp.ones((B, M), jnp.int32)
    for step in range(L):
        logits = jnp.asarray(
            rng.standard_normal((B, M, vocab)), jnp.float32)
        want_lp, want_next = policy.step(logits, nodes, step)
        for share_width in (None, 2, B * M):
            got_lp, got_next, n_uni = policy.shared_mask_step(
                logits.reshape(B * M, vocab), nodes.reshape(B * M),
                share_width=share_width)
            np.testing.assert_array_equal(
                np.asarray(want_lp).reshape(B * M, vocab),
                np.asarray(got_lp))
            np.testing.assert_array_equal(
                np.asarray(want_next).reshape(B * M, vocab),
                np.asarray(got_next))
        assert int(n_uni) <= B * M
        # advance all rows along the best edge to reach the next level
        tok = jnp.argmax(want_lp, axis=-1)
        nodes = jnp.take_along_axis(
            want_next, tok[:, :, None], axis=-1)[:, :, 0].astype(jnp.int32)


def test_level_free_requires_all_sparse_index(rng):
    sids = make_sids(rng, 40, 16, 3)
    tm = TransitionMatrix.from_sids(sids, 16, dense_d=2)
    policy = DecodePolicy.static(tm)
    assert not policy.supports_level_free
    with pytest.raises(ValueError, match="dense_d=0"):
        policy.shared_mask_step(
            jnp.zeros((4, 16), jnp.float32), jnp.ones(4, jnp.int32))


# ---------------------------------------------------------------------------
# the engine: differential fuzz vs ServingEngine
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def gr_stack():
    rng = np.random.default_rng(7)
    vocab, L, beam = 32, 3, 4
    cfg = gr_model_config(vocab)
    params = transformer.init_params(cfg, jax.random.key(0))
    catalog = synthetic_catalog(rng, 300, vocab, L)
    registry = ConstraintRegistry(vocab, dense_d=0, headroom=0.5)
    registry.register("fresh", freshness_window(60.0))
    registry.register("cats", category_allowlist(0, 1, 2, 3))
    registry.build(catalog)
    policy = DecodePolicy.stacked(registry.current()[0])
    retr = GenerativeRetriever(params, cfg, policy, L, vocab,
                               beam_size=beam)
    ref = ServingEngine(params, cfg, batch_size=3, max_len=16,
                        retriever=retr, registry=registry)
    cont = ContinuousServingEngine(
        retr, registry=registry, slots=5, prompt_width=8, page_size=4,
        prefill_chunk=2, share_width=12)
    return dict(vocab=vocab, L=L, registry=registry, catalog=catalog,
                ref=ref, cont=cont, rng=rng)


def _drive_both(stack, n_req, seed, dup_every=4):
    vocab, L = stack["vocab"], stack["L"]
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, vocab, size=(n_req, 8)).astype(np.int32)
    for i in range(dup_every, n_req, dup_every):
        prompts[i] = prompts[i - dup_every]  # exercise prompt sharing
    q1, q2 = RequestQueue(), RequestQueue()
    for i in range(n_req):
        cid = int(i % 2)
        q1.submit(prompts[i], L, cid)
        q2.submit(prompts[i], L, cid)
    return stack["ref"].serve(q1), stack["cont"].serve(q2)


def test_fuzz_bit_identical_to_serving_engine(gr_stack):
    a, b = _drive_both(gr_stack, 13, seed=11)
    assert set(a) == set(b)
    for rid in a:
        np.testing.assert_array_equal(
            a[rid]["sids"], b[rid]["sids"],
            err_msg=f"rid {rid}: SID beams diverged")
        np.testing.assert_array_equal(
            a[rid]["scores"], b[rid]["scores"],
            err_msg=f"rid {rid}: beam scores diverged")
        assert b[rid]["constraint_id"] == a[rid]["constraint_id"]
        assert "latency_s" in b[rid] and "queue_s" in b[rid]


def test_static_position_rope_is_not_constant_folded():
    """The batch engine decodes at compile-time-constant positions, the
    continuous engine at traced ones.  Both must compute RoPE's cos/sin on
    the device: a folded cos/sin comes from the compiler's host math, which
    on a TPU differs from the device's in the last place and breaks the
    engines' bit-identity there."""
    from repro.models.layers import apply_rope

    x = jnp.ones((1, 1, 2, 8), jnp.float32)
    txt = jax.jit(lambda x: apply_rope(x, jnp.asarray([261]))).lower(
        x).compile().as_text()
    assert "cosine(" in txt and "sine(" in txt


def test_fuzz_bit_identical_across_hot_swap(gr_stack):
    churned = synthetic_catalog(np.random.default_rng(13), 300,
                                gr_stack["vocab"], gr_stack["L"])
    gr_stack["registry"].swap(churned)
    a, b = _drive_both(gr_stack, 9, seed=17)
    for rid in a:
        np.testing.assert_array_equal(a[rid]["sids"], b[rid]["sids"])
        np.testing.assert_array_equal(a[rid]["scores"], b[rid]["scores"])
    cont = gr_stack["cont"]
    unexpected = cont.metrics.counter(
        "serving_recompiles_total").value(expected="false")
    assert int(unexpected) == 0, "hot swap recompiled the continuous step"


def test_mid_flight_admission_and_sharing_counters(gr_stack):
    cont = gr_stack["cont"]
    # more requests than slots forces step-boundary refills
    _drive_both(gr_stack, 12, seed=23)
    assert int(cont._slot_reuse.total()) > 0, \
        "no slot was ever refilled mid-flight"
    hits = cont.metrics.counter("serving_prefix_share_hits_total")
    assert int(hits.value(kind="prompt")) > 0, \
        "duplicate prompts never hit the prefix-share table"
    assert int(hits.value(kind="mask_row")) > 0, \
        "beams on one trie node never shared a mask row"
    cont.alloc.check()  # drained serve leaves the page pool consistent


def test_deadline_shedding_end_to_end(gr_stack):
    cont = gr_stack["cont"]
    vocab, L = gr_stack["vocab"], gr_stack["L"]
    cont.sched.deadline_s = 0.0  # every queued request is already late
    try:
        q = RequestQueue()
        rng = np.random.default_rng(29)
        rids = [q.submit(rng.integers(0, vocab, 8).astype(np.int32), L, 0)
                for _ in range(3)]
        before = int(cont._m.rejected.total())
        out = cont.serve(q)
        assert all("error" in out[rid] for rid in rids)
        assert all("sids" not in out[rid] for rid in rids)
        assert int(cont._m.rejected.total()) == before + 3
    finally:
        cont.sched.deadline_s = None


def test_continuous_rejects_non_level_free_policy(gr_stack):
    rng = np.random.default_rng(31)
    sids = make_sids(rng, 40, 16, 3)
    tm = TransitionMatrix.from_sids(sids, 16, dense_d=2)
    cfg = gr_model_config(16)
    params = transformer.init_params(cfg, jax.random.key(1))
    retr = GenerativeRetriever(params, cfg, DecodePolicy.static(tm), 3, 16,
                               beam_size=2)
    with pytest.raises(ValueError, match="dense_d=0"):
        ContinuousServingEngine(retr, slots=2)
