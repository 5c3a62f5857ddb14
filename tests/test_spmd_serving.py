"""SPMD constrained serving (DESIGN.md §6).

Load-bearing properties: (1) SPMD decoding over a mesh — replicated or
CSR-row-sharded constraints — is bit-identical to single-device decoding;
(2) a registry hot-swap under the mesh compiles NOTHING new; (3) the
continuous-batching engine drains mixed-constraint queues with per-request
compliance at any occupancy.

Runs on however many devices exist (a 1-device mesh still exercises
shard_map, the psum combine, and the padding rules); CI additionally runs
this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.constraints import (
    ConstraintRegistry,
    ConstraintStore,
    ItemCatalog,
    freshness_window,
)
from repro.core import NEG_INF, TransitionMatrix
from repro.core.vntk import vntk_xla
from repro.decoding import DecodePolicy
from repro.distributed.constraint_sharding import (
    pad_rows,
    policy_pspecs,
    spmd_beam_search,
    vntk_row_sharded,
)
from repro.distributed.sharding import dp_size
from repro.launch.mesh import make_subset_mesh
from repro.models import transformer
from repro.serving.engine import RequestQueue
from repro.serving.generative_retrieval import GenerativeRetriever
from repro.serving.spmd_engine import SpmdRetriever, SpmdServingEngine
from conftest import make_sids

V, L = 16, 4


def data_mesh():
    """All visible devices on the data axis (model kept at 1)."""
    return make_subset_mesh(len(jax.devices()), 1)


def model_mesh():
    """A mesh with a non-trivial model axis when devices allow."""
    n = len(jax.devices())
    model = 2 if n % 2 == 0 and n >= 2 else 1
    return make_subset_mesh(n // model, model)


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("stablelm-12b")
    params = transformer.init_params(cfg, jax.random.key(0))
    return params, cfg


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    sids = np.unique(make_sids(rng, 150, V, L, clustered=True), axis=0)
    tm = TransitionMatrix.from_sids(sids, V, dense_d=2)
    table = jnp.asarray(rng.normal(size=(L, V, V)).astype(np.float32))
    return sids, tm, table


def table_logits_fn(table):
    def fn(carry, last, step):
        return table[step][last], carry
    return fn


# ---------------------------------------------------------------------------
# spmd_beam_search: bit-identity over the mesh
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows", ["replicated", "model"])
def test_spmd_beam_search_bit_identical(corpus, rows):
    from repro.core import beam_search

    _, tm, table = corpus
    mesh = model_mesh()
    B = 2 * dp_size(mesh)
    policy = DecodePolicy.static(tm)

    @jax.jit
    def single(pol):  # compiled-vs-compiled: both sides XLA-optimized
        state, _ = beam_search(table_logits_fn(table), None, B, 5, L, pol)
        return state.tokens, state.scores

    want_t, want_s = single(policy)
    tokens, scores = spmd_beam_search(
        mesh, table_logits_fn(table), B, 5, L, policy, rows=rows
    )
    # deterministic table logits -> full float bit-identity, scores included
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(want_t))
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(want_s))


def test_spmd_beam_search_stacked_constraint_ids(corpus, rng):
    from repro.core import beam_search

    sids, tm, table = corpus
    mats = [tm, TransitionMatrix.from_sids(
        make_sids(rng, 60, V, L, clustered=True), V, dense_d=2)]
    store = ConstraintStore.from_matrices(mats, headroom=0.25)
    mesh = data_mesh()
    B = 2 * dp_size(mesh)
    cids = np.arange(B, dtype=np.int32) % 2
    policy = DecodePolicy.stacked(store)

    @jax.jit
    def single(pol, ids):
        state, _ = beam_search(
            table_logits_fn(table), None, B, 4, L, pol, constraint_ids=ids
        )
        return state.tokens, state.scores

    want_t, want_s = single(policy, jnp.asarray(cids))
    tokens, scores = spmd_beam_search(
        mesh, table_logits_fn(table), B, 4, L, policy, constraint_ids=cids
    )
    np.testing.assert_array_equal(np.asarray(tokens), np.asarray(want_t))
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(want_s))


def test_spmd_beam_search_rejects_ragged_batch(corpus):
    _, tm, table = corpus
    mesh = data_mesh()
    n = dp_size(mesh)
    if n == 1:
        pytest.skip("every batch divides a 1-way mesh")
    with pytest.raises(ValueError, match="pad with inactive rows"):
        spmd_beam_search(mesh, table_logits_fn(table), n + 1, 4, L,
                         DecodePolicy.static(tm))


# ---------------------------------------------------------------------------
# row-sharded CSR: one-hop gather == replicated VNTK, and padding is inert
# ---------------------------------------------------------------------------
def test_vntk_row_sharded_matches_replicated(corpus, rng):
    _, tm, _ = corpus
    mesh = model_mesh()
    ms = mesh.shape["model"]
    tm_pad = pad_rows(tm, ms)
    assert tm_pad.edges.shape[0] % ms == 0
    step = 2
    bmax = max(tm.bmax_for_step(step), 1)
    nodes = jnp.asarray(
        rng.integers(0, tm.n_states, size=(12,)), jnp.int32)
    lp = jnp.asarray(rng.normal(size=(12, V)).astype(np.float32))
    want_lp, want_nx = vntk_xla(lp, nodes, tm, bmax)

    f = jax.jit(jax.shard_map(
        lambda lp, nodes, rp, edges: vntk_row_sharded(
            lp, nodes, rp, edges, bmax, V, "model"),
        mesh=mesh,
        in_specs=(P(), P(), P(), P("model", None)),
        out_specs=(P(), P()),
    ))
    got_lp, got_nx = f(lp, nodes, tm_pad.row_pointers, tm_pad.edges)
    np.testing.assert_array_equal(np.asarray(got_lp), np.asarray(want_lp))
    np.testing.assert_array_equal(np.asarray(got_nx), np.asarray(want_nx))


def test_pad_rows_roundtrip_and_determinism(corpus):
    _, tm, _ = corpus
    p3 = pad_rows(tm, 3)
    assert p3.edges.shape[0] % 3 == 0
    assert p3.n_edges == tm.n_edges  # static metadata untouched
    np.testing.assert_array_equal(
        np.asarray(p3.edges[: tm.edges.shape[0]]), np.asarray(tm.edges))
    assert not np.asarray(p3.edges[tm.edges.shape[0]:]).any()
    # idempotent at the same shard count => hot-swap shapes are deterministic
    assert pad_rows(p3, 3).edges.shape == p3.edges.shape
    assert pad_rows(tm, 1) is tm


def test_policy_pspecs_structure(corpus):
    _, tm, _ = corpus
    mesh = model_mesh()
    policy = DecodePolicy.static(tm)
    specs = policy_pspecs(policy, mesh)
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(policy)
    assert all(s == P() for s in jax.tree_util.tree_leaves(specs))
    sharded = policy_pspecs(policy, mesh, rows="model")
    edge_specs = {b.tm.edges for b in sharded.backends}
    assert P("model", None) in edge_specs
    with pytest.raises(ValueError, match="rows"):
        policy_pspecs(policy, mesh, rows="banana")


def test_row_sharded_rejects_pallas_and_fused(corpus):
    _, tm, _ = corpus
    mesh = model_mesh()
    cfg = smoke_config("stablelm-12b")
    params = transformer.init_params(cfg, jax.random.key(0))
    tm_v = TransitionMatrix.from_sids(
        make_sids(np.random.default_rng(0), 40, cfg.vocab_size, L),
        cfg.vocab_size)
    for bad in (DecodePolicy.static(tm_v, fused=True),
                DecodePolicy.static(tm_v, impl="pallas")):
        with pytest.raises(ValueError, match="rows='model'"):
            SpmdRetriever(params, cfg, bad, L, cfg.vocab_size, beam_size=4,
                          mesh=mesh, rows="model")


# ---------------------------------------------------------------------------
# SpmdRetriever: end-to-end identity, padding, and hot-swap under the mesh
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows", ["replicated", "model"])
def test_spmd_retriever_matches_single_device(small_lm, rng, rows):
    params, cfg = small_lm
    Vm, Lm = cfg.vocab_size, 4
    sids = make_sids(rng, 80, Vm, Lm, clustered=True)
    tm = TransitionMatrix.from_sids(sids, Vm)
    mesh = model_mesh() if rows == "model" else data_mesh()
    # B deliberately NOT a multiple of the dp ways: exercises padding
    B = dp_size(mesh) + 1
    hist = rng.integers(0, Vm, (B, 8)).astype(np.int32)
    want_t, want_s = GenerativeRetriever(
        params, cfg, tm, sid_length=Lm, sid_vocab=Vm, beam_size=4
    ).retrieve(hist)
    got_t, got_s = SpmdRetriever(
        params, cfg, tm, sid_length=Lm, sid_vocab=Vm, beam_size=4,
        mesh=mesh, rows=rows,
    ).retrieve(hist)
    assert got_t.shape == (B, 4, Lm)
    np.testing.assert_array_equal(got_t, want_t)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)


def test_spmd_retriever_active_mask(small_lm, rng):
    params, cfg = small_lm
    Vm, Lm = cfg.vocab_size, 3
    tm = TransitionMatrix.from_sids(make_sids(rng, 50, Vm, Lm), Vm)
    mesh = data_mesh()
    B = 2 * dp_size(mesh)
    hist = rng.integers(0, Vm, (B, 8)).astype(np.int32)
    active = np.ones(B, bool)
    active[0] = False
    retr = SpmdRetriever(params, cfg, tm, sid_length=Lm, sid_vocab=Vm,
                         beam_size=4, mesh=mesh)
    _, scores = retr.retrieve(hist, active_mask=active)
    assert (scores[0] <= NEG_INF).all()  # free slot: parked, unmistakable
    assert (scores[1:, 0] > NEG_INF / 2).all()


def test_spmd_hot_swap_zero_recompile_under_mesh(small_lm, rng):
    """Acceptance: retriever.set_constraints under the mesh reuses the
    mesh-compiled executable — zero backend compiles across the swap."""
    params, cfg = small_lm
    Vm, Lm = cfg.vocab_size, 4
    cat = ItemCatalog(
        sids=make_sids(rng, 200, Vm, Lm, clustered=True),
        age_days=rng.uniform(0, 60, size=200),
        category=rng.integers(0, 4, size=200),
    )
    reg = ConstraintRegistry(Vm, headroom=0.5)
    reg.register("fresh_20", freshness_window(20))
    reg.register("fresh_45", freshness_window(45))
    store = reg.build(cat)
    mesh = data_mesh()
    retr = SpmdRetriever(params, cfg, store, sid_length=Lm, sid_vocab=Vm,
                         beam_size=4, mesh=mesh)
    eng = SpmdServingEngine(retr, registry=reg, slots=4, prompt_width=8)

    q = RequestQueue()
    for i in range(5):
        q.submit(rng.integers(0, Vm, (8,)), n_tokens=Lm, constraint_id=i % 2)
    r1 = eng.serve(q)
    assert all(r["store_version"] == 1 for r in r1.values())

    cat2 = ItemCatalog(
        sids=make_sids(rng, 220, Vm, Lm, clustered=True),
        age_days=rng.uniform(0, 60, size=220),
        category=rng.integers(0, 4, size=220),
    )
    assert reg.swap(cat2) == 2
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: compiles.append(name)
        if "backend_compile" in name else None
    )
    for i in range(3):
        q.submit(rng.integers(0, Vm, (8,)), n_tokens=Lm, constraint_id=i % 2)
    r2 = eng.serve(q)
    assert len(compiles) == 0, f"mesh hot-swap recompiled: {compiles}"
    assert all(r["store_version"] == 2 for r in r2.values())


def test_spmd_metadata_changing_swap_rebuilds(small_lm, rng):
    """A swap OUTSIDE the registry envelope (different static metadata)
    rebuilds the mesh step instead of dying on a spec/treedef mismatch —
    matching the single-device retriever's retrace-on-metadata-change."""
    params, cfg = small_lm
    Vm, Lm = cfg.vocab_size, 3
    tm1 = TransitionMatrix.from_sids(make_sids(rng, 40, Vm, Lm), Vm)
    tm2 = TransitionMatrix.from_sids(make_sids(rng, 90, Vm, Lm), Vm)
    assert tm1.n_states != tm2.n_states  # genuinely different envelope
    retr = SpmdRetriever(params, cfg, tm1, sid_length=Lm, sid_vocab=Vm,
                         beam_size=4, mesh=data_mesh(), rows="model")
    hist = rng.integers(0, Vm, (dp_size(data_mesh()), 8)).astype(np.int32)
    retr.retrieve(hist)
    retr.set_constraints(tm2)
    _, scores = retr.retrieve(hist)
    assert (scores[:, 0] > NEG_INF / 2).all()


def test_spmd_engine_mixed_queue_compliance(small_lm, rng):
    """Continuous batching drains a mixed-constraint queue larger than the
    slot count, each row 100% compliant with ITS OWN constraint set."""
    params, cfg = small_lm
    Vm, Lm = cfg.vocab_size, 4
    cat = ItemCatalog(
        sids=make_sids(rng, 250, Vm, Lm, clustered=True),
        age_days=rng.uniform(0, 60, size=250),
        category=rng.integers(0, 4, size=250),
    )
    reg = ConstraintRegistry(Vm, headroom=0.4)
    preds = {
        reg.register("fresh_25", freshness_window(25)): freshness_window(25),
        reg.register("fresh_50", freshness_window(50)): freshness_window(50),
    }
    store = reg.build(cat)
    mesh = data_mesh()
    retr = SpmdRetriever(params, cfg, store, sid_length=Lm, sid_vocab=Vm,
                         beam_size=4, mesh=mesh)
    eng = SpmdServingEngine(retr, registry=reg, slots=4, prompt_width=8)
    q = RequestQueue()
    rids = [q.submit(rng.integers(0, Vm, (8,)), n_tokens=Lm,
                     constraint_id=i % 2) for i in range(9)]
    results = eng.serve(q)
    assert set(results) == set(rids) and len(q) == 0
    for r in results.values():
        valid = {tuple(x)
                 for x in cat.sids[preds[r["constraint_id"]](cat)]}
        for m, sid in enumerate(r["sids"]):
            if r["scores"][m] > NEG_INF / 2:
                assert tuple(sid) in valid, (r["constraint_id"], sid)
    # an out-of-range constraint id is rejected per-request (never clamped
    # to the wrong tenant), and the rest of the batch still serves
    bad = q.submit(rng.integers(0, Vm, (8,)), n_tokens=Lm, constraint_id=77)
    ok = q.submit(rng.integers(0, Vm, (8,)), n_tokens=Lm, constraint_id=1)
    res = eng.serve(q)
    assert "constraint_id 77" in res[bad]["error"] and "sids" not in res[bad]
    assert res[ok]["scores"][0] > NEG_INF / 2 and len(q) == 0


def test_spmd_retriever_rejects_cpu_trie(small_lm, rng):
    params, cfg = small_lm
    sids = make_sids(rng, 30, cfg.vocab_size, 3)
    with pytest.raises(TypeError, match="io_callback"):
        SpmdRetriever(params, cfg,
                      DecodePolicy.cpu_trie(sids, cfg.vocab_size),
                      3, cfg.vocab_size, mesh=data_mesh())
