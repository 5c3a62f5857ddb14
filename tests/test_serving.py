"""Serving: engine greedy generation, continuous batching, constrained GR."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import TransitionMatrix
from repro.core.vntk import NEG_INF
from repro.models import transformer
from repro.serving.engine import RequestQueue, ServingEngine
from repro.serving.generative_retrieval import GenerativeRetriever
from conftest import make_sids


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("stablelm-12b")
    params = transformer.init_params(cfg, jax.random.key(0))
    return params, cfg


def test_engine_matches_manual_greedy(small_lm):
    params, cfg = small_lm
    B, S, n_new = 2, 6, 4
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    eng = ServingEngine(params, cfg, batch_size=B, max_len=S + n_new + 1)
    got = eng.generate(prompts, n_new)
    # manual teacher-forced reference using full forwards
    toks = prompts.copy()
    want = []
    for _ in range(n_new):
        x, _, _ = transformer.forward(params, jnp.asarray(toks), cfg)
        w = params["unemb"]
        logits = np.asarray((x[:, -1, :] @ w).astype(jnp.float32))
        nxt = logits.argmax(-1).astype(np.int32)
        want.append(nxt)
        toks = np.concatenate([toks, nxt[:, None]], axis=1)
    want = np.stack(want, axis=1)
    np.testing.assert_array_equal(got, want)


def test_continuous_batching_drains_queue(small_lm):
    params, cfg = small_lm
    eng = ServingEngine(params, cfg, batch_size=2, max_len=32)
    q = RequestQueue()
    rng = np.random.default_rng(1)
    rids = [
        q.submit(rng.integers(0, cfg.vocab_size, (5,)), n_tokens=3)
        for _ in range(5)
    ]
    results = eng.serve(q)
    assert len(q) == 0
    assert set(results) == set(rids)
    assert all(len(v) == 3 for v in results.values())


def test_generative_retriever_100pct_compliance(small_lm, rng):
    params, cfg = small_lm
    V, L = cfg.vocab_size, 4
    sids = make_sids(rng, 40, V, L, clustered=True)
    tm = TransitionMatrix.from_sids(sids, V)
    gr = GenerativeRetriever(params, cfg, tm, sid_length=L, sid_vocab=V,
                             beam_size=6)
    hist = rng.integers(0, V, (3, 8)).astype(np.int32)
    beams, scores = gr.retrieve(hist)
    assert beams.shape == (3, 6, L)
    valid = {tuple(r) for r in sids}
    for b in range(3):
        for m in range(6):
            if scores[b, m] > NEG_INF / 2:
                assert tuple(beams[b, m]) in valid


def test_request_queue_fairness_mixed_constraint_slots():
    """A tenant bursting the queue must not monopolize batched admission:
    pop rotates across constraint-id lanes, FIFO within a lane."""
    q = RequestQueue()
    p = np.zeros(4, np.int32)
    burst = [q.submit(p, 1, constraint_id=0) for _ in range(6)]
    late = [q.submit(p, 1, constraint_id=1) for _ in range(2)]
    assert len(q) == 8
    batch = q.pop_batch(4)
    # the first batch already mixes both tenants (strict FIFO would have
    # admitted four constraint-0 requests and starved tenant 1 for batches)
    assert [r.constraint_id for r in batch] == [0, 1, 0, 1]
    # arrival order preserved within each lane
    assert [r.rid for r in batch if r.constraint_id == 0] == burst[:2]
    assert [r.rid for r in batch if r.constraint_id == 1] == late
    rest = q.pop_batch(10)
    assert [r.rid for r in rest] == burst[2:] and len(q) == 0
    assert q.pop() is None


def test_request_queue_single_tenant_is_fifo():
    q = RequestQueue()
    p = np.zeros(4, np.int32)
    rids = [q.submit(p, 1) for _ in range(5)]
    assert [q.pop().rid for _ in range(5)] == rids


def test_generative_retriever_unconstrained_vs_constrained_scores(small_lm, rng):
    """Constrained top beam score <= unconstrained top beam score."""
    params, cfg = small_lm
    V, L = cfg.vocab_size, 3
    sids = make_sids(rng, 30, V, L)
    tm = TransitionMatrix.from_sids(sids, V)
    hist = rng.integers(0, V, (2, 8)).astype(np.int32)
    g_c = GenerativeRetriever(params, cfg, tm, L, V, beam_size=4)
    g_u = GenerativeRetriever(params, cfg, None, L, V, beam_size=4)
    _, s_c = g_c.retrieve(hist)
    _, s_u = g_u.retrieve(hist)
    assert (s_c[:, 0] <= s_u[:, 0] + 1e-4).all()


# ---------------------------------------------------------------------------
# launch.serve builders (the CLI and chip_smoke.py share them)
# ---------------------------------------------------------------------------
def test_static_gr_decoder_geometry():
    from repro.launch import serve

    cfg, geo = serve.decoder("static-gr")
    assert (cfg.name, cfg.n_layers, cfg.d_model, cfg.dtype) == (
        "static-gr-3b", 26, 3072, "bfloat16")
    assert (geo.vocab, geo.sid_length, geo.beam, geo.batch, geo.history,
            geo.dense_d) == (2048, 8, 70, 2, 256, 2)
    with pytest.raises(ValueError):
        serve.decoder("stablelm-12b")


@pytest.mark.parametrize("kind", ["batch", "spmd", "continuous"])
def test_serve_builders_serve_compliant_beams(kind):
    from repro.launch import serve

    cfg, geo = serve.decoder(serve.TOY_MODEL, vocab=64)
    cfg = dataclasses.replace(cfg, n_layers=2)
    geo = dataclasses.replace(geo, batch=2, beam=4, constraints=500)
    params = serve.build_params(cfg, seed=1)
    sids = serve.constraint_sids(geo.constraints, geo, seed=1)
    tm = serve.build_index(sids, geo, dense_d=0 if kind == "continuous"
                           else None)
    mesh = None
    if kind == "spmd":
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(model=1)
    r = serve.build_retriever(params, cfg, serve.build_policy(tm), geo,
                              mesh=mesh)
    engine = serve.build_engine(kind, r, geo)
    q = RequestQueue()
    hist = serve.request_histories(4, geo, seed=1)
    rids = [q.submit(h, geo.sid_length) for h in hist]
    res = engine.serve(q)
    beams = np.stack([res[i]["sids"] for i in rids])
    scores = np.stack([res[i]["scores"] for i in rids])
    checked, bad = serve.compliance(beams, scores,
                                    {tuple(s) for s in sids.tolist()})
    assert checked == 4 * geo.beam and bad == 0
    want = GenerativeRetriever(params, cfg, serve.build_policy(tm),
                               geo.sid_length, geo.vocab,
                               beam_size=geo.beam).retrieve(hist[:2])
    np.testing.assert_array_equal(beams[:2], want[0])


def test_compile_cache_dir_rule(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent.joinpath("chip_smoke.py"
                                                         ).exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
