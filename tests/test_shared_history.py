"""The retrieval step holds each request's history once, shared by its beams.

``transformer.gr_decode_step`` attends every beam to its request's history
and its own SID suffix; ``GenerativeRetriever`` keeps only the suffix per
beam.  Both are checked here against teacher forcing: a full prefill of
each beam's whole token sequence, recomputed from scratch at every level.
Three decoders: dense GQA, latent attention with experts (the history and
suffix held as latents, attended absorbed), and GQA with experts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import TransitionMatrix, beam_search
from repro.decoding import DecodePolicy
from repro.models import kvcache as kv_lib
from repro.models import transformer
from repro.serving.generative_retrieval import GenerativeRetriever
from conftest import make_sids

B, M, S, L, V = 2, 6, 8, 4, 16
TOL = dict(rtol=1e-5, atol=1e-5)  # float32 smoke decoder
# latent attention's step contracts W_uk into the query and the context
# into W_uv, the prefill expands K and V first: float32 sums in another
# order, off by up to ~2e-5 on logits of order 1
TOL_ABSORBED = dict(rtol=1e-4, atol=1e-4)


def _decoder(arch="static-gr", **changes):
    cfg = dataclasses.replace(smoke_config(arch), **changes)
    return cfg, transformer.init_params(cfg, jax.random.key(0))


def _histories(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)


# (arch, changes) of each decoder the step serves
DECODERS = {
    "dense-gqa": ("static-gr", {}),
    "mla-moe": ("deepseek-v2-lite-16b", {}),
    "gqa-moe": ("mixtral-8x7b", {"sliding_window": None}),
}


def _state(cache):
    """The two arrays a prefill cache holds per position: K and V, or the
    latents and rotated keys of latent attention."""
    if isinstance(cache, kv_lib.MLACache):
        return cache.c_kv, cache.k_rope
    return cache.k, cache.v


@pytest.mark.parametrize("decoder", list(DECODERS))
def test_step_matches_a_teacher_forced_prefill(decoder):
    """Level by level, the logits equal a prefill of history plus the SID
    tokens so far, and the suffix ends up holding that prefill's state."""
    arch, changes = DECODERS[decoder]
    cfg, params = _decoder(arch, **changes)
    tol = TOL_ABSORBED if cfg.attention == "mla" else TOL
    hist = _histories()
    sfx = jnp.asarray(np.random.default_rng(1).integers(
        0, V, (B * M, L - 1)), jnp.int32)
    seqs = jnp.concatenate([jnp.repeat(hist, M, axis=0), sfx], axis=1)
    _, cache = transformer.prefill(params, hist, cfg)
    ha, hb = _state(cache)
    ba, bb = (jnp.zeros((cfg.n_layers, B * M, L - 1) + h.shape[3:],
                        jnp.float32) for h in (ha, hb))
    for j in range(L - 1):
        logits, ba, bb = transformer.gr_decode_step(
            params, ha, hb, ba, bb, sfx[:, j:j + 1],
            jnp.asarray(j, jnp.int32), cfg)
        want, _ = transformer.prefill(params, seqs[:, :S + j + 1], cfg)
        np.testing.assert_allclose(logits, want, **tol)
    _, full = transformer.prefill(params, seqs, cfg)
    fa, fb = _state(full)
    np.testing.assert_allclose(ba, fa[:, :, S:], **tol)
    np.testing.assert_allclose(bb, fb[:, :, S:], **tol)


def _teacher_forced_search(params, cfg, hist, policy):
    """Beam search whose every level re-prefills each beam's sequence."""
    def logits_fn(seqs, last, step):
        seqs = jnp.concatenate([seqs, last.reshape(B * M, 1)], axis=1)
        logits, _ = transformer.prefill(params, seqs, cfg)
        return logits[:, 0, :V].reshape(B, M, V), seqs

    def gather(seqs, beam_idx):
        return seqs[(jnp.arange(B)[:, None] * M + beam_idx).reshape(-1)]

    first, _ = transformer.prefill(params, hist, cfg)
    state, _ = beam_search(
        logits_fn, jnp.repeat(hist, M, axis=0), B, M, L, policy,
        carry_gather_fn=gather, first_logits=first[:, 0, :V])
    return state


@pytest.mark.parametrize("arch,changes", [
    ("static-gr", {}),
    ("static-gr", {"sliding_window": S + L - 1}),
    DECODERS["mla-moe"],
], ids=["full", "shortest-window-served", "mla-moe"])
def test_retriever_matches_a_teacher_forced_beam_search(arch, changes):
    """Dense levels, then top-C levels: the same SIDs, and the scores of
    the reference within float32 rounding."""
    cfg, params = _decoder(arch, **changes)
    rng = np.random.default_rng(2)
    tm = TransitionMatrix.from_sids(make_sids(rng, 200, V, L), V, dense_d=2)
    policy = DecodePolicy.static(tm)
    assert not policy.supports_topk_at(1) and policy.supports_topk_at(L - 1)
    hist = _histories(3)
    got_t, got_s = GenerativeRetriever(
        params, cfg, policy, sid_length=L, sid_vocab=V,
        beam_size=M).retrieve(hist)
    want = _teacher_forced_search(params, cfg, hist, policy)
    np.testing.assert_array_equal(got_t, want.tokens)
    np.testing.assert_allclose(
        got_s, want.scores, **(TOL_ABSORBED if cfg.attention == "mla"
                               else TOL))


@pytest.mark.parametrize("arch,changes", [
    ("static-gr", {"sliding_window": S + L - 2}),
], ids=["window-shorter-than-history-and-suffix"])
def test_retriever_refuses_decoders_the_shared_step_cannot_serve(
        arch, changes):
    cfg, params = _decoder(arch, **changes)
    retr = GenerativeRetriever(params, cfg, None, sid_length=L, sid_vocab=V,
                               beam_size=2)
    with pytest.raises(NotImplementedError, match="shares each history"):
        retr.retrieve(np.zeros((1, S), np.int32))


def test_engine_counts_rows_per_held_expert():
    """The batch engine counts, for an expert decoder, the rows its decode
    levels routed to each held expert."""
    from repro.serving.engine import RequestQueue, ServingEngine

    cfg, params = _decoder("deepseek-v2-lite-16b")
    rng = np.random.default_rng(4)
    tm = TransitionMatrix.from_sids(make_sids(rng, 200, V, L), V, dense_d=2)
    retr = GenerativeRetriever(params, cfg, DecodePolicy.static(tm),
                               sid_length=L, sid_vocab=V, beam_size=M)
    eng = ServingEngine(params, cfg, B, 2 * S, retriever=retr)
    q = RequestQueue()
    for i in range(2 * B):
        q.submit(rng.integers(0, V, S).astype(np.int32), L)
    assert len(eng.serve(q)) == 2 * B
    m = eng.metrics
    routed = m.counter("serving_moe_expert_rows_total")
    held = cfg.moe.n_experts  # the smoke decoder holds all it routes to
    per_batch = (L - 1) * B * M * cfg.moe.top_k * (
        cfg.n_layers - cfg.moe.first_dense_layers)
    assert routed.total() == 2 * per_batch
    assert {dict(k)["expert"] for k, _ in routed.labeled()} == {
        str(e) for e in range(held)}
    assert retr.expert_rows.shape == (held,)


def test_engine_counts_row_tiles_of_the_grouped_matmul_kernel(monkeypatch):
    """With the expert layers on the Pallas grouped matmul (interpret
    mode), the engine counts the row tiles it visited in the decode
    levels: the retriever's last batch, at least one tile in each expert
    layer of each level, with the same routing as the XLA path.  The XLA
    path counts none."""
    import functools
    import types

    from repro.kernels import ops
    from repro.models import moe
    from repro.serving.engine import RequestQueue, ServingEngine

    cfg, params = _decoder("deepseek-v2-lite-16b")
    rng = np.random.default_rng(5)
    tm = TransitionMatrix.from_sids(make_sids(rng, 200, V, L), V, dense_d=2)
    hist = [rng.integers(0, V, S).astype(np.int32) for _ in range(B)]

    def serve():
        retr = GenerativeRetriever(params, cfg, DecodePolicy.static(tm),
                                   sid_length=L, sid_vocab=V, beam_size=M)
        eng = ServingEngine(params, cfg, B, 2 * S, retriever=retr)
        q = RequestQueue()
        for h in hist:
            q.submit(h, L)
        res = eng.serve(q)
        assert len(res) == B
        return retr, eng.metrics

    xla, m_xla = serve()
    assert xla.row_tiles == 0
    assert "serving_moe_row_tiles_total" not in m_xla.render_prometheus()
    monkeypatch.setattr(moe, "ops", types.SimpleNamespace(
        resolve=lambda impl: "pallas",
        grouped_matmul=functools.partial(ops.grouped_matmul, impl="pallas")))
    retr, m = serve()
    np.testing.assert_array_equal(retr.expert_rows, xla.expert_rows)
    tiles = m.counter("serving_moe_row_tiles_total").total()
    assert tiles == retr.row_tiles > 0
    per_layer_level = (L - 1) * (cfg.n_layers - cfg.moe.first_dense_layers)
    assert tiles >= per_layer_level  # every layer routes some rows here
