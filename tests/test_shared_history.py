"""The retrieval step holds each request's history once, shared by its beams.

``transformer.gr_decode_step`` attends every beam to its request's history
and its own SID suffix; ``GenerativeRetriever`` keeps only the suffix per
beam.  Both are checked here against teacher forcing: a full prefill of
each beam's whole token sequence, recomputed from scratch at every level.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import TransitionMatrix, beam_search
from repro.decoding import DecodePolicy
from repro.models import transformer
from repro.serving.generative_retrieval import GenerativeRetriever
from conftest import make_sids

B, M, S, L, V = 2, 6, 8, 4, 16
TOL = dict(rtol=1e-5, atol=1e-5)  # float32 smoke decoder


def _decoder(arch="static-gr", **changes):
    cfg = dataclasses.replace(smoke_config(arch), **changes)
    return cfg, transformer.init_params(cfg, jax.random.key(0))


def _histories(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)


def test_step_matches_a_teacher_forced_prefill():
    """Level by level, the logits equal a prefill of history plus the SID
    tokens so far, and the suffix ends up holding that prefill's K/V."""
    cfg, params = _decoder()
    hist = _histories()
    sfx = jnp.asarray(np.random.default_rng(1).integers(
        0, V, (B * M, L - 1)), jnp.int32)
    seqs = jnp.concatenate([jnp.repeat(hist, M, axis=0), sfx], axis=1)
    _, cache = transformer.prefill(params, hist, cfg)
    bk = bv = jnp.zeros(
        (cfg.n_layers, B * M, L - 1, cfg.n_kv_heads, cfg.resolved_head_dim()),
        jnp.float32)
    for j in range(L - 1):
        logits, bk, bv = transformer.gr_decode_step(
            params, cache.k, cache.v, bk, bv, sfx[:, j:j + 1],
            jnp.asarray(j, jnp.int32), cfg)
        want, _ = transformer.prefill(params, seqs[:, :S + j + 1], cfg)
        np.testing.assert_allclose(logits, want, **TOL)
    _, full = transformer.prefill(params, seqs, cfg)
    np.testing.assert_allclose(bk, full.k[:, :, S:], **TOL)
    np.testing.assert_allclose(bv, full.v[:, :, S:], **TOL)


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


@pytest.mark.parametrize("window", [None, S + L - 1],
                         ids=["full", "shortest-window-served"])
def test_retriever_matches_a_teacher_forced_beam_search(window):
    """Dense levels, then top-C levels: the same SIDs, and the scores of
    the reference within float32 rounding."""
    cfg, params = _decoder(sliding_window=window)
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
    np.testing.assert_allclose(got_s, want.scores, **TOL)


@pytest.mark.parametrize("arch,changes", [
    ("deepseek-v2-lite-16b", {}),
    ("mixtral-8x7b", {"sliding_window": None}),
    ("static-gr", {"sliding_window": S + L - 2}),
], ids=["mla", "moe", "window-shorter-than-history-and-suffix"])
def test_retriever_refuses_decoders_the_shared_step_cannot_serve(
        arch, changes):
    cfg, params = _decoder(arch, **changes)
    retr = GenerativeRetriever(params, cfg, None, sid_length=L, sid_vocab=V,
                               beam_size=2)
    with pytest.raises(NotImplementedError, match="shares each history"):
        retr.retrieve(np.zeros((1, S), np.int32))
