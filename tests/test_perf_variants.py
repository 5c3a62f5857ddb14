"""§Perf optimization flags must be numerically equivalent to baselines.

Every hillclimb optimization (EXPERIMENTS.md §Perf) is a *schedule/layout*
change, never a math change — asserted here on smoke configs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import transformer
from repro.models.moe import moe_ffn


def test_deferred_commit_decode_equivalence():
    """defer_cache_write=True produces identical logits; pending k/v equal
    what the eager path wrote into the cache slot."""
    cfg = smoke_config("stablelm-12b")
    cfg_d = dataclasses.replace(cfg, defer_cache_write=True)
    params = transformer.init_params(cfg, jax.random.key(0))
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab_size)
    _, cache = transformer.prefill(params, tokens[:, :S], cfg, max_len=S + 4)
    logits_a, cache_a = transformer.decode_step(
        params, cache, tokens[:, S:S + 1], cfg)
    logits_b, cache_b, pending = transformer.decode_step(
        params, cache, tokens[:, S:S + 1], cfg_d)
    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(logits_b), rtol=2e-4, atol=2e-4)
    # pending k/v == what the eager path wrote at slot S
    k_pend, v_pend = pending
    np.testing.assert_allclose(
        np.asarray(k_pend[:, :, 0], np.float32),
        np.asarray(cache_a.k[:, :, S], np.float32), rtol=2e-2, atol=2e-2)
    # deferred path leaves the cache array untouched at slot S
    np.testing.assert_array_equal(
        np.asarray(cache_b.k[:, :, S], np.float32),
        np.asarray(cache.k[:, :, S], np.float32))


def test_deferred_commit_mla_equivalence():
    cfg = smoke_config("deepseek-v2-lite-16b")
    cfg_d = dataclasses.replace(cfg, defer_cache_write=True)
    params = transformer.init_params(cfg, jax.random.key(0))
    B, S = 2, 6
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab_size)
    _, cache = transformer.prefill(params, tokens[:, :S], cfg, max_len=S + 4)
    logits_a, _ = transformer.decode_step(params, cache, tokens[:, S:S + 1], cfg)
    logits_b, _, _ = transformer.decode_step(
        params, cache, tokens[:, S:S + 1], cfg_d)
    np.testing.assert_allclose(
        np.asarray(logits_a), np.asarray(logits_b), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "deepseek-v2-lite-16b"])
def test_grouped_dispatch_equivalence(arch):
    """Per-sequence dispatch groups == flat dispatch at high capacity."""
    cfg = smoke_config(arch)
    hi_cap = dataclasses.replace(cfg.moe, capacity_factor=8.0)
    cfg_flat = dataclasses.replace(cfg, moe=dataclasses.replace(
        hi_cap, dispatch_groups=0))
    cfg_grp = dataclasses.replace(cfg, moe=dataclasses.replace(
        hi_cap, dispatch_groups=4))
    params = transformer.init_params(cfg_flat, jax.random.key(0))
    tok = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    xa, _, _ = transformer.forward(params, tok, cfg_flat)
    xb, _, _ = transformer.forward(params, tok, cfg_grp)
    np.testing.assert_allclose(
        np.asarray(xa, np.float32), np.asarray(xb, np.float32),
        rtol=3e-4, atol=3e-4)


def test_gr_batched_beam_layout_equivalence():
    """(L, B, M, S, KV, hd) beam layout == flat (L, B*M, S, KV, hd)."""
    cfg = smoke_config("static-gr")
    cfg_b = dataclasses.replace(cfg, gr_batched_beams=True)
    params = transformer.init_params(cfg, jax.random.key(0))
    L_layers = cfg.n_layers
    B, M, S_h, S_sid = 2, 3, 6, 4
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim()
    rng = np.random.default_rng(0)
    hk = jnp.asarray(rng.normal(size=(L_layers, B, S_h, KV, hd)).astype(np.float32))
    hv = jnp.asarray(rng.normal(size=(L_layers, B, S_h, KV, hd)).astype(np.float32))
    bk = jnp.asarray(rng.normal(size=(L_layers, B, M, S_sid, KV, hd)).astype(np.float32))
    bv = jnp.asarray(rng.normal(size=(L_layers, B, M, S_sid, KV, hd)).astype(np.float32))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B * M, 1)).astype(np.int32))
    step = jnp.asarray(1, jnp.int32)

    logits_b, nbk, nbv = transformer.gr_decode_step(
        params, hk, hv, bk, bv, toks, step, cfg_b)
    flat = lambda a: a.reshape(L_layers, B * M, S_sid, KV, hd)
    logits_f, fbk, fbv = transformer.gr_decode_step(
        params, hk, hv, flat(bk), flat(bv), toks, step, cfg)
    np.testing.assert_allclose(
        np.asarray(logits_b), np.asarray(logits_f), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(flat(nbk)), np.asarray(fbk), rtol=2e-4, atol=2e-4)


def test_split_k_flag_is_noop_without_mesh():
    """decode_split_k with empty sp_axes must not change single-device math."""
    cfg = smoke_config("qwen1.5-110b")
    cfg_s = dataclasses.replace(cfg, decode_split_k=True, sp_axes=())
    params = transformer.init_params(cfg, jax.random.key(0))
    B, S = 2, 6
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab_size)
    _, cache = transformer.prefill(params, tokens[:, :S], cfg, max_len=S + 4)
    la, _ = transformer.decode_step(params, cache, tokens[:, S:S + 1], cfg)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with jax.set_mesh(mesh):
        cfg_s = dataclasses.replace(cfg_s, sp_axes=("data",))
        lb, _ = transformer.decode_step(params, cache, tokens[:, S:S + 1], cfg_s)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=2e-4,
                               atol=2e-4)
