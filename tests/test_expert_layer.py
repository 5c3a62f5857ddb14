"""The expert layer that holds a share of the experts, its routing rule,
and DeepSeek-V2's YaRN rotary scaling, against plain float32 formulas."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_bundle
from repro.configs.base import MoEConfig, YarnScaling
from repro.models import moe
from repro.models.layers import apply_rope, rope_frequencies, swiglu

D, E, K, T = 32, 16, 6, 24
UNCUT = MoEConfig(n_experts=E, top_k=K, d_expert=8, n_shared=1, d_shared=16,
                  norm_topk_prob=False)


def _silu(x):
    return x / (1 + np.exp(-x))


def _reference(p, x, top_k, renorm, held=range(E)):
    """The layer as its equations read: softmax over every expert, top-k,
    the held experts' SwiGLUs weighted, plus the shared SwiGLU."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    logits = x @ p["router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:top_k]
        w = probs[t, top] / (probs[t, top].sum() if renorm else 1.0)
        for e, we in zip(top, w):
            if e in held:
                i = list(held).index(e)
                h = _silu(x[t] @ p["w1"][i]) * (x[t] @ p["w3"][i])
                out[t] += we * (h @ p["w2"][i])
    s = p["shared"]
    return out + (_silu(x @ s["w1"]) * (x @ s["w3"])) @ s["w2"]


def _layer(cfg=UNCUT, seed=0):
    p = moe.moe_init(jax.random.key(seed), D, cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(seed + 1), (T, D))
    return p, x


def test_expert_shares_add_up_to_the_uncut_layer():
    """8 chips hold 2 experts each at their offsets; with the shared
    expert counted once, their parts add up to the whole layer."""
    p, x = _layer()
    whole, rows = moe.moe_ffn_dropless(p, x, UNCUT)
    np.testing.assert_allclose(whole, _reference(p, x, K, False),
                               rtol=1e-4, atol=1e-5)
    parts = []
    for chip in range(8):
        cfg = dataclasses.replace(UNCUT, n_experts=2, n_routed=E,
                                  expert_offset=2 * chip)
        share = dict(p, **{n: p[n][2 * chip:2 * chip + 2]
                           for n in ("w1", "w3", "w2")})
        y, r = moe.moe_ffn_dropless(share, x, cfg)
        np.testing.assert_allclose(
            y, _reference(share, x, K, False, range(2 * chip, 2 * chip + 2)),
            rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(r, rows[2 * chip:2 * chip + 2])
        parts.append(y)
    shared = swiglu(p["shared"], x)
    np.testing.assert_allclose(sum(parts) - 7 * shared, whole,
                               rtol=1e-4, atol=1e-5)
    assert rows.shape == (E,) and int(rows.sum()) == T * K


def test_renormalised_shares_add_up_to_the_uncut_layer():
    """Mixtral's rule (top-k weights renormalised, no shared expert): 4
    chips of 4 experts each add up to the whole layer."""
    cfg = MoEConfig(n_experts=E, top_k=2, d_expert=8)
    p, x = _layer(cfg, seed=5)
    whole, _ = moe.moe_ffn_dropless(p, x, cfg)
    np.testing.assert_allclose(
        whole, _reference(dict(p, shared=_no_shared(cfg)), x, 2, True),
        rtol=1e-4, atol=1e-5)
    parts = [moe.moe_ffn_dropless(
        dict(p, **{n: p[n][4 * c:4 * c + 4] for n in ("w1", "w3", "w2")}),
        x, dataclasses.replace(cfg, n_experts=4, n_routed=E,
                               expert_offset=4 * c))[0] for c in range(4)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)


def test_held_expert_rows_count_the_routed_pairs():
    """``rows[e]`` is the number of rows whose top-k holds expert
    ``expert_offset + e``; pairs routed to experts held elsewhere are not
    counted."""
    cfg = dataclasses.replace(UNCUT, n_experts=4, n_routed=E,
                              expert_offset=4)
    p, x = _layer(UNCUT, seed=6)
    p = dict(p, **{n: p[n][4:8] for n in ("w1", "w3", "w2")})
    _, rows = moe.moe_ffn_dropless(p, x, cfg)
    _, _, ids = moe.route(p, x, cfg)
    want = np.bincount(np.asarray(ids).reshape(-1), minlength=E)[4:8]
    np.testing.assert_array_equal(rows, want)
    assert int(rows.sum()) < T * K  # the other 12 experts took the rest


def test_dropless_when_every_row_routes_to_one_held_expert():
    """Every row picks held expert 0: the capacity-bounded dispatch drops
    most of them, the served layer computes them all."""
    cfg = MoEConfig(n_experts=4, top_k=2, d_expert=8, n_routed=8)
    p, x = _layer(cfg, seed=3)
    x = jnp.abs(x) + 0.1
    p = dict(p, router=p["router"].at[:, 0].set(10.0))
    y, rows = moe.moe_ffn_dropless(p, x, cfg)
    assert rows.shape == (4,) and int(rows[0]) == T
    want = _reference(dict(p, shared=_no_shared(cfg)), x, 2, True,
                      range(4))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    capped, _ = moe.moe_ffn(p, x, cfg)  # capacity 16 rows per expert
    assert not np.allclose(capped, want, rtol=1e-2, atol=1e-3)


def _no_shared(cfg):
    return {"w1": np.zeros((D, 1)), "w3": np.zeros((D, 1)),
            "w2": np.zeros((1, D))}


@pytest.mark.parametrize("renorm", [True, False],
                         ids=["mixtral-renormalised", "deepseek-softmax"])
def test_router_weights_follow_the_config(renorm):
    cfg = dataclasses.replace(UNCUT, norm_topk_prob=renorm)
    p, x = _layer(cfg)
    probs, w, i = moe.route(p, x, cfg)
    top = np.take_along_axis(np.asarray(probs), np.asarray(i), -1)
    np.testing.assert_allclose(
        w, top / (top.sum(-1, keepdims=True) if renorm else 1.0), rtol=1e-6)
    assert get_bundle("mixtral-8x7b").config.moe.norm_topk_prob
    ds = get_bundle("deepseek-v2-lite-16b").config.moe
    assert not ds.norm_topk_prob


YARN = YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)


def test_yarn_frequencies_and_softmax_scale():
    """DeepSeek-V2's YaRN at hand-computed points: 64 rotary dims over
    theta 1e4 ramp from pair 10 (floor of 64 ln(4096 / 64 pi) / 2 ln 1e4,
    kept) to pair 23 (ceil of 64 ln(4096 / 2 pi) / 2 ln 1e4, divided by
    40); the scale gains (0.1 * 0.707 * ln 40 + 1)^2 = 1.58963."""
    f = np.asarray(rope_frequencies(64, 1e4, YARN), np.float64)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)
    assert f[16] == pytest.approx(base[16] * (ramp / 40 + 1 - ramp), 1e-6)
    np.testing.assert_allclose(rope_frequencies(64, 1e4), base, rtol=1e-6)
    cfg = get_bundle("deepseek-v2-lite-16b").config
    assert cfg.rope_scaling == YARN and cfg.norm_eps == 1e-6
    m2 = cfg.mla_softmax_scale() * math.sqrt(128 + 64)
    assert m2 == pytest.approx(1.58963, abs=5e-6)
    # mscale equals mscale_all_dim: cos and sin keep their size
    x = jax.random.normal(jax.random.key(0), (5, 64))
    pos = jnp.arange(5)
    ang = pos[:, None] * jnp.asarray(f, jnp.float32)
    x1, x2 = x[:, 0::2], x[:, 1::2]
    want = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)
    np.testing.assert_allclose(apply_rope(x, pos, 1e4, YARN),
                               want.reshape(5, 64), rtol=1e-5, atol=1e-5)
