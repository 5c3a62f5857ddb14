"""Decoder-only transformer family covering all five assigned LM archs.

One implementation, configured by :class:`TransformerConfig`:
  * GQA / MHA (+ optional QKV bias — qwen1.5 family)     — stablelm, qwen,
    codeqwen
  * sliding-window attention with a ring KV cache        — mixtral
  * MLA (multi-head latent attention, DeepSeek-V2)       — deepseek-v2-lite,
    with the *absorbed* decode path (latent-space scores; the full K/V are
    never materialized at decode time)
  * MoE FFNs (Mixtral 8x top-2; DeepSeek 64x top-6 + 2 shared, first layer
    dense)

Layers are stacked and driven by ``lax.scan`` (O(1) HLO size in depth) with
``jax.checkpoint`` inside the scan body for activation remat; training CE is
computed in sequence chunks so the (tokens, vocab) logits tensor is never
materialized.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import TransformerConfig
from repro.models import kvcache as kv_lib
from repro.models.attention import (
    NEG,
    chunked_causal_attention,
    decode_attention,
)
from repro.models.layers import (
    apply_rope,
    dense_init,
    rms_norm,
    rms_norm_init,
    swiglu,
    swiglu_init,
    _he,
)
from repro.models.moe import moe_ffn, moe_ffn_dropless, moe_init

__all__ = [
    "init_params", "param_specs", "forward", "lm_loss",
    "lm_loss_trie_aware", "prefill", "decode_step", "gr_decode_step",
    "paged_decode_step", "init_cache",
]


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# --------------------------------------------------------------------------
# Parameter initialization
# --------------------------------------------------------------------------


def _attn_init(key, cfg: TransformerConfig, dtype):
    D = cfg.d_model
    if cfg.attention == "mla":
        k1, k2, k3, k4 = jax.random.split(key, 4)
        qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {
            "wq": _he(k1, (D, cfg.n_heads * qk_dim), dtype),
            "w_kv_a": _he(k2, (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dtype),
            "kv_norm": rms_norm_init(cfg.kv_lora_rank, dtype),
            "w_kv_b": _he(
                k3,
                (cfg.kv_lora_rank,
                 cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                dtype,
            ),
            "wo": _he(k4, (cfg.n_heads * cfg.v_head_dim, D), dtype,
                      fan_in=cfg.n_heads * cfg.v_head_dim),
        }
    hd = cfg.resolved_head_dim()
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, D, cfg.n_heads * hd, dtype, bias=cfg.qkv_bias),
        "wk": dense_init(k2, D, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wv": dense_init(k3, D, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wo": {"w": _he(k4, (cfg.n_heads * hd, D), dtype, fan_in=cfg.n_heads * hd)},
    }
    return p


def _layer_init(key, cfg: TransformerConfig, moe_layer: bool, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "ln_attn": rms_norm_init(cfg.d_model, dtype),
        "attn": _attn_init(k1, cfg, dtype),
        "ln_ffn": rms_norm_init(cfg.d_model, dtype),
    }
    if moe_layer:
        p["moe"] = moe_init(k2, cfg.d_model, cfg.moe, dtype)
    else:
        d_ff = cfg.d_ff
        if cfg.moe is not None and cfg.moe.d_ff_dense:
            d_ff = cfg.moe.d_ff_dense
        p["ffn"] = swiglu_init(k2, cfg.d_model, d_ff, dtype)
    return p


def init_params(cfg: TransformerConfig, key: jax.Array):
    dtype = _dtype(cfg)
    k_emb, k_unemb, k_dense, k_moe = jax.random.split(key, 4)
    n_dense = cfg.moe.first_dense_layers if cfg.moe else cfg.n_layers
    n_scan = cfg.n_layers - n_dense if cfg.moe else cfg.n_layers
    if cfg.moe is None:
        n_dense, n_scan = cfg.n_layers, 0

    params = {
        "emb": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02
                ).astype(dtype),
        "final_norm": rms_norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unemb"] = _he(k_unemb, (cfg.d_model, cfg.vocab_size), dtype)
    if n_dense:
        keys = jax.random.split(k_dense, n_dense)
        params["dense_layers"] = jax.vmap(
            lambda k: _layer_init(k, cfg, moe_layer=False, dtype=dtype)
        )(keys)
    if n_scan:
        keys = jax.random.split(k_moe, n_scan)
        params["moe_layers"] = jax.vmap(
            lambda k: _layer_init(k, cfg, moe_layer=True, dtype=dtype)
        )(keys)
    return params


def param_specs(cfg: TransformerConfig):
    """Shape/dtype pytree without allocating (for the dry-run)."""
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0)
    )


# --------------------------------------------------------------------------
# Attention application (full-sequence path)
# --------------------------------------------------------------------------


def _attn_full(p, x, cfg: TransformerConfig, q_offset=0):
    B, S, D = x.shape
    if cfg.attention == "mla":
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        H, lora, vd = cfg.n_heads, cfg.kv_lora_rank, cfg.v_head_dim
        with jax.named_scope("qkv_proj"):
            q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
            q_nope, q_rope = q[..., :nope], q[..., nope:]
            kv_a = x @ p["w_kv_a"]  # (B, S, lora + rope)
            c_kv = rms_norm(p["kv_norm"], kv_a[..., :lora], cfg.norm_eps)
            k_rope = kv_a[..., lora:][:, :, None, :]  # (B, S, 1, rope)
            pos = q_offset + jnp.arange(S)
            q_rope = apply_rope(q_rope, pos[None], cfg.rope_theta,
                                cfg.rope_scaling)
            k_rope = apply_rope(k_rope, pos[None], cfg.rope_theta,
                                cfg.rope_scaling)
            kv_b = (c_kv @ p["w_kv_b"]).reshape(B, S, H, nope + vd)
            k_nope, v = kv_b[..., :nope], kv_b[..., nope:]
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], axis=-1
            )
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
        with jax.named_scope("attention"):
            out = chunked_causal_attention(
                q, k, v, chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
                window=cfg.sliding_window, q_offset=q_offset,
                scale=cfg.mla_softmax_scale(), unroll=cfg.inner_unroll,
            )
        with jax.named_scope("out_proj"):
            cache_kv = (c_kv, k_rope[:, :, 0, :])
            return out.reshape(B, S, H * vd) @ p["wo"], cache_kv
    hd = cfg.resolved_head_dim()
    H, KV = cfg.n_heads, cfg.n_kv_heads

    def proj(pp, width):
        y = x @ pp["w"]
        if "b" in pp:
            y = y + pp["b"]
        return y.reshape(B, S, width, hd)

    with jax.named_scope("qkv_proj"):
        q = proj(p["wq"], H)
        k = proj(p["wk"], KV)
        v = proj(p["wv"], KV)
        pos = q_offset + jnp.arange(S)
        q = apply_rope(q, pos[None], cfg.rope_theta)
        k = apply_rope(k, pos[None], cfg.rope_theta)
    with jax.named_scope("attention"):
        out = chunked_causal_attention(
            q, k, v, chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv,
            window=cfg.sliding_window, q_offset=q_offset,
            unroll=cfg.inner_unroll,
        )
    with jax.named_scope("out_proj"):
        return out.reshape(B, S, H * hd) @ p["wo"]["w"], (k, v)


def _layer_fwd(p, x, cfg: TransformerConfig, moe_layer: bool, q_offset=0,
               dropless: bool = False):
    attn_out, cache_kv = _attn_with_norm(p, x, cfg, q_offset)
    with jax.named_scope("out_proj"):
        x = x + attn_out
    with jax.named_scope("ffn"):
        h = rms_norm(p["ln_ffn"], x, cfg.norm_eps)
        aux = jnp.zeros((), jnp.float32)
        if moe_layer and dropless:
            y, _, _ = moe_ffn_dropless(p["moe"], h, cfg.moe)
        elif moe_layer:
            y, aux = moe_ffn(p["moe"], h, cfg.moe)
        else:
            y = swiglu(p["ffn"], h)
        x = x + y
    return x, cache_kv, aux


def _attn_with_norm(p, x, cfg, q_offset):
    with jax.named_scope("qkv_proj"):
        h = rms_norm(p["ln_attn"], x, cfg.norm_eps)
    return _attn_full(p["attn"], h, cfg, q_offset)


# --------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# --------------------------------------------------------------------------


def _sp_constraint(x, cfg: TransformerConfig):
    """Sequence-parallel residual-stream sharding (batch=dp, seq=model)."""
    if not cfg.sp_axes:
        return x
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        x, P(tuple(cfg.sp_axes), "model", None)
    )


def forward(params, tokens: jax.Array, cfg: TransformerConfig,
            collect_cache: bool = False):
    """tokens (B, S) -> hidden (B, S, D) [+ per-layer cache stacks, aux loss].

    With ``collect_cache`` (the serving prefill) expert layers compute
    every routed row (:func:`~repro.models.moe.moe_ffn_dropless`); training
    keeps the capacity-bounded dispatch.
    """
    x = jnp.take(params["emb"], tokens, axis=0)
    aux_total = jnp.zeros((), jnp.float32)

    def make_body(moe_layer: bool):
        def body(x, p):
            x = _sp_constraint(x, cfg)
            y, cache_kv, aux = _layer_fwd(p, x, cfg, moe_layer,
                                          dropless=collect_cache)
            y = _sp_constraint(y, cfg)
            ys = cache_kv if collect_cache else None
            return y, (ys, aux)

        if cfg.remat:
            body = jax.checkpoint(body)
        return body

    caches = []
    if "dense_layers" in params:
        x, (c, aux) = jax.lax.scan(make_body(False), x, params["dense_layers"],
                                   unroll=cfg.layer_unroll)
        caches.append(c)
        aux_total = aux_total + jnp.sum(aux)
    if "moe_layers" in params:
        x, (c, aux) = jax.lax.scan(make_body(True), x, params["moe_layers"],
                                   unroll=cfg.layer_unroll)
        caches.append(c)
        aux_total = aux_total + jnp.sum(aux)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, caches, aux_total


def _unemb(params, cfg):
    return params["emb"].T if cfg.tie_embeddings else params["unemb"]


def lm_loss(params, tokens: jax.Array, cfg: TransformerConfig,
            ce_chunk: int | None = None):
    """Next-token CE, computed in sequence chunks (no (T, V) logits tensor).

    The full sequence is forwarded (keeping S power-of-two aligned with the
    shard grid — slicing to S-1 would break sequence sharding and MoE group
    alignment); the final position is masked out of the loss instead.
    """
    x, _, aux = forward(params, tokens, cfg)
    labels = jnp.roll(tokens, -1, axis=1)
    B, S, D = x.shape
    valid = (jnp.arange(S) < S - 1).astype(jnp.float32)
    w = _unemb(params, cfg)
    chunk = min(ce_chunk or cfg.ce_chunk, S)
    while S % chunk:
        chunk //= 2
    n = S // chunk
    xs = x.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    vs = valid.reshape(n, 1, chunk)

    @jax.checkpoint
    def body(tot, inp):
        xc, lc, vc = inp
        logits = (xc @ w).astype(jnp.float32)  # (B, chunk, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum((lse - ll) * vc), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls, vs),
                          unroll=n if cfg.inner_unroll else 1)
    return tot / (B * (S - 1)) + aux


def lm_loss_trie_aware(params, tokens: jax.Array, cfg: TransformerConfig,
                       adm_mask: jax.Array, weight: float):
    """Next-token CE + the trie-aware admissible-mass auxiliary loss.

    ``adm_mask`` is (B, S, V) bool: the constrained decoder's admissible
    token set at the position of the token AT each index (the per-prefix
    sets from :mod:`repro.scenarios.trie_signal`, gathered per item).  The
    auxiliary term is the probability mass the model puts OUTSIDE the
    admissible set, in log space::

        logsumexp(logits) - logsumexp(logits[admissible])

    i.e. -log P(admissible) — zero when the model concentrates on tokens
    the trie will accept, so training pushes mass toward decodable SIDs
    (Trie-Aware Transformers, arxiv 2602.21677).  Targets drawn from the
    trie are always admissible, so the CE target never sits outside its
    own mask.  Dense (B, S, V) logits — this loss serves the small GR
    retrieval model (V = a few hundred), not the chunked-CE giants.
    """
    x, _, aux = forward(params, tokens, cfg)
    labels = jnp.roll(tokens, -1, axis=1)
    # align masks with labels: position p scores the token at p+1
    mask = jnp.roll(adm_mask, -1, axis=1)
    B, S, D = x.shape
    valid = (jnp.arange(S) < S - 1).astype(jnp.float32)
    w = _unemb(params, cfg)
    logits = (x @ w).astype(jnp.float32)  # (B, S, V)
    lse_full = jax.nn.logsumexp(logits, axis=-1)
    # -1e30 (not -inf): an all-False row would otherwise yield nan grads
    lse_adm = jax.nn.logsumexp(
        jnp.where(mask, logits, jnp.float32(-1e30)), axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    denom = B * (S - 1)
    ce = jnp.sum((lse_full - ll) * valid) / denom
    trie_aux = jnp.sum((lse_full - lse_adm) * valid) / denom
    return ce + aux + weight * trie_aux


# --------------------------------------------------------------------------
# Serving: prefill + single-token decode with KV caches
# --------------------------------------------------------------------------


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    dtype = _dtype(cfg)
    if cfg.attention == "mla":
        return kv_lib.init_mla_cache(
            cfg.n_layers, batch, max_len, cfg.kv_lora_rank,
            cfg.qk_rope_head_dim, dtype,
        )
    return kv_lib.init_kv_cache(
        cfg.n_layers, batch, max_len, cfg.n_kv_heads,
        cfg.resolved_head_dim(), v_dim=None, dtype=dtype,
        window=cfg.sliding_window,
    )


def prefill(params, tokens: jax.Array, cfg: TransformerConfig,
            max_len: int | None = None):
    """Full-sequence pass that also builds the decode cache.

    Returns (last_token_logits, cache).  ``max_len`` reserves extra decode
    slots; for ring (SWA) caches only the last ``window`` positions are
    retained regardless.
    """
    B, S = tokens.shape
    max_len = max_len or S
    x, caches, _ = forward(params, tokens, cfg, collect_cache=True)
    logits = (x[:, -1:, :] @ _unemb(params, cfg)).astype(jnp.float32)

    def pad_to(arr, n_slots):
        pad = n_slots - arr.shape[2]
        if pad <= 0:
            return arr
        cfg_pad = [(0, 0)] * arr.ndim
        cfg_pad[2] = (0, pad)
        return jnp.pad(arr, cfg_pad)

    if cfg.attention == "mla":
        (c_kv, k_rope) = _merge(caches)
        slot_pos = jnp.concatenate(
            [jnp.arange(S, dtype=jnp.int32),
             jnp.full((max_len - S,), -1, jnp.int32)]
        ) if max_len > S else jnp.arange(S, dtype=jnp.int32)
        cache = kv_lib.MLACache(
            c_kv=pad_to(c_kv, max_len), k_rope=pad_to(k_rope, max_len),
            slot_pos=slot_pos, pos=jnp.asarray(S, jnp.int32),
        )
        return logits, cache
    ks, vs = _merge(caches)
    window = cfg.sliding_window
    if window and window < max_len:
        # keep last `window` positions at their ring slots (slot = pos % window)
        keep = min(window, S)
        positions = jnp.arange(S - keep, S)
        slots = positions % window
        k_ring = jnp.zeros(ks.shape[:2] + (window,) + ks.shape[3:], ks.dtype)
        v_ring = jnp.zeros(vs.shape[:2] + (window,) + vs.shape[3:], vs.dtype)
        k_ring = k_ring.at[:, :, slots].set(ks[:, :, S - keep:])
        v_ring = v_ring.at[:, :, slots].set(vs[:, :, S - keep:])
        slot_pos = jnp.full((window,), -1, jnp.int32).at[slots].set(positions)
        cache = kv_lib.KVCache(k=k_ring, v=v_ring, slot_pos=slot_pos,
                               pos=jnp.asarray(S, jnp.int32), ring=True)
    else:
        slot_pos = jnp.concatenate(
            [jnp.arange(S, dtype=jnp.int32),
             jnp.full((max_len - S,), -1, jnp.int32)]
        ) if max_len > S else jnp.arange(S, dtype=jnp.int32)
        cache = kv_lib.KVCache(
            k=pad_to(ks, max_len), v=pad_to(vs, max_len),
            slot_pos=slot_pos, pos=jnp.asarray(S, jnp.int32), ring=False,
        )
    return logits, cache


def _merge(caches):
    """Concatenate per-layer-group cache stacks along the layer axis."""
    if len(caches) == 1:
        return caches[0]
    parts = list(zip(*caches))
    return tuple(jnp.concatenate(p, axis=0) for p in parts)


def _decode_attn_gqa(p, x, cfg, k_cache, v_cache, slot_pos, pos, slot=None):
    B = x.shape[0]
    hd = cfg.resolved_head_dim()
    H, KV = cfg.n_heads, cfg.n_kv_heads

    def proj(pp, width):
        y = x @ pp["w"]
        if "b" in pp:
            y = y + pp["b"]
        return y.reshape(B, 1, width, hd)

    with jax.named_scope("qkv_proj"):
        q = proj(p["wq"], H)
        k_new = proj(p["wk"], KV)
        v_new = proj(p["wv"], KV)
        q = apply_rope(q, pos[None, None], cfg.rope_theta)
        k_new = apply_rope(k_new, pos[None, None], cfg.rope_theta)
        if cfg.decode_split_k:
            # replicate the tiny per-token tensors over `model`; the cache
            # stays sequence-sharded and attention contracts shard-locally
            # (split-K).
            from jax.sharding import PartitionSpec as P

            spec = P(tuple(cfg.sp_axes) or None, None, None, None)
            q = jax.lax.with_sharding_constraint(q, spec)
            k_new = jax.lax.with_sharding_constraint(k_new, spec)
            v_new = jax.lax.with_sharding_constraint(v_new, spec)
    if cfg.defer_cache_write:
        # Read-only cache + separate fresh-token score: no dynamic write into
        # the sequence-sharded cache (which would force a full all-gather).
        # Grouped einsum: never materialize the G-times repeated cache.
        with jax.named_scope("attention"):
            groups = H // KV
            qg = q.reshape(B, 1, KV, groups, hd)
            s_c = jnp.einsum(
                "bqkgd,bskd->bkgqs", qg, k_cache,
                preferred_element_type=jnp.float32,
            ) * hd ** -0.5  # (B, KV, G, 1, S)
            mask = (slot_pos >= 0) & (slot_pos < pos)
            if cfg.sliding_window is not None:
                mask = mask & (slot_pos > pos - cfg.sliding_window)
            s_c = jnp.where(mask[None, None, None, None, :], s_c, -1e30)
            s_n = jnp.einsum(
                "bqkgd,bqkd->bkgq", qg, k_new,
                preferred_element_type=jnp.float32,
            )[..., None] * hd ** -0.5  # (B, KV, G, 1, 1)
            prob = jax.nn.softmax(jnp.concatenate([s_c, s_n], -1), axis=-1)
            out_c = jnp.einsum(
                "bkgqs,bskd->bqkgd", prob[..., :-1].astype(v_cache.dtype),
                v_cache, preferred_element_type=jnp.float32,
            )  # (B, 1, KV, G, hd) f32
            p_new = prob[..., 0, -1]  # (B, KV, G)
            out_n = p_new[:, None, :, :, None] \
                * v_new.astype(jnp.float32)[:, :, :, None, :]
            out = (out_c + out_n).reshape(B, 1, H, hd).astype(x.dtype)
        with jax.named_scope("out_proj"):
            return out.reshape(B, 1, H * hd) @ p["wo"]["w"], (k_new, v_new)
    with jax.named_scope("kv_write"):
        if slot is None:
            # standalone call: derive the write slot from the config
            # (decode_step passes the cache-derived slot so the two can
            # never disagree)
            slots = k_cache.shape[1]
            ring = cfg.sliding_window is not None and cfg.sliding_window <= slots
            slot = jnp.where(ring, pos % slots, jnp.minimum(pos, slots - 1))
        k_cache = kv_lib.write_slot(k_cache, k_new, slot)
        v_cache = kv_lib.write_slot(v_cache, v_new, slot)
    with jax.named_scope("attention"):
        out = decode_attention(
            q, k_cache, v_cache, slot_pos, pos, window=cfg.sliding_window
        )
    with jax.named_scope("out_proj"):
        return out.reshape(B, 1, H * hd) @ p["wo"]["w"], (k_cache, v_cache)


def _decode_attn_mla(p, x, cfg, c_kv_cache, k_rope_cache, slot_pos, pos):
    """Absorbed MLA decode: scores and context stay in latent space."""
    B = x.shape[0]
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    H, lora, vd = cfg.n_heads, cfg.kv_lora_rank, cfg.v_head_dim
    scale = cfg.mla_softmax_scale()
    q = (x @ p["wq"]).reshape(B, 1, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, pos[None, None], cfg.rope_theta,
                        cfg.rope_scaling)
    kv_a = x @ p["w_kv_a"]
    c_new = rms_norm(p["kv_norm"], kv_a[..., :lora], cfg.norm_eps)
    kr_new = apply_rope(kv_a[..., lora:], pos[None, None], cfg.rope_theta,
                        cfg.rope_scaling)
    if not cfg.defer_cache_write:
        slots = c_kv_cache.shape[1]
        slot = jnp.minimum(pos, slots - 1)
        c_kv_cache = kv_lib.write_slot(c_kv_cache, c_new, slot)
        k_rope_cache = kv_lib.write_slot(k_rope_cache, kr_new, slot)

    w_kv_b = p["w_kv_b"].reshape(lora, H, nope + vd)
    w_uk, w_uv = w_kv_b[..., :nope], w_kv_b[..., nope:]
    q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, w_uk)  # (B,1,H,lora)
    s = (
        jnp.einsum("bqhl,bsl->bhqs", q_lat.astype(jnp.float32),
                   c_kv_cache.astype(jnp.float32))
        + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                     k_rope_cache.astype(jnp.float32))
    ) * scale
    mask = (slot_pos >= 0) & (
        (slot_pos < pos) if cfg.defer_cache_write else (slot_pos <= pos)
    )
    s = jnp.where(mask[None, None, None, :], s, -1e30)
    if cfg.defer_cache_write:
        # separate fresh-token score/context term (read-only cache)
        s_n = (
            jnp.einsum("bqhl,bql->bhq", q_lat.astype(jnp.float32),
                       c_new.astype(jnp.float32))
            + jnp.einsum("bqhr,bqr->bhq", q_rope.astype(jnp.float32),
                         kr_new.astype(jnp.float32))
        )[..., None] * scale
        probs = jax.nn.softmax(jnp.concatenate([s, s_n], -1), axis=-1)
        ctx = jnp.einsum("bhqs,bsl->bqhl", probs[..., :-1],
                         c_kv_cache.astype(jnp.float32))
        ctx = ctx + probs[:, :, 0, -1][:, None, :, None] \
            * c_new.astype(jnp.float32)[:, :, None, :]
        out = jnp.einsum("bqhl,lhv->bqhv", ctx.astype(x.dtype), w_uv)
        return out.reshape(B, 1, H * vd) @ p["wo"], (c_new, kr_new)
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bsl->bqhl", probs, c_kv_cache.astype(jnp.float32))
    out = jnp.einsum("bqhl,lhv->bqhv", ctx.astype(x.dtype), w_uv)
    return out.reshape(B, 1, H * vd) @ p["wo"], (c_kv_cache, k_rope_cache)


def _shared_history_attention(q, hist_k, hist_v, sfx_k, sfx_v, sfx_valid):
    """Attention of each row's M beams over the row's history, held once,
    and over each beam's own decoded suffix, under one softmax.

    q (B, M, H, hd); hist_k/v (B, S, KV, hd); sfx_k/v (B, M, Ls, KV, hd);
    sfx_valid (B, Ls) marks the suffix columns each row attends (every
    history column is attended).  The (KV, G)-factored query contracts both
    caches directly, so neither is repeated per beam or per head group.
    Returns (B, M, H, hd) in q's dtype.
    """
    B, M, H, hd = q.shape
    S, KV = hist_k.shape[1], hist_k.shape[2]
    qg = q.reshape(B, M, KV, H // KV, hd)
    scale = hd ** -0.5
    s_h = jnp.einsum("bmkgd,bskd->bkmgs", qg, hist_k,
                     preferred_element_type=jnp.float32) * scale
    s_s = jnp.einsum("bmkgd,bmskd->bkmgs", qg, sfx_k,
                     preferred_element_type=jnp.float32) * scale
    s_s = jnp.where(sfx_valid[:, None, None, None, :], s_s, NEG)
    p = jax.nn.softmax(jnp.concatenate([s_h, s_s], axis=-1), axis=-1)
    p = p.astype(hist_v.dtype)
    out = jnp.einsum("bkmgs,bskd->bmkgd", p[..., :S], hist_v,
                     preferred_element_type=jnp.float32) + jnp.einsum(
        "bkmgs,bmskd->bmkgd", p[..., S:], sfx_v,
        preferred_element_type=jnp.float32)
    return out.reshape(B, M, H, hd).astype(q.dtype)


def _latent_attention(q_lat, q_rope, hist_c, hist_r, sfx_c, sfx_r,
                      sfx_valid, scale):
    """:func:`_shared_history_attention` for latent attention, absorbed:
    the query's no-rope part, already through ``W_uk`` (``q_lat``), scores
    the latents and its rope part the rotated keys, over the row's history
    and the beam's suffix under one softmax; the context stays a latent.

    q_lat (B, M, H, lora), q_rope (B, M, H, rope); hist_c/r (B, S, lora |
    rope); sfx_c/r (B, M, Ls, lora | rope); sfx_valid (B, Ls).  Returns
    the latent context (B, M, H, lora) in q_lat's dtype.
    """
    S = hist_c.shape[1]
    f32 = jnp.float32
    s_h = (jnp.einsum("bmhl,bsl->bmhs", q_lat, hist_c,
                      preferred_element_type=f32)
           + jnp.einsum("bmhr,bsr->bmhs", q_rope, hist_r,
                        preferred_element_type=f32)) * scale
    s_s = (jnp.einsum("bmhl,bmtl->bmht", q_lat, sfx_c,
                      preferred_element_type=f32)
           + jnp.einsum("bmhr,bmtr->bmht", q_rope, sfx_r,
                        preferred_element_type=f32)) * scale
    s_s = jnp.where(sfx_valid[:, None, None, :], s_s, NEG)
    p = jax.nn.softmax(jnp.concatenate([s_h, s_s], axis=-1), axis=-1)
    p = p.astype(hist_c.dtype)
    ctx = jnp.einsum("bmhs,bsl->bmhl", p[..., :S], hist_c,
                     preferred_element_type=f32) + jnp.einsum(
        "bmht,bmtl->bmhl", p[..., S:], sfx_c, preferred_element_type=f32)
    return ctx.astype(q_lat.dtype)


def _gr_attn_gqa(a, h, hk, hv, bk, bv, i, pos, sid_step, sfx_valid, cfg):
    """GQA for :func:`gr_decode_step`: writes this token's K/V into layer
    ``i``'s suffix column ``sid_step`` and attends history and suffix."""
    BM, B = h.shape[0], hk.shape[0]
    M = BM // B
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim()
    with jax.named_scope("qkv_proj"):
        def proj(pp, width):
            y = h @ pp["w"]
            if "b" in pp:
                y = y + pp["b"]
            return y.reshape(BM, 1, width, hd)

        q = apply_rope(proj(a["wq"], H), pos[None, None], cfg.rope_theta)
        k_new = apply_rope(proj(a["wk"], KV), pos[None, None],
                           cfg.rope_theta)
        v_new = proj(a["wv"], KV)
    with jax.named_scope("kv_write"):
        at = (i, 0, 0, sid_step, 0, 0)
        bk = jax.lax.dynamic_update_slice(
            bk, k_new.reshape(1, B, M, 1, KV, hd).astype(bk.dtype), at)
        bv = jax.lax.dynamic_update_slice(
            bv, v_new.reshape(1, B, M, 1, KV, hd).astype(bv.dtype), at)
    with jax.named_scope("attention"):
        out = _shared_history_attention(
            q.reshape(B, M, H, hd), hk, hv, bk[i], bv[i], sfx_valid)
    with jax.named_scope("out_proj"):
        return out.reshape(BM, 1, H * hd) @ a["wo"]["w"], bk, bv


def _gr_attn_mla(a, h, hc, hr, sc, sr, i, pos, sid_step, sfx_valid, cfg):
    """Latent attention for :func:`gr_decode_step`, absorbed: this token's
    latent and rotated key go into layer ``i``'s suffix column
    ``sid_step``; ``W_uk`` folds into the query and ``W_uv`` into the
    output, so no K or V is ever expanded."""
    BM, B = h.shape[0], hc.shape[0]
    M = BM // B
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    H, lora, vd = cfg.n_heads, cfg.kv_lora_rank, cfg.v_head_dim
    w_kv_b = a["w_kv_b"].reshape(lora, H, nope + vd)
    with jax.named_scope("qkv_proj"):
        q = (h @ a["wq"]).reshape(BM, 1, H, nope + rope)
        q_rope = apply_rope(q[..., nope:], pos[None, None], cfg.rope_theta,
                            cfg.rope_scaling)
        kv_a = h @ a["w_kv_a"]  # (BM, 1, lora + rope)
        c_new = rms_norm(a["kv_norm"], kv_a[..., :lora], cfg.norm_eps)
        r_new = apply_rope(kv_a[..., lora:], pos[None, None], cfg.rope_theta,
                           cfg.rope_scaling)
        q_lat = jnp.einsum("bqhn,lhn->bqhl", q[..., :nope],
                           w_kv_b[..., :nope])  # (BM, 1, H, lora)
    with jax.named_scope("kv_write"):
        at = (i, 0, 0, sid_step, 0)
        sc = jax.lax.dynamic_update_slice(
            sc, c_new.reshape(1, B, M, 1, lora).astype(sc.dtype), at)
        sr = jax.lax.dynamic_update_slice(
            sr, r_new.reshape(1, B, M, 1, rope).astype(sr.dtype), at)
    with jax.named_scope("attention"):
        ctx = _latent_attention(
            q_lat.reshape(B, M, H, lora), q_rope.reshape(B, M, H, rope),
            hc, hr, sc[i], sr[i], sfx_valid, cfg.mla_softmax_scale())
    with jax.named_scope("out_proj"):
        out = jnp.einsum("bmhl,lhv->bmhv", ctx, w_kv_b[..., nope:])
        return out.reshape(BM, 1, H * vd) @ a["wo"], sc, sr


def gr_decode_step(
    params,
    hist_k: jax.Array,  # (n_layers, B, S, KV, hd) history, one per request
    hist_v: jax.Array,
    beam_k: jax.Array,  # (n_layers, B*M | B, M, Ls, KV, hd) SID suffixes
    beam_v: jax.Array,
    tokens: jax.Array,  # (B*M, 1)
    sid_step: jax.Array,  # () suffix column of this step's token
    cfg: TransformerConfig,
):
    """One decode level of generative retrieval: the batch and SPMD
    retrievers' served step (DESIGN.md §14).

    Each request's history is held once and shared by its M beams; only
    the SID suffix is beam-private, in the flat ``(n_layers, B*M, ...)`` or
    the batched ``(n_layers, B, M, ...)`` layout, whichever the caller
    keeps.  The token at ``sid_step`` sits at position ``S + sid_step``, its
    state goes into suffix column ``sid_step``, and every beam attends its
    whole history and suffix columns ``0..sid_step`` under one softmax.

    The state is the decoder's: K and V for GQA/MHA; for latent attention
    (MLA) the normalised latents (``hist_k``/``beam_k``, last axis
    ``kv_lora_rank``) and the rotated keys (``hist_v``/``beam_v``, last
    axis ``qk_rope_head_dim``), attended absorbed.  The layers run stack by
    stack (``dense_layers``, then ``moe_layers``), each keeping its global
    index for its suffix slot; expert layers compute every routed row
    (:func:`~repro.models.moe.moe_ffn_dropless`).  No sliding window
    shorter than ``S + Ls``.

    The pieces carry :func:`decode_step`'s ``jax.named_scope`` names
    (``embed``, ``qkv_proj``, ``kv_write``, ``attention``, ``out_proj``,
    ``ffn``, ``unembed``; DESIGN.md §9); expert layers add ``moe_router``,
    ``moe_experts`` and ``moe_shared`` under ``ffn``.

    Returns ``(logits (B*M, 1, vocab), new beam_k, new beam_v)``.
    """
    return _gr_decode(params, hist_k, hist_v, beam_k, beam_v, tokens,
                      sid_step, cfg)[:3]


def _gr_decode(params, hist_a, hist_b, beam_a, beam_b, tokens, sid_step,
               cfg):
    """:func:`gr_decode_step`, and ``moe_ffn_dropless``'s counts summed
    over the expert layers (the rows each held expert received, and the
    row tiles its grouped matmuls visited), or None for a decoder without
    experts."""
    BM = tokens.shape[0]
    n, B, S = hist_a.shape[:3]
    M = BM // B
    Ls = beam_a.shape[2 if beam_a.ndim == hist_a.ndim else 3]
    pos = S + sid_step
    sfx_valid = jnp.broadcast_to(jnp.arange(Ls) <= sid_step, (B, Ls))
    attend = _gr_attn_mla if cfg.attention == "mla" else _gr_attn_gqa
    with jax.named_scope("embed"):
        x = jnp.take(params["emb"], tokens, axis=0)  # (BM, 1, D)

    def body(carry, inp):
        x, sa, sb, rows, tiles = carry
        p, ha, hb, i = inp
        with jax.named_scope("qkv_proj"):
            h = rms_norm(p["ln_attn"], x, cfg.norm_eps)
        out, sa, sb = attend(p["attn"], h, ha, hb, sa, sb, i, pos, sid_step,
                             sfx_valid, cfg)
        with jax.named_scope("out_proj"):
            x = x + out
        with jax.named_scope("ffn"):
            h = rms_norm(p["ln_ffn"], x, cfg.norm_eps)
            if "moe" in p:
                y, r, t = moe_ffn_dropless(p["moe"], h, cfg.moe)
                rows, tiles = rows + r, tiles + t
            else:
                y = swiglu(p["ffn"], h)
            x = x + y
        return (x, sa, sb, rows, tiles), None

    # The suffixes ride in the carry, each layer's slot written in place:
    # as the scan's xs/ys every layer's slice would be sliced out and
    # stacked back, a copy each way per layer and level.  The histories
    # are only read, as the scan's xs.
    n_dense = cfg.moe.first_dense_layers if cfg.moe else n
    rows, tiles = ((jnp.zeros((cfg.moe.n_experts,), jnp.int32),
                    jnp.zeros((), jnp.int32)) if cfg.moe else (None, None))
    carry = (x, beam_a.reshape((n, B, M, Ls) + hist_a.shape[3:]),
             beam_b.reshape((n, B, M, Ls) + hist_b.shape[3:]), rows, tiles)
    for group, lo, hi in (("dense_layers", 0, n_dense),
                          ("moe_layers", n_dense, n)):
        if hi > lo:
            carry, _ = jax.lax.scan(
                body, carry, (params[group], hist_a[lo:hi], hist_b[lo:hi],
                              jnp.arange(lo, hi)),
                unroll=cfg.layer_unroll)
    x, new_a, new_b, rows, tiles = carry
    with jax.named_scope("unembed"):
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = (x @ _unemb(params, cfg)).astype(jnp.float32)  # (BM, 1, V)
    return (logits, new_a.reshape(beam_a.shape), new_b.reshape(beam_b.shape),
            rows, tiles)


def _shared_history_step(params, cache, tokens, cfg):
    """:func:`decode_step` for the retrievers' shared-history caches."""
    if isinstance(cache, kv_lib.LatentHistoryCache):
        names = ("sfx_c", "sfx_r")
        hist = (cache.hist_c, cache.hist_r)
    else:
        names = ("sfx_k", "sfx_v")
        hist = (cache.hist_k, cache.hist_v)
    logits, a, b, rows, tiles = _gr_decode(
        params, *hist, *(getattr(cache, f) for f in names), tokens,
        cache.step, cfg)
    new = dict(zip(names, (a, b)), step=cache.step + 1)
    if cache.expert_rows is not None:
        new["expert_rows"] = cache.expert_rows + rows
        new["row_tiles"] = cache.row_tiles + tiles
    return logits, dataclasses.replace(cache, **new)


def decode_step(params, cache, tokens: jax.Array, cfg: TransformerConfig):
    """One autoregressive step. tokens (B, 1) -> (logits (B,1,V), new cache).

    A :class:`~repro.models.kvcache.SharedHistoryCache` or
    :class:`~repro.models.kvcache.LatentHistoryCache` takes
    :func:`gr_decode_step`, with one token per beam (tokens (B*M, 1)).

    Each piece of the step has a ``jax.named_scope`` (``embed``,
    ``qkv_proj``, ``kv_write``, ``attention``, ``out_proj``, ``ffn``,
    ``unembed``; DESIGN.md §9), so a profile splits a decode level by them.
    """
    if isinstance(cache, (kv_lib.SharedHistoryCache,
                          kv_lib.LatentHistoryCache)):
        return _shared_history_step(params, cache, tokens, cfg)
    B = tokens.shape[0]
    with jax.named_scope("embed"):
        x = jnp.take(params["emb"], tokens, axis=0)  # (B, 1, D)
    pos = cache.pos
    mla = cfg.attention == "mla"
    if mla:
        slots = cache.c_kv.shape[2]
        ring = False
    else:
        slots = cache.k.shape[2]
        ring = cache.ring
    with jax.named_scope("kv_write"):
        slot_pos, write_slot = kv_lib.advance_positions(
            cache.slot_pos, pos, slots, ring=False if mla else ring
        )

    def body(x, inp):
        p, ca, cb = inp
        with jax.named_scope("qkv_proj"):
            h = rms_norm(p["ln_attn"], x, cfg.norm_eps)
        if mla:
            attn_out, new_cache = _decode_attn_mla(
                p["attn"], h, cfg, ca, cb, slot_pos, pos
            )
        else:
            attn_out, new_cache = _decode_attn_gqa(
                p["attn"], h, cfg, ca, cb, slot_pos, pos, slot=write_slot
            )
        with jax.named_scope("out_proj"):
            x = x + attn_out
        with jax.named_scope("ffn"):
            h = rms_norm(p["ln_ffn"], x, cfg.norm_eps)
            if "moe" in p:
                y, _, _ = moe_ffn_dropless(p["moe"], h, cfg.moe)
                x = x + y
            else:
                x = x + swiglu(p["ffn"], h)
        return x, new_cache

    n_dense = (cfg.moe.first_dense_layers if cfg.moe else cfg.n_layers)
    if cfg.moe is None:
        n_dense = cfg.n_layers
    arrays = (cache.c_kv, cache.k_rope) if mla else (cache.k, cache.v)
    new_arrays = []
    x_cur = x
    offset = 0
    for group, count in (("dense_layers", n_dense),
                         ("moe_layers", cfg.n_layers - n_dense)):
        if count == 0 or group not in params:
            continue
        sl = tuple(a[offset:offset + count] for a in arrays)
        x_cur, outs = jax.lax.scan(body, x_cur, (params[group],) + sl,
                                   unroll=cfg.layer_unroll)
        new_arrays.append(outs)
        offset += count
    merged = tuple(
        jnp.concatenate([g[i] for g in new_arrays], axis=0)
        for i in range(2)
    )
    with jax.named_scope("unembed"):
        x_cur = rms_norm(params["final_norm"], x_cur, cfg.norm_eps)
        logits = (x_cur @ _unemb(params, cfg)).astype(jnp.float32)
    if cfg.defer_cache_write:
        # caches untouched; pending per-layer k/v stacks returned for the
        # serving layer to commit at block granularity.
        if mla:
            new_cache = kv_lib.MLACache(
                c_kv=cache.c_kv, k_rope=cache.k_rope,
                slot_pos=slot_pos, pos=pos + 1,
            )
        else:
            new_cache = kv_lib.KVCache(
                k=cache.k, v=cache.v, slot_pos=slot_pos, pos=pos + 1,
                ring=ring,
            )
        return logits, new_cache, merged
    if mla:
        new_cache = kv_lib.MLACache(
            c_kv=merged[0], k_rope=merged[1], slot_pos=slot_pos, pos=pos + 1
        )
    else:
        new_cache = kv_lib.KVCache(
            k=merged[0], v=merged[1], slot_pos=slot_pos, pos=pos + 1,
            ring=ring,
        )
    return logits, new_cache


def paged_decode_step(
    params,
    k_pool: jax.Array,  # (n_layers, P, page_size, KVH, Dh) shared history
    v_pool: jax.Array,  # (n_layers, P, page_size, KVH, Dh)
    page_table: jax.Array,  # (slots, n_pages) int32 page ids per slot
    suffix_k: jax.Array,  # (n_layers, slots, M, Ls, KVH, Dh) decoded KV
    suffix_v: jax.Array,  # (n_layers, slots, M, Ls, KVH, Dh)
    tokens: jax.Array,  # (slots, M) int32 last emitted token per beam
    pos: jax.Array,  # (slots,) int32 attention position (= S + level - 1)
    write_col: jax.Array,  # (slots,) int32 suffix column receiving this k/v
    cfg: TransformerConfig,
    *,
    hist_len: int,  # static S: history columns attended per slot
):
    """One continuous-batching decode step through the paged KV cache.

    Rows may sit at *different* decode levels: ``pos`` and ``write_col`` are
    per-slot vectors, and attention masks each row to its own ``[0, pos]``
    window.  History KV is read through ``page_table`` (one stored copy per
    slot — or per shared prompt — instead of per beam); per-beam decoded
    suffixes live in the dense ``suffix_k/v`` arrays where beam permutation
    is a plain gather.

    Bit-identity contract (DESIGN.md §10, fuzz-asserted in
    ``tests/test_continuous.py``): for a row at level ``l >= 1`` with
    ``pos = S + l - 1`` this computes exactly what :func:`gr_decode_step`
    computes at ``sid_step = l - 1`` for that row — the gathered history is
    sliced to exactly ``hist_len`` columns and the suffix has the retriever's
    ``Ls = L - 1`` columns, so both run :func:`_shared_history_attention` on
    the same shapes and every reduction keeps its order.  Rows whose output
    is unused (level-0 or dead slots) point ``write_col`` past the last
    column (``Ls``), which writes nothing.

    Returns ``(logits (slots*M, 1, vocab), new_suffix_k, new_suffix_v)``.
    """
    if (cfg.attention == "mla" or cfg.sliding_window is not None
            or cfg.defer_cache_write or cfg.moe is not None
            or cfg.decode_split_k):
        raise NotImplementedError(
            "paged_decode_step supports dense GQA models without sliding "
            "window / MLA / MoE / deferred writes"
        )
    slots, M = tokens.shape
    N = slots * M
    S = int(hist_len)
    Ls = suffix_k.shape[3]
    hd = cfg.resolved_head_dim()
    H, KV = cfg.n_heads, cfg.n_kv_heads
    ps = k_pool.shape[2]
    n_pages = page_table.shape[1]
    if n_pages * ps < S:
        raise ValueError(
            f"page table covers {n_pages * ps} columns < hist_len {S}"
        )
    x = jnp.take(params["emb"], tokens.reshape(N, 1), axis=0)  # (N, 1, D)
    pos_row = jnp.repeat(pos, M)  # (N,)
    # suffix column j holds position S + j
    sfx_valid = (jnp.arange(Ls, dtype=jnp.int32)[None, :]
                 <= (pos - S)[:, None])  # (slots, Ls)
    col_mask = (jnp.arange(Ls, dtype=jnp.int32)[None, None, :]
                == write_col[:, None, None])  # (slots, 1, Ls)

    def body(x, inp):
        p, kp, vp, sk, sv = inp
        h = rms_norm(p["ln_attn"], x, cfg.norm_eps)
        a = p["attn"]

        def proj(pp, width):
            y = h @ pp["w"]
            if "b" in pp:
                y = y + pp["b"]
            return y.reshape(N, 1, width, hd)

        q = proj(a["wq"], H)
        k_new = proj(a["wk"], KV)
        v_new = proj(a["wv"], KV)
        q = apply_rope(q, pos_row[:, None], cfg.rope_theta)
        k_new = apply_rope(k_new, pos_row[:, None], cfg.rope_theta)
        # write this step's k/v into the per-beam suffix BEFORE attention
        # (decode_step order), at each slot's own column
        sk = jnp.where(
            col_mask[..., None, None],
            k_new.reshape(slots, M, 1, KV, hd).astype(sk.dtype), sk,
        )
        sv = jnp.where(
            col_mask[..., None, None],
            v_new.reshape(slots, M, 1, KV, hd).astype(sv.dtype), sv,
        )
        # history through the page table: one stored copy per slot, shared
        # by its beams
        hk = kv_lib.gather_pages(kp, page_table, S)  # (slots, S, KV, hd)
        hv = kv_lib.gather_pages(vp, page_table, S)
        out = _shared_history_attention(
            q.reshape(slots, M, H, hd), hk, hv, sk, sv, sfx_valid)
        x = x + out.reshape(N, 1, H * hd) @ a["wo"]["w"]
        hh = rms_norm(p["ln_ffn"], x, cfg.norm_eps)
        x = x + swiglu(p["ffn"], hh)
        return x, (sk, sv)

    x, (new_sk, new_sv) = jax.lax.scan(
        body, x,
        (params["dense_layers"], k_pool, v_pool, suffix_k, suffix_v),
        unroll=cfg.layer_unroll,
    )
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = (x @ _unemb(params, cfg)).astype(jnp.float32)  # (N, 1, V)
    return logits, new_sk, new_sv
