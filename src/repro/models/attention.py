"""Attention kernels: chunked (flash-style) causal attention + decode paths.

``chunked_causal_attention`` streams KV in fixed chunks with an online
log-sum-exp accumulator so the (Sq, Skv) score matrix is never materialized —
required to fit train_4k / prefill_32k activation memory under remat (see
DESIGN.md §7).  Supports GQA head grouping and sliding windows (Mixtral).

``decode_attention`` is the single-token path against a (possibly ring-
buffered) KV cache: one matvec per head, with slot-validity masking.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["chunked_causal_attention", "decode_attention"]

NEG = -1.0e30


def _repeat_kv(x: jax.Array, groups: int) -> jax.Array:
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=2)


def chunked_causal_attention(
    q: jax.Array,  # (B, Sq, H, Dh)
    k: jax.Array,  # (B, Skv, KVH, Dh)
    v: jax.Array,  # (B, Skv, KVH, Dv)
    *,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
    window: int | None = None,
    q_offset: int = 0,
    scale: float | None = None,
    unroll: bool = False,
) -> jax.Array:
    B, Sq, H, Dh = q.shape
    _, Skv, KVH, Dv = v.shape
    groups = H // KVH
    scale = scale if scale is not None else Dh ** -0.5
    chunk_q = min(chunk_q, Sq)
    chunk_kv = min(chunk_kv, Skv)
    while Sq % chunk_q:
        chunk_q //= 2
    while Skv % chunk_kv:
        chunk_kv //= 2
    nq, nk = Sq // chunk_q, Skv // chunk_kv

    # (nk, B, chunk_kv, KVH, D*) scan inputs
    ks = k.reshape(B, nk, chunk_kv, KVH, Dh).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, chunk_kv, KVH, Dv).transpose(1, 0, 2, 3, 4)
    qs = q.reshape(B, nq, chunk_q, H, Dh).transpose(1, 0, 2, 3, 4)

    def q_chunk_body(qi, q_c):
        q_pos = q_offset + qi * chunk_q + jnp.arange(chunk_q)

        def kv_body(carry, inp):
            m, l, acc = carry
            kj, k_c, v_c = inp
            k_pos = kj * chunk_kv + jnp.arange(chunk_kv)
            k_rep = _repeat_kv(k_c, groups)
            v_rep = _repeat_kv(v_c, groups)
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q_c, k_rep,
                preferred_element_type=jnp.float32,
            ) * scale  # (B, H, cq, ck) f32
            mask = k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
            s = jnp.where(mask[None, None], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_rep.dtype), v_rep,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        # the carry is built from q_c so that, inside shard_map, it varies
        # over the same mesh axes as the body's output
        l0 = jnp.zeros_like(q_c[..., 0], jnp.float32).transpose(0, 2, 1)
        m0 = jnp.full_like(l0, NEG)
        a0 = jnp.broadcast_to(l0[..., None], (B, H, chunk_q, Dv))
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (jnp.arange(nk), ks, vs),
            unroll=nk if unroll else 1,
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, H, cq, Dv)
        return out.transpose(0, 2, 1, 3)  # (B, cq, H, Dv)

    # checkpoint per q-chunk: the backward recomputes the (cq, ck) probability
    # blocks instead of storing them — the flash-attention memory recipe.
    body = jax.checkpoint(lambda args: q_chunk_body(*args))
    _, outs = jax.lax.scan(
        lambda _, args: (None, body(args)), None, (jnp.arange(nq), qs),
        unroll=nq if unroll else 1,
    )
    # (nq, B, cq, H, Dv) -> (B, Sq, H, Dv)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, Dv).astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, 1, H, Dh)
    k_cache: jax.Array,  # (B, S, KVH, Dh)
    v_cache: jax.Array,  # (B, S, KVH, Dv)
    slot_positions: jax.Array,  # (S,) or (B, S): absolute position per slot, -1 invalid
    cur_pos: jax.Array,  # scalar or (B,): position of the query token
    *,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """GQA decode via grouped einsum — the KV cache is contracted directly
    with the (KV, G)-factored query, never materializing the G-times
    repeated cache (for kv=8 -> 64 heads that repeat would 8x the largest
    tensor of the whole decode step)."""
    B, S, KVH, Dh = k_cache.shape
    H = q.shape[2]
    Dv = v_cache.shape[-1]
    groups = H // KVH
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    qg = q.reshape(B, 1, KVH, groups, Dh)
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale  # (B, KV, G, 1, S)
    pos = jnp.broadcast_to(slot_positions, (B, S))
    cur = jnp.broadcast_to(cur_pos, (B,))[:, None]
    mask = (pos >= 0) & (pos <= cur)
    if window is not None:
        mask = mask & (pos > cur - window)
    s = jnp.where(mask[:, None, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    ).reshape(B, 1, H, Dv)
    return out.astype(q.dtype)  # (B, 1, H, Dv)
