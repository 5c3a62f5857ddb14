"""KV caches: full, ring-buffered (sliding-window), MLA latent, and paged.

All caches are per-layer-stacked pytrees (leading axis = n_layers) so the
decode step can ``lax.scan`` over layers carrying the matching cache slice.

The ring cache keeps only ``window`` slots; insertion is at ``pos % window``
and every slot remembers its absolute position for masking — this is what
makes mixtral long_500k decode O(window) in memory instead of O(S).

Paged pools (DESIGN.md §10) back the continuous-batching engine: history KV
lives in a flat pool of fixed-size pages indexed through a per-slot page
table, so shared prompt prefixes are stored once and join/evict is a
host-side free-list operation — never a device reshape.  The device-side
helpers here (``init_page_pool`` / ``scatter_pages`` / ``gather_pages``) are
pure shape plumbing; ownership and refcounts are host state
(:class:`repro.serving.continuous.PagedKVAllocator`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "KVCache", "MLACache", "SharedHistoryCache", "LatentHistoryCache",
    "init_kv_cache",
    "init_mla_cache",
    "init_page_pool", "scatter_pages", "gather_pages", "pages_for",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jax.Array  # (L, B, S_slots, KVH, Dh)
    v: jax.Array  # (L, B, S_slots, KVH, Dv)
    slot_pos: jax.Array  # (S_slots,) absolute position per slot, -1 = empty
    pos: jax.Array  # () next position to write
    ring: bool = dataclasses.field(metadata=dict(static=True), default=False)

    def layer(self, i):
        return self.k[i], self.v[i]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MLACache:
    c_kv: jax.Array  # (L, B, S, kv_lora) compressed latents
    k_rope: jax.Array  # (L, B, S, rope_dim) shared decoupled keys
    slot_pos: jax.Array  # (S,)
    pos: jax.Array  # ()


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SharedHistoryCache:
    """Generative retrieval's decode cache (DESIGN.md §14): each request's
    history held once, shared by its M beams, and each beam's SID suffix."""

    hist_k: jax.Array  # (L, B, S, KVH, Dh)
    hist_v: jax.Array  # (L, B, S, KVH, Dh)
    sfx_k: jax.Array  # (L, B*M, Ls, KVH, Dh), or (L, B, M, Ls, KVH, Dh)
    sfx_v: jax.Array
    step: jax.Array  # () suffix column of the next token
    # MoE decoders: rows each held expert received, and the row tiles the
    # grouped matmuls visited, summed over the expert layers
    expert_rows: Optional[jax.Array] = None
    row_tiles: Optional[jax.Array] = None

    def take_beams(self, rows: jax.Array):
        """The cache with the flat suffix's rows ``rows`` (B*M,) kept."""
        return dataclasses.replace(
            self, sfx_k=jnp.take(self.sfx_k, rows, axis=1),
            sfx_v=jnp.take(self.sfx_v, rows, axis=1))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatentHistoryCache:
    """:class:`SharedHistoryCache` for latent attention (MLA): each
    request's history held once as the latents prefill cached
    (:class:`MLACache`), and each beam's SID suffix as latents."""

    hist_c: jax.Array  # (L, B, S, kv_lora) normalised latents
    hist_r: jax.Array  # (L, B, S, rope_dim) rotated keys
    sfx_c: jax.Array  # (L, B*M, Ls, kv_lora), or (L, B, M, Ls, kv_lora)
    sfx_r: jax.Array  # (L, B*M, Ls, rope_dim), or batched
    step: jax.Array  # () suffix column of the next token
    expert_rows: Optional[jax.Array] = None
    row_tiles: Optional[jax.Array] = None

    def take_beams(self, rows: jax.Array):
        return dataclasses.replace(
            self, sfx_c=jnp.take(self.sfx_c, rows, axis=1),
            sfx_r=jnp.take(self.sfx_r, rows, axis=1))


def init_kv_cache(
    n_layers, batch, max_len, n_kv_heads, head_dim, v_dim=None,
    dtype=jnp.bfloat16, window=None,
) -> KVCache:
    slots = min(max_len, window) if window else max_len
    v_dim = v_dim or head_dim
    return KVCache(
        k=jnp.zeros((n_layers, batch, slots, n_kv_heads, head_dim), dtype),
        v=jnp.zeros((n_layers, batch, slots, n_kv_heads, v_dim), dtype),
        slot_pos=jnp.full((slots,), -1, jnp.int32),
        pos=jnp.zeros((), jnp.int32),
        ring=window is not None and slots == window,
    )


def init_mla_cache(
    n_layers, batch, max_len, kv_lora_rank, rope_dim, dtype=jnp.bfloat16
) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((n_layers, batch, max_len, kv_lora_rank), dtype),
        k_rope=jnp.zeros((n_layers, batch, max_len, rope_dim), dtype),
        slot_pos=jnp.full((max_len,), -1, jnp.int32),
        pos=jnp.zeros((), jnp.int32),
    )


def write_slot(cache_arr: jax.Array, new: jax.Array, slot: jax.Array):
    """cache_arr (B, S, ...) <- new (B, 1, ...) at index ``slot``."""
    return jax.lax.dynamic_update_slice_in_dim(
        cache_arr, new.astype(cache_arr.dtype), slot, axis=1
    )


def advance_positions(slot_pos: jax.Array, pos: jax.Array, n_slots: int, ring: bool):
    """Mark the slot written at this step with its absolute position."""
    slot = jnp.where(ring, pos % n_slots, jnp.minimum(pos, n_slots - 1))
    return slot_pos.at[slot].set(pos), slot


# ---------------------------------------------------------------------------
# Paged history pools (continuous batching, DESIGN.md §10)
# ---------------------------------------------------------------------------
def pages_for(seq_len: int, page_size: int) -> int:
    """Pages needed to hold ``seq_len`` KV columns."""
    return -(-int(seq_len) // int(page_size))


def init_page_pool(
    n_layers, n_pages, page_size, n_kv_heads, head_dim, v_dim=None,
    dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """(k_pool, v_pool), each (n_layers, n_pages, page_size, KVH, Dh).

    Page 0 is conventionally the allocator's NULL page (never handed out),
    so an all-zero page table is always safe to gather through.
    """
    v_dim = v_dim or head_dim
    return (
        jnp.zeros((n_layers, n_pages, page_size, n_kv_heads, head_dim),
                  dtype),
        jnp.zeros((n_layers, n_pages, page_size, n_kv_heads, v_dim), dtype),
    )


def scatter_pages(pool: jax.Array, rows: jax.Array,
                  page_ids: jax.Array) -> jax.Array:
    """Commit prefilled KV rows into the pool at ``page_ids``.

    pool (n_layers, P, ps, KVH, Dh); rows (n_layers, B, S, KVH, Dh) with
    ``S`` padded by zeros up to ``n_pages_per_row * ps``; page_ids
    (B, n_pages_per_row) int32.  Rows sharing a page id (refcounted prompt
    sharing) must carry identical content — the scatter order is undefined.
    """
    L, P, ps = pool.shape[0], pool.shape[1], pool.shape[2]
    B, S = rows.shape[1], rows.shape[2]
    n_per = page_ids.shape[1]
    pad = n_per * ps - S
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    paged = rows.reshape(L, B * n_per, ps, *rows.shape[3:])
    return pool.at[:, page_ids.reshape(-1)].set(paged.astype(pool.dtype))


def gather_pages(pool_layer: jax.Array, page_table: jax.Array,
                 hist_len: int) -> jax.Array:
    """Read ``hist_len`` history columns per slot through the page table.

    pool_layer (P, ps, KVH, Dh); page_table (slots, n_pages) ->
    (slots, hist_len, KVH, Dh).  The trailing ``n_pages*ps - hist_len``
    columns are sliced off, so page-granule padding never reaches attention
    (exact-width gathers keep the softmax reduction bit-identical to the
    contiguous cache).
    """
    slots, n_pages = page_table.shape
    ps = pool_layer.shape[1]
    flat = jnp.take(pool_layer, page_table.reshape(-1), axis=0)
    return flat.reshape(slots, n_pages * ps, *pool_layer.shape[2:])[
        :, :hist_len
    ]
