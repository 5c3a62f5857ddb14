"""SPMD sharding story for constraint backends + beam search (DESIGN.md §6).

This module makes the constrained-retrieval hot path run SPMD over a
``Mesh`` from :mod:`repro.launch.mesh`:

  * **Batch/beam parallelism** — :func:`spmd_beam_search` wraps the ordinary
    :func:`~repro.core.beam_search` in ``shard_map``, splitting the *batch*
    axis across the mesh's data axes (``dp_axes``).  Rows are independent in
    Algorithm 1 (beams only compete within their own row's ``M·V``
    candidates), so each device runs the unmodified search on its batch
    shard and results are **bit-identical** to single-device decoding
    (asserted in ``tests/test_differential_fuzz.py``).  The beam axis stays
    device-local: sharding it would turn the per-row ``top_k`` over ``M·V``
    candidates into a cross-device tournament for zero memory win (``M·V``
    floats per row is trivially small).

  * **Constraint placement** — each backend exposes
    ``ConstraintBackend.shardings(mesh, rows=...)`` (a PartitionSpec pytree
    with the backend's own treedef).  Default is paper §A.3: every table
    replicated, the constraint check collective-free.  ``rows="model"``
    row-shards the CSR ``edges`` slab — the one leaf that grows with the
    corpus — along the mesh's ``model`` axis (plus the compressed
    ``tok_delta`` slab when the backend carries one, DESIGN.md §11);
    :func:`vntk_row_sharded` then resolves cross-shard rows with a ONE-HOP
    gather: every device picks the speculative edge rows it owns and a
    single ``psum`` over ``model`` assembles the full ``(nb, bmax, 2)``
    slab on all devices.  The candidate-compressed step (§8) stays sharded
    end-to-end: :func:`vntk_row_sharded_topk` runs a shard-local top-C over
    the rows each device owns and merges the per-shard winner lists with
    one ``psum`` — ``(nb, ms, C)`` floats cross the interconnect instead of
    the ``(nb, bmax, 2)`` edge slab.

  * **Hot-swap invariance** — spec trees are pure functions of the policy's
    *structure* (static metadata), never of leaf values, so a registry
    hot-swap (``with_constraints``) keeps every sharding valid and every
    compiled executable alive (asserted in ``tests/test_spmd_serving.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.vntk import NEG_INF, _topk_from_candidates
from repro.decoding.backends import StackedStaticBackend, StaticBackend
from repro.distributed.sharding import (
    dp_axes,
    dp_size,
    tree_shardings,
)

__all__ = [
    "dp_size",
    "policy_pspecs",
    "shard_policy",
    "pad_rows",
    "pad_slab",
    "pad_policy_rows",
    "vntk_row_sharded",
    "vntk_row_sharded_topk",
    "vntk_row_sharded_compressed",
    "vntk_row_sharded_compressed_topk",
    "RowShardedStatic",
    "to_row_sharded",
    "spmd_beam_search",
]


def policy_pspecs(policy, mesh: Mesh, *, rows: str = "replicated"):
    """PartitionSpec pytree for a DecodePolicy (its ``shardings`` composed).

    The result has the policy's exact treedef, so it is directly usable as
    ``shard_map`` in_specs or as input to :func:`tree_shardings`.
    """
    return policy.shardings(mesh, rows=rows)


def shard_policy(policy, mesh: Mesh, *, rows: str = "replicated"):
    """``device_put`` the policy's leaves per its spec tree.

    With ``rows="model"`` the CSR edge slab must divide the model axis —
    apply :func:`pad_policy_rows` first (the SPMD serving stack does).
    """
    return jax.device_put(
        policy, tree_shardings(mesh, policy_pspecs(policy, mesh, rows=rows))
    )


# ---------------------------------------------------------------------------
# Row-sharded CSR: padding + one-hop gather lookup
# ---------------------------------------------------------------------------
def pad_rows(obj, n_shards: int):
    """Pad the CSR ``edges`` row count to a multiple of ``n_shards``.

    Works on a TransitionMatrix (rows on axis 0) or a ConstraintStore (rows
    on axis 1).  Pad rows are zeros — outside every CSR row's
    ``[start, start + n_child)`` window, so the ``iota < n_child``
    sanitization of Alg. 2 never reads them as real edges.  Static metadata
    (``n_edges`` = real edge count) is untouched; only the array envelope
    grows, deterministically, so repeated application (every hot-swap) lands
    on the same shapes and never recompiles.
    """
    if n_shards <= 1:
        return obj
    edges = obj.edges
    e = edges.shape[-2]
    e_pad = -(-e // n_shards) * n_shards
    if e_pad == e:
        return obj
    pad = [(0, 0)] * edges.ndim
    pad[-2] = (0, e_pad - e)
    return dataclasses.replace(obj, edges=jnp.pad(edges, pad))


def pad_slab(slab, n_shards: int):
    """Pad a compressed slab's ``tok_delta`` edge axis like :func:`pad_rows`.

    Zero pad deltas sit past every CSR row's window, so the row-start
    anchored cumsum of DESIGN.md §11 never folds them into a *valid* slot's
    token — they decompress to the same garbage the uncompressed path's
    speculative over-read produces, and every consumer masks them.
    """
    if slab is None or n_shards <= 1:
        return slab
    tok_delta = slab.tok_delta
    e = tok_delta.shape[-1]
    e_pad = -(-e // n_shards) * n_shards
    if e_pad == e:
        return slab
    pad = [(0, 0)] * tok_delta.ndim
    pad[-1] = (0, e_pad - e)
    return dataclasses.replace(slab, tok_delta=jnp.pad(tok_delta, pad))


def pad_policy_rows(policy, n_shards: int):
    """Apply :func:`pad_rows` to every CSR-carrying backend in a policy.

    Backends carrying a compressed slab (DESIGN.md §11) get their
    ``tok_delta`` padded in lock-step — both leaves are row-sharded under
    ``rows="model"`` and must divide the model axis.
    """
    def pad_backend(b):
        if isinstance(b, StaticBackend):
            return dataclasses.replace(
                b, tm=pad_rows(b.tm, n_shards),
                slab=pad_slab(b.slab, n_shards),
            )
        if isinstance(b, StackedStaticBackend):
            return dataclasses.replace(
                b, store=pad_rows(b.store, n_shards),
                slab=pad_slab(b.slab, n_shards),
            )
        return b

    return dataclasses.replace(
        policy, backends=tuple(pad_backend(b) for b in policy.backends)
    )


def _sharded_row_window(nodes, row_pointers, bmax, constraint_ids,
                        batch_shape):
    """Phase 1 of Alg. 2, replicated: per-row speculative burst window.

    Row pointers are replicated (``4(S+1)`` bytes vs the edge slab's
    ``8E``), so every device computes the same global edge indices and
    validity mask; only the slab gather itself is shard-local.
    """
    n_flat = nodes.reshape(-1)
    if constraint_ids is None:
        cid = None
        starts = row_pointers[n_flat]
        lens = row_pointers[n_flat + 1] - starts
    else:
        cid = jnp.broadcast_to(constraint_ids, batch_shape).reshape(-1)
        starts = row_pointers[cid, n_flat]
        lens = row_pointers[cid, n_flat + 1] - starts
    offsets = jnp.arange(bmax, dtype=starts.dtype)
    idx = starts[:, None] + offsets[None, :]  # global edge-row indices
    valid = offsets[None, :] < lens[:, None]
    return cid, offsets, idx, valid


def _own_window(idx, rows_local, axis):
    """Ownership mask + clipped local indices for this shard's row block."""
    lo = jax.lax.axis_index(axis) * rows_local
    rel = idx - lo
    own = (rel >= 0) & (rel < rows_local)
    return own, jnp.clip(rel, 0, rows_local - 1)


def _scatter_dense(lp_flat, cols, nxt, valid, vocab_size, out_dtype):
    """Phases 3-4: the replicated scatter-projection (core/vntk.py)."""
    V = vocab_size
    nb = cols.shape[0]
    scatter_idx = jnp.where(valid, cols, V)
    rows = jnp.arange(nb)[:, None]
    cand_lp = jnp.take_along_axis(lp_flat, jnp.clip(cols, 0, V - 1), axis=1)
    masked = jnp.full((nb, V + 1), NEG_INF, dtype=out_dtype)
    masked = masked.at[rows, scatter_idx].set(
        jnp.where(valid, cand_lp, NEG_INF)
    )[:, :V]
    next_dense = jnp.zeros((nb, V + 1), dtype=jnp.int32)
    next_dense = next_dense.at[rows, scatter_idx].set(nxt)[:, :V]
    return masked, next_dense


def vntk_row_sharded(
    log_probs: jax.Array,  # (..., V)
    nodes: jax.Array,  # (...,) int32 current trie states
    row_pointers: jax.Array,  # (S+1,) or (K, S+1) int32, REPLICATED
    edges_local: jax.Array,  # (E/ms, 2) or (K, E/ms, 2): THIS shard's rows
    bmax: int,
    vocab_size: int,
    axis: str,
    constraint_ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Alg. 2 with the CSR edge slab row-sharded along mesh axis ``axis``.

    Must run inside ``shard_map``.  Every device computes the same global
    speculative indices; each keeps only the rows it owns
    (``lo <= idx < lo + rows_local``) and one ``psum`` over ``axis``
    assembles the full slab — the "one-hop gather" for cross-shard
    next-states.  int32 summation is exact, and exactly one shard owns each
    index, so results are bit-identical to the replicated
    :func:`~repro.core.vntk.vntk_xla`.
    """
    V = vocab_size
    batch_shape = nodes.shape
    lp_flat = log_probs.reshape(-1, V)
    cid, offsets, idx, valid = _sharded_row_window(
        nodes, row_pointers, bmax, constraint_ids, batch_shape
    )
    own, rel_c = _own_window(idx, edges_local.shape[-2], axis)
    if cid is None:
        g = jnp.take(edges_local, rel_c, axis=0)  # (nb, bmax, 2)
    else:
        g = edges_local[cid[:, None], rel_c]
    g = jnp.where(own[..., None], g, 0)
    gathered = jax.lax.psum(g, axis)  # one hop: full slab everywhere

    cols = gathered[:, :, 0]
    nxt = jnp.where(valid, gathered[:, :, 1], 0)
    masked, next_dense = _scatter_dense(
        lp_flat, cols, nxt, valid, V, log_probs.dtype
    )
    return (
        masked.reshape(batch_shape + (V,)),
        next_dense.reshape(batch_shape + (V,)),
    )


def vntk_row_sharded_topk(
    log_probs: jax.Array,  # (..., V) normalized log-probs
    nodes: jax.Array,  # (...,) int32 current trie states
    row_pointers: jax.Array,  # (S+1,) or (K, S+1) int32, REPLICATED
    edges_local: jax.Array,  # (E/ms, 2) or (K, E/ms, 2): THIS shard's rows
    bmax: int,
    vocab_size: int,
    width: int,
    axis: str,
    n_shards: int,
    constraint_ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Candidate-compressed Alg. 2 (§8) over the row-sharded edge slab.

    Shard-local top-C + one-hop psum merge: each device scores only the CSR
    slots it owns (everything else pinned to the float minimum), selects its
    local dense-rank top-``width``, and ONE ``psum`` over ``axis``
    assembles the ``(nb, ms, width)`` per-shard winner lists plus the
    additive missing-token counts on every device.  The merged pool is then
    re-ranked with the same ``top_k`` the replicated oracle uses.

    Bit-identity with :func:`~repro.core.vntk._topk_from_candidates` rests
    on two invariants:

      * any entry of the true global top-``width`` ranks at least as high
        within its own shard (its local competitors are a subset of its
        global ones), so it always survives the local cut;
      * the oracle breaks key ties by pool index — i.e. token-ascending
        over the real candidates, then the fill entries.  Each shard emits
        its winners in slot order (token-ascending, rows are token-sorted),
        shards own contiguous — hence token-ordered — slot ranges, and the
        fills are appended last, so the merged pool preserves the oracle's
        exact tie order.  Losing entries all sit at the float minimum and
        can never displace the guaranteed ``width`` real-or-fill entries.

    The i-th-missing-token counts ``|{j : cols[j] - j <= i}|`` sum exactly
    across shards (every valid slot is owned by exactly one shard), so they
    ride in the same psum.  Interconnect traffic is ``(nb, ms, width)``
    floats + ints instead of the full ``(nb, bmax, 2)`` edge slab.
    """
    V = vocab_size
    batch_shape = nodes.shape
    lp_flat = log_probs.reshape(-1, V)
    cid, offsets, idx, valid = _sharded_row_window(
        nodes, row_pointers, bmax, constraint_ids, batch_shape
    )
    own, rel_c = _own_window(idx, edges_local.shape[-2], axis)
    own = own & valid
    if cid is None:
        g = jnp.take(edges_local, rel_c, axis=0)  # (nb, bmax, 2)
    else:
        g = edges_local[cid[:, None], rel_c]
    cols = g[:, :, 0]
    nxt = g[:, :, 1]

    nb = cols.shape[0]
    minf = jnp.asarray(jnp.finfo(jnp.float32).min, lp_flat.dtype)
    cand_lp = jnp.take_along_axis(lp_flat, jnp.clip(cols, 0, V - 1), axis=1)
    key_loc = jnp.where(own, cand_lp, minf)
    tok_loc = jnp.where(own, cols, 0).astype(jnp.int32)
    nxt_loc = jnp.where(own, nxt, 0).astype(jnp.int32)

    # local pool padded with `width` sentinels so top_k is always in range
    # (a shard may own fewer than `width` slots of a row's burst)
    pad_i = jnp.zeros((nb, width), jnp.int32)
    pool_k = jnp.concatenate(
        [key_loc, jnp.full((nb, width), minf, key_loc.dtype)], axis=1
    )
    pool_t = jnp.concatenate([tok_loc, pad_i], axis=1)
    pool_n = jnp.concatenate([nxt_loc, pad_i], axis=1)
    _, win = jax.lax.top_k(pool_k, width)
    win = jnp.sort(win, axis=-1)  # back to slot order == token-ascending
    loc_k = jnp.take_along_axis(pool_k, win, axis=1)
    loc_t = jnp.take_along_axis(pool_t, win, axis=1)
    loc_n = jnp.take_along_axis(pool_n, win, axis=1)

    # i-th missing token's count contribution from this shard's slots
    adj = jnp.where(own, cols - offsets[None, :], V + bmax + 1)
    fill_i = jnp.arange(width, dtype=jnp.int32)
    cnt_loc = jnp.sum(adj[:, None, :] <= fill_i[None, :, None], axis=-1)

    # ONE psum: each shard writes its slice of the zero merge buffers
    s = jax.lax.axis_index(axis)
    buf_k = jax.lax.dynamic_update_slice(
        jnp.zeros((nb, n_shards, width), loc_k.dtype),
        loc_k[:, None, :], (0, s, 0),
    )
    buf_t = jax.lax.dynamic_update_slice(
        jnp.zeros((nb, n_shards, width), jnp.int32),
        loc_t[:, None, :], (0, s, 0),
    )
    buf_n = jax.lax.dynamic_update_slice(
        jnp.zeros((nb, n_shards, width), jnp.int32),
        loc_n[:, None, :], (0, s, 0),
    )
    buf_k, buf_t, buf_n, cnt = jax.lax.psum(
        (buf_k, buf_t, buf_n, cnt_loc), axis
    )

    # replicated finale: merged winners + the oracle's missing-token fills
    fill_tok = fill_i[None, :] + cnt
    in_range = fill_tok < V
    fill_key = jnp.where(in_range, jnp.asarray(NEG_INF, lp_flat.dtype), minf)
    fill_tok = jnp.where(in_range, fill_tok, 0)

    keys = jnp.concatenate([buf_k.reshape(nb, -1), fill_key], axis=1)
    toks = jnp.concatenate([buf_t.reshape(nb, -1), fill_tok], axis=1)
    nxts = jnp.concatenate([buf_n.reshape(nb, -1), pad_i], axis=1)
    top_vals, top_idx = jax.lax.top_k(keys, width)
    out_tok = jnp.take_along_axis(toks, top_idx, axis=1)
    out_next = jnp.take_along_axis(nxts, top_idx, axis=1)
    shp = batch_shape + (width,)
    return (top_vals.reshape(shp), out_tok.reshape(shp),
            out_next.reshape(shp))


def _sharded_delta_decode(log_probs, nodes, row_pointers, tok_delta_local,
                          base, bmax, vocab_size, axis, constraint_ids):
    """Assemble + decode a compressed burst whose slab is row-sharded.

    The delta slab (DESIGN.md §11) is sharded along its edge axis; each
    device contributes the deltas it owns (zeros elsewhere) and one
    ``psum`` assembles the full ``(nb, bmax)`` burst, which then
    decompresses with the usual row-start anchored cumsum — replicated, so
    Phases 3-4 / the candidate selection run unchanged.  Unowned indices
    contribute zero, matching the replicated oracle's out-of-range fill;
    garbage past a row's end differs only on ``~valid`` slots, which every
    consumer masks.
    """
    batch_shape = nodes.shape
    lp_flat = log_probs.reshape(-1, vocab_size)
    cid, offsets, idx, valid = _sharded_row_window(
        nodes, row_pointers, bmax, constraint_ids, batch_shape
    )
    own, rel_c = _own_window(idx, tok_delta_local.shape[-1], axis)
    if cid is None:
        d = jnp.take(tok_delta_local, rel_c, axis=0)
    else:
        d = tok_delta_local[cid[:, None], rel_c]
    deltas = jax.lax.psum(jnp.where(own, d.astype(jnp.int32), 0), axis)
    cols = jnp.cumsum(deltas, axis=1)
    base = jnp.asarray(base, jnp.int32)
    if cid is not None and base.ndim == 1:
        base = base[cid]
    base = base[:, None] if base.ndim == 1 else base
    nxt = jnp.where(valid, idx.astype(jnp.int32) + base, 0)
    return lp_flat, cols, nxt, valid, batch_shape


def vntk_row_sharded_compressed(
    log_probs: jax.Array,  # (..., V)
    nodes: jax.Array,  # (...,) int32
    row_pointers: jax.Array,  # (S+1,) or (K, S+1), REPLICATED
    tok_delta_local: jax.Array,  # (E/ms,) or (K, E/ms): THIS shard's deltas
    base,  # scalar or (K,) int32 per-level next-state base for this step
    bmax: int,
    vocab_size: int,
    axis: str,
    constraint_ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Alg. 2 over the row-sharded COMPRESSED slab (§11): the one-hop psum
    carries the ``(nb, bmax)`` int32 delta burst — a quarter of the raw
    ``(nb, bmax, 2)`` edge slab — and the decode is bit-identical to
    :func:`~repro.core.vntk.vntk_compressed_reference`."""
    V = vocab_size
    lp_flat, cols, nxt, valid, batch_shape = _sharded_delta_decode(
        log_probs, nodes, row_pointers, tok_delta_local, base, bmax, V,
        axis, constraint_ids,
    )
    masked, next_dense = _scatter_dense(
        lp_flat, cols, nxt, valid, V, log_probs.dtype
    )
    return (
        masked.reshape(batch_shape + (V,)),
        next_dense.reshape(batch_shape + (V,)),
    )


def vntk_row_sharded_compressed_topk(
    log_probs: jax.Array,  # (..., V) normalized log-probs
    nodes: jax.Array,  # (...,) int32
    row_pointers: jax.Array,  # (S+1,) or (K, S+1), REPLICATED
    tok_delta_local: jax.Array,  # (E/ms,) or (K, E/ms)
    base,  # scalar or (K,) int32
    bmax: int,
    vocab_size: int,
    width: int,
    axis: str,
    constraint_ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Candidate-compressed step over the row-sharded compressed slab.

    The burst must decompress before candidates can be ranked (cumsum needs
    the whole row-start-anchored prefix), so the psum assembles the delta
    burst and the §8 selection runs replicated — the interconnect payload
    is already smaller than the sharded-topk merge for typical widths.
    """
    V = vocab_size
    lp_flat, cols, nxt, valid, batch_shape = _sharded_delta_decode(
        log_probs, nodes, row_pointers, tok_delta_local, base, bmax, V,
        axis, constraint_ids,
    )
    sc, tok, nx = _topk_from_candidates(lp_flat, cols, nxt, valid, width, V)
    shp = batch_shape + (width,)
    return sc.reshape(shp), tok.reshape(shp), nx.reshape(shp)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowShardedStatic:
    """Shard-local view of a Static/StackedStatic backend inside shard_map.

    Wraps the backend whose ``edges`` leaf arrived row-sharded: dense-band
    steps delegate to the inner backend (dense tables are replicated), sparse
    steps run :func:`vntk_row_sharded`.  Built by :func:`to_row_sharded`
    inside the shard_map body — never constructed by user code.
    """

    inner: object  # StaticBackend | StackedStaticBackend (pytree child)
    axis: str = dataclasses.field(
        default="model", metadata=dict(static=True)
    )
    # static shard count of `axis` — jax.lax has no axis_size query, so the
    # builder (spmd_beam_search) threads mesh.shape[axis] through
    # to_row_sharded; only the sharded-topk merge buffers need it.
    n_shards: int = dataclasses.field(default=1, metadata=dict(static=True))

    supports_fused = False
    needs_prefix = False
    # Candidate compression composes with row sharding (DESIGN.md §8 x §6):
    # topk_step runs the shard-local top-C + one-hop psum merge of
    # vntk_row_sharded_topk, so the interconnect carries (nb, ms, C)
    # winner lists instead of the (nb, bmax, 2) edge slab.
    supports_topk = True

    @property
    def supports_stacked(self) -> bool:
        return self.inner.supports_stacked

    @property
    def sid_length(self) -> int:
        return self.inner.sid_length

    @property
    def num_sets(self):
        return getattr(self.inner, "num_sets", None)

    @property
    def _constraints(self):
        return (self.inner.store if isinstance(self.inner, StackedStaticBackend)
                else self.inner.tm)

    def shardings(self, mesh, *, rows: str = "replicated"):
        raise TypeError(
            "RowShardedStatic is a shard-local view; take shardings from the "
            "inner backend before entering shard_map"
        )

    def topk_at(self, step: int) -> bool:
        return self.inner.topk_at(step)

    def candidate_width(self, beams: int) -> int:
        return self.inner.candidate_width(beams)

    def mask_step(self, log_probs, nodes, step, *, prefix_tokens=None,
                  constraint_ids=None):
        del prefix_tokens
        obj = self._constraints
        stacked = self.inner.supports_stacked
        if stacked and constraint_ids is None:
            raise ValueError(
                "ConstraintStore lookups need per-row constraint_ids"
            )
        if step < obj.dense_d:
            # dense band: replicated bit-packed tables, untouched path
            return self.inner.mask_step(
                log_probs, nodes, step,
                constraint_ids=constraint_ids if stacked else None,
            )
        bmax = max(obj.bmax_for_step(step), 1)
        cids = constraint_ids if stacked else None
        slab = getattr(self.inner, "slab", None)
        if slab is not None:
            return vntk_row_sharded_compressed(
                log_probs, nodes, obj.row_pointers, slab.tok_delta,
                slab.base_for_step(step), bmax, obj.vocab_size, self.axis,
                constraint_ids=cids,
            )
        return vntk_row_sharded(
            log_probs, nodes, obj.row_pointers, obj.edges, bmax,
            obj.vocab_size, self.axis, constraint_ids=cids,
        )

    def topk_step(self, values, nodes, step, width, *, prefix_tokens=None,
                  constraint_ids=None, normalized=True):
        """Sharded candidate-compressed Phases 1-2 (DESIGN.md §8 x §6)."""
        del prefix_tokens
        if not normalized:
            # to_row_sharded rejects fused inners, so the policy hands us
            # normalized log-probs; guard against direct callers.
            values = jax.nn.log_softmax(values.astype(jnp.float32), axis=-1)
        obj = self._constraints
        stacked = self.inner.supports_stacked
        if stacked and constraint_ids is None:
            raise ValueError(
                "ConstraintStore lookups need per-row constraint_ids"
            )
        if not self.topk_at(step):
            raise ValueError(
                f"no candidate row at dense step {step}; fix the policy plan"
            )
        bmax = max(obj.bmax_for_step(step), 1)
        cids = constraint_ids if stacked else None
        slab = getattr(self.inner, "slab", None)
        if slab is not None:
            return vntk_row_sharded_compressed_topk(
                values, nodes, obj.row_pointers, slab.tok_delta,
                slab.base_for_step(step), bmax, obj.vocab_size, width,
                self.axis, constraint_ids=cids,
            )
        return vntk_row_sharded_topk(
            values, nodes, obj.row_pointers, obj.edges, bmax,
            obj.vocab_size, width, self.axis, self.n_shards,
            constraint_ids=cids,
        )


def to_row_sharded(policy, axis: str = "model", n_shards: int = 1):
    """Rewrite a policy's sparse Static backends into shard-local views.

    Called inside the shard_map body, where Static backends' ``edges`` (and
    compressed ``tok_delta``) leaves are this device's row shard.
    Dense-band backend instances never touch ``edges`` and are left alone.
    Pallas/fused sparse paths have no row-sharded formulation yet —
    rejected at entry, not silently wrong.  ``n_shards`` is the static size
    of mesh axis ``axis`` (jax.lax cannot query it inside shard_map); the
    sharded-topk merge buffers are shaped with it.
    """
    def wrap(b):
        if (isinstance(b, (StaticBackend, StackedStaticBackend))
                and b.levels != "dense"):
            if b.impl == "pallas" or b.fused:
                raise ValueError(
                    "rows='model' supports the XLA unfused VNTK only; "
                    "rebuild the policy with impl='xla', fused=False"
                )
            return RowShardedStatic(inner=b, axis=axis, n_shards=n_shards)
        return b

    return dataclasses.replace(
        policy, backends=tuple(wrap(b) for b in policy.backends)
    )


# ---------------------------------------------------------------------------
# SPMD beam search: batch axis over the mesh's data axes
# ---------------------------------------------------------------------------
def spmd_beam_search(
    mesh: Mesh,
    logits_fn,
    batch_size: int,
    beam_size: int,
    length: int,
    policy,
    *,
    constraint_ids: Optional[jax.Array] = None,
    rows: str = "replicated",
):
    """Data-parallel :func:`~repro.core.beam_search` over ``mesh``.

    The batch axis is split across ``dp_axes(mesh)`` via ``shard_map``; the
    policy rides in with per-backend specs from its ``shardings`` hook (and
    with ``rows="model"`` its sparse steps run the one-hop-gather VNTK).
    ``logits_fn(carry, last, step)`` must be shard-oblivious — a function of
    its arguments and replicated closures only (the full serving path with a
    transformer + KV cache lives in ``repro.serving.spmd_engine``).

    ``batch_size`` must divide by :func:`dp_size` — callers pad with inactive
    rows (the static-shape padding rule of DESIGN.md §6).  Returns
    ``(tokens (B, M, L), scores (B, M))`` as global arrays, bit-identical to
    the single-device search.
    """
    from repro.decoding.policy import as_policy  # lazy: import cycle

    policy = as_policy(policy)
    dp = dp_axes(mesh)
    n = dp_size(mesh)
    if batch_size % n:
        raise ValueError(
            f"batch_size {batch_size} must divide the {n}-way data "
            f"parallelism (axes {dp}); pad with inactive rows"
        )
    if rows == "model":
        policy = pad_policy_rows(policy, mesh.shape["model"])
    local_b = batch_size // n
    have_ids = constraint_ids is not None
    # jit keys on the wrapped function OBJECT: without this cache a caller
    # looping over spmd_beam_search would recompile every iteration (the
    # exact per-call-jit defect GenerativeRetriever.__init__ fixed)
    key = (mesh, logits_fn, local_b, beam_size, length, rows, have_ids,
           jax.tree_util.tree_structure(policy))
    fn = _SPMD_SEARCH_CACHE.get(key)
    if fn is None:
        specs = policy_pspecs(policy, mesh, rows=rows)

        ms = mesh.shape["model"] if rows == "model" else 1

        def body(pol, *maybe_cids):
            p = (to_row_sharded(pol, n_shards=ms) if rows == "model"
                 else pol)
            from repro.core.beam_search import beam_search

            state, _ = beam_search(
                logits_fn, None, local_b, beam_size, length, p,
                constraint_ids=maybe_cids[0] if have_ids else None,
            )
            return state.tokens, state.scores

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(specs, P(dp)) if have_ids else (specs,),
            out_specs=(P(dp, None, None), P(dp, None)),
        ))
        _SPMD_SEARCH_CACHE[key] = fn
    args = ((policy, jnp.asarray(constraint_ids, jnp.int32)) if have_ids
            else (policy,))
    return fn(*args)


_SPMD_SEARCH_CACHE: dict = {}
