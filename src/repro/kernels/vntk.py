"""Pallas TPU kernel for the Vectorized Node Transition Kernel (paper Alg. 2).

TPU-native adaptation of the paper's GPU-friendly gather/scatter formulation
(see DESIGN.md §3):

  * Phase 1/2 (boundary lookup + speculative slicing) run in XLA in front of
    the kernel: one gather of each beam's row bounds and one gather of its
    ``bmax``-slot burst from the edge slab — ``nb * bmax`` elements, the
    paper's "single coalesced memory transaction".  The kernel never DMAs
    the slab itself: a burst starts at an arbitrary edge offset, and Mosaic
    only slices HBM at tile granularity (1024 elements for a 1-D slab, 8x128
    for a 2-D one), so a per-beam DMA of the raw burst does not compile.
  * Phase 4 (scatter projection) becomes a **compare-broadcast reduction**:
    ``mask[v] = any_j (cols[j] == v & j < n_child)``.  TPUs have no efficient
    VMEM scatter; an elementwise compare over the lane-aligned vocab axis is
    branch-free and VPU-friendly.  Next-state ids are produced vocab-aligned
    by the same reduction (token columns within a CSR row are unique, so at
    most one slot hits each column).

Slots stream through a ``fori_loop`` one at a time, so VMEM holds
``O(beam_tile * (V + bmax))`` for any branch factor.  Every value in the
kernel is a 2-D ``(beam_tile, lanes)`` array; slot ``j`` of a row-major slot
array is read with a one-hot lane reduction (:func:`_lane`), because the TPU
has no dynamic lane indexing.

The fused variant additionally normalizes raw logits with an in-register
log-softmax before masking, eliminating one full HBM round-trip over the
``(B*M, V)`` tensor per decode step (a beyond-paper optimization).

The **candidate-compressed** kernels (``vntk_topk_pallas`` /
``vntk_stacked_topk_pallas``, DESIGN.md §8) go one step further: instead of
writing the vocab-aligned ``(nb, V)`` masked log-probs *and* next-state map
back to HBM, they select each beam's dense-rank top-``C`` **in VMEM** and
emit only ``(nb, C)`` scores/tokens/states.  HBM write traffic per step drops
from ``O(nb * V)`` to ``O(nb * C)``.  Selection is a branch-free
rank-by-counting pass (TPUs have no in-VMEM sort) over the valid children
plus the smallest missing tokens at NEG_INF (the dense tie-break's
invalid-continuation order); the index tie-break reproduces the dense path's
flat-index tie order exactly (candidate slots are token-ascending, see
``core.vntk._topk_from_candidates``).

The **compressed-slab** entry points (``vntk_compressed_*``, DESIGN.md §11)
gather the delta-encoded token burst of
:class:`repro.core.compressed_slab.CompressedSlab` (int16 where the vocab
permits) instead of the ``(E, 2)`` int32 edge slab and decompress it in the
same XLA front — an int32 cumsum over the burst recovers the token columns,
and next states are ``row_start + slot + level_base``.  Everything after the
front is the shared kernel, so outputs are bit-identical to the
uncompressed kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e10
LANES = 128

__all__ = [
    "vntk_pallas",
    "vntk_fused_logsoftmax_pallas",
    "vntk_stacked_pallas",
    "vntk_stacked_fused_logsoftmax_pallas",
    "vntk_topk_pallas",
    "vntk_stacked_topk_pallas",
    "vntk_compressed_pallas",
    "vntk_stacked_compressed_pallas",
    "vntk_compressed_topk_pallas",
    "vntk_stacked_compressed_topk_pallas",
]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _beam_padding(nb: int, beam_tile: int) -> tuple[int, int]:
    """Grid tiling for ``nb`` beam rows: ``(beam_tile, nb_padded)``.

    The beam axis is padded UP to a tile multiple instead of degrading the
    tile (walking a prime row count down to tile=1 would serialize the whole
    grid).  Pad rows have no children, so they mask everything and are
    sliced away by the caller.
    """
    beam_tile = max(1, min(beam_tile, nb))
    return beam_tile, _round_up(nb, beam_tile)


def _pad_rows(arr, nb_padded: int):
    """Pad axis 0 of ``arr`` to ``nb_padded`` rows with zeros."""
    nb = arr.shape[0]
    if nb == nb_padded:
        return arr
    return jnp.pad(arr, [(0, nb_padded - nb)] + [(0, 0)] * (arr.ndim - 1))


# ---------------------------------------------------------------------------
# Phases 1+2 (XLA front): row bounds and the speculative burst
# ---------------------------------------------------------------------------
def _burst(nodes, cids, row_pointers, bmax: int):
    """Each row's child count and the edge index of each of its ``bmax``
    speculative slots.  With ``cids`` the row pointers carry a leading
    constraint axis (stacked store, §4)."""
    if cids is None:
        starts = row_pointers[nodes]
        n_child = row_pointers[nodes + 1] - starts
    else:
        starts = row_pointers[cids, nodes]
        n_child = row_pointers[cids, nodes + 1] - starts
    idx = starts[:, None] + jnp.arange(bmax, dtype=starts.dtype)[None, :]
    return n_child, idx


def _raw_slots(edges, cids, idx):
    """Slot arrays ``(cols, next)`` of the ``(E, 2)`` / ``(K, E, 2)`` slab."""
    e = edges[idx] if cids is None else edges[cids[:, None], idx]
    return e[..., 0], e[..., 1]


def _delta_slots(tok_delta, cids, idx, base):
    """Slot arrays of the compressed slab (DESIGN.md §11).

    The burst starts at the row start, whose delta IS the absolute token, so
    one int32 cumsum recovers every column (the cast comes BEFORE the cumsum:
    int16 partial sums would wrap for vocabularies near the int16 limit).
    Next states need no stored bytes: ``next = edge_index + level_base``.
    Slots past the row end decode to garbage exactly like the uncompressed
    speculative over-read — the kernel's ``slot < n_child`` test masks both.
    """
    d = tok_delta[idx] if cids is None else tok_delta[cids[:, None], idx]
    return jnp.cumsum(d.astype(jnp.int32), axis=1), idx + base[:, None]


# ---------------------------------------------------------------------------
# Phases 3+4 (the kernel)
# ---------------------------------------------------------------------------
def _lane(x, j):
    """Column ``j`` (traced) of a ``(rows, lanes)`` value as ``(rows, 1)``.

    A one-hot max over the lanes: exact for every value (``-inf`` included),
    and it needs no dynamic lane indexing, which the TPU does not have."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        low = jnp.asarray(-jnp.inf, x.dtype)
    else:
        low = jnp.asarray(jnp.iinfo(x.dtype).min, x.dtype)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.max(jnp.where(iota == j, x, low), axis=1, keepdims=True)


def _log_probs(x_ref, fused_logsoftmax: bool):
    x = x_ref[...].astype(jnp.float32)
    if not fused_logsoftmax:
        return x
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))
    return x - m - lse


def _mask_body(n_ref, cols_ref, next_ref, x_ref, out_lp_ref, out_next_ref, *,
               n_slots: int, fused_logsoftmax: bool):
    """Vocab-aligned ``(masked log-probs, next states)`` for one beam tile."""
    cols, nexts, n_child = cols_ref[...], next_ref[...], n_ref[...]
    shape = out_lp_ref.shape
    iota_v = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def slot(j, carry):
        mask, nxt = carry
        hit = (iota_v == _lane(cols, j)) & (j < n_child)
        return jnp.where(hit, 1, mask), jnp.where(hit, _lane(nexts, j), nxt)

    zeros = jnp.zeros(shape, jnp.int32)
    mask, nxt = jax.lax.fori_loop(0, n_slots, slot, (zeros, zeros))
    lp = _log_probs(x_ref, fused_logsoftmax).astype(out_lp_ref.dtype)
    out_lp_ref[...] = jnp.where(
        mask != 0, lp, jnp.asarray(NEG_INF, out_lp_ref.dtype))
    out_next_ref[...] = nxt


def _select_body(n_ref, cols_ref, next_ref, x_ref, out_sc_ref, out_tok_ref,
                 out_next_ref, *, n_slots: int, vocab: int,
                 fused_logsoftmax: bool):
    """Candidate-compressed step (DESIGN.md §8) for one beam tile.

    Each beam's dense-rank top-``C`` over its valid children, by (lp desc,
    token asc), then the smallest missing tokens at NEG_INF — exactly
    :func:`repro.core.vntk._topk_from_candidates`.  Only the
    ``(beam_tile, C)`` winners leave VMEM.

    The missing-token fills need no rank pass: all of them tie at NEG_INF
    and rank after every child (ties go to the lower index), so fill ``i``
    lands at output lane ``a + i``, where ``a`` counts the children whose
    log-prob is ``>= NEG_INF``.  A child's rank is the number of children
    that beat it plus, when its log-prob is below NEG_INF, the number of
    in-range fills among the first ``C``.
    """
    lp = _log_probs(x_ref, fused_logsoftmax)
    cols, nexts, n_child = cols_ref[...], next_ref[...], n_ref[...]
    width = out_sc_ref.shape[1]
    iota_v = jax.lax.broadcasted_iota(jnp.int32, lp.shape, 1)
    iota_s = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, out_sc_ref.shape, 1)
    minf = jnp.float32(jnp.finfo(jnp.float32).min)
    neg = jnp.float32(NEG_INF)

    # candidate log-prob of every slot (minf where the slot is no child)
    def gather(j, keys):
        lp_j = jnp.max(jnp.where(iota_v == _lane(cols, j), lp, -jnp.inf),
                       axis=1, keepdims=True)
        return jnp.where((iota_s == j) & (j < n_child), lp_j, keys)

    keys = jax.lax.fori_loop(
        0, n_slots, gather, jnp.full(cols.shape, minf, jnp.float32))

    # fills: i-th missing token = i + |{j : cols[j] - j <= i}|
    n_fill = jnp.minimum(width, vocab - n_child)  # in-range fills among C
    a = jnp.sum((keys >= neg).astype(jnp.int32), axis=1, keepdims=True)
    fill_i = iota_c - a

    def count(j, cnt):
        below = (_lane(cols, j) - j) <= fill_i
        return cnt + (below & (j < n_child)).astype(jnp.int32)

    cnt = jax.lax.fori_loop(0, n_slots, count,
                            jnp.zeros(out_sc_ref.shape, jnp.int32))
    is_fill = (fill_i >= 0) & (fill_i < n_fill)
    out0 = (jnp.where(is_fill, neg, 0.0).astype(jnp.float32),
            jnp.where(is_fill, fill_i + cnt, 0),
            jnp.zeros(out_sc_ref.shape, jnp.int32))

    # children: rank-by-counting, then a compare-broadcast scatter into C
    def place(j, outs):
        sc, tok, nxt = outs
        key_j = _lane(keys, j)
        beats = (keys > key_j) | ((keys == key_j) & (iota_s < j))
        rank = jnp.sum(beats.astype(jnp.int32), axis=1, keepdims=True)
        rank = rank + jnp.where(neg > key_j, n_fill, 0)
        sel = (iota_c == rank) & (j < n_child)
        return (jnp.where(sel, key_j, sc),
                jnp.where(sel, _lane(cols, j), tok),
                jnp.where(sel, _lane(nexts, j), nxt))

    sc, tok, nxt = jax.lax.fori_loop(0, n_slots, place, out0)
    out_sc_ref[...] = sc.astype(out_sc_ref.dtype)
    out_tok_ref[...] = tok
    out_next_ref[...] = nxt


def _kernel_call(values, n_child, cols, nexts, *, vocab: int,
                 width: int | None, fused_logsoftmax: bool, out_dtype,
                 beam_tile: int = 8, interpret: bool | None = None):
    """Run the shared kernel over decoded slot arrays.

    ``width=None`` runs the vocab projection (two ``(nb, V)`` outputs); an
    integer runs the candidate-compressed selection (three ``(nb, width)``
    outputs)."""
    nb, n_slots = cols.shape
    if width is not None and not 1 <= width <= vocab:
        raise ValueError(f"width must be in [1, {vocab}], got {width}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    beam_tile, nb_pad = _beam_padding(nb, beam_tile)
    lanes = _round_up(n_slots, LANES)

    def rows(a, cols_to=None):
        a = _pad_rows(a.astype(jnp.int32), nb_pad)  # pad rows: no children
        if cols_to is not None and a.shape[1] < cols_to:
            a = jnp.pad(a, [(0, 0), (0, cols_to - a.shape[1])])
        return a

    def spec(w):
        return pl.BlockSpec((beam_tile, w), lambda i: (i, 0))

    inputs = (rows(n_child[:, None]), rows(cols, lanes), rows(nexts, lanes),
              _pad_rows(values, nb_pad))
    in_specs = [spec(1), spec(lanes), spec(lanes), spec(vocab)]
    if width is None:
        body = functools.partial(_mask_body, n_slots=n_slots,
                                 fused_logsoftmax=fused_logsoftmax)
        outs = [(vocab, out_dtype), (vocab, jnp.int32)]
    else:
        body = functools.partial(_select_body, n_slots=n_slots, vocab=vocab,
                                 fused_logsoftmax=fused_logsoftmax)
        outs = [(width, jnp.float32), (width, jnp.int32), (width, jnp.int32)]
    res = pl.pallas_call(
        body,
        grid=(nb_pad // beam_tile,),
        in_specs=in_specs,
        out_specs=[spec(w) for w, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((nb_pad, w), dt) for w, dt in outs],
        interpret=interpret,
    )(*inputs)
    return tuple(o[:nb] for o in res)


def _flat_ids(constraint_ids, batch_shape):
    return jnp.broadcast_to(constraint_ids, batch_shape).reshape(-1).astype(
        jnp.int32)


def _edges_call(values, nodes, cids, row_pointers, edges, bmax, vocab, width,
                *, fused_logsoftmax, out_dtype=jnp.float32, **kw):
    """Kernel over the raw ``(E, 2)`` (or stacked ``(K, E, 2)``) slab."""
    batch_shape = nodes.shape
    bmax = max(bmax, 1)
    if edges.shape[-2] < bmax:
        raise ValueError("edges tensor smaller than one speculative burst")
    n_child, idx = _burst(nodes.reshape(-1), cids, row_pointers, bmax)
    cols, nexts = _raw_slots(edges, cids, idx)
    outs = _kernel_call(values.reshape(-1, vocab), n_child, cols, nexts,
                        vocab=vocab, width=width,
                        fused_logsoftmax=fused_logsoftmax,
                        out_dtype=out_dtype, **kw)
    last = vocab if width is None else width
    return tuple(o.reshape(batch_shape + (last,)) for o in outs)


def _compressed_call(values, nodes, cids, base, row_pointers, tok_delta, bmax,
                     vocab, width, *, fused_logsoftmax, out_dtype=jnp.float32,
                     **kw):
    """Kernel over the compressed slab (DESIGN.md §11); ``base`` is one
    int32 per row."""
    batch_shape = nodes.shape
    bmax = max(bmax, 1)
    if tok_delta.shape[-1] < bmax:
        raise ValueError("token slab smaller than one speculative burst")
    n_child, idx = _burst(nodes.reshape(-1), cids, row_pointers, bmax)
    cols, nexts = _delta_slots(tok_delta, cids, idx, base)
    outs = _kernel_call(values.reshape(-1, vocab), n_child, cols, nexts,
                        vocab=vocab, width=width,
                        fused_logsoftmax=fused_logsoftmax,
                        out_dtype=out_dtype, **kw)
    last = vocab if width is None else width
    return tuple(o.reshape(batch_shape + (last,)) for o in outs)


def vntk_pallas(
    log_probs: jax.Array,
    nodes: jax.Array,
    row_pointers: jax.Array,
    edges: jax.Array,
    bmax: int,
    vocab: int,
    **kw,
) -> tuple[jax.Array, jax.Array]:
    """Alg. 2 on pre-normalized log-probs. Shapes: (..., V) / (...,)."""
    return _edges_call(log_probs, nodes, None, row_pointers, edges, bmax,
                       vocab, None, fused_logsoftmax=False,
                       out_dtype=log_probs.dtype, **kw)


def vntk_fused_logsoftmax_pallas(
    logits: jax.Array,
    nodes: jax.Array,
    row_pointers: jax.Array,
    edges: jax.Array,
    bmax: int,
    vocab: int,
    **kw,
) -> tuple[jax.Array, jax.Array]:
    """Fused LogSoftmax + Alg. 2 masking in a single HBM pass."""
    return _edges_call(logits, nodes, None, row_pointers, edges, bmax, vocab,
                       None, fused_logsoftmax=True, **kw)


def vntk_stacked_pallas(
    log_probs: jax.Array,
    nodes: jax.Array,
    constraint_ids: jax.Array,
    row_pointers: jax.Array,  # (K, S+1)
    edges: jax.Array,  # (K, E, 2)
    bmax: int,
    vocab: int,
    **kw,
) -> tuple[jax.Array, jax.Array]:
    """Alg. 2 over a stacked constraint store, pre-normalized log-probs."""
    return _edges_call(log_probs, nodes,
                       _flat_ids(constraint_ids, nodes.shape), row_pointers,
                       edges, bmax, vocab, None, fused_logsoftmax=False,
                       out_dtype=log_probs.dtype, **kw)


def vntk_stacked_fused_logsoftmax_pallas(
    logits: jax.Array,
    nodes: jax.Array,
    constraint_ids: jax.Array,
    row_pointers: jax.Array,
    edges: jax.Array,
    bmax: int,
    vocab: int,
    **kw,
) -> tuple[jax.Array, jax.Array]:
    """Fused LogSoftmax + stacked Alg. 2 masking in a single HBM pass."""
    return _edges_call(logits, nodes, _flat_ids(constraint_ids, nodes.shape),
                       row_pointers, edges, bmax, vocab, None,
                       fused_logsoftmax=True, **kw)


def vntk_topk_pallas(
    values: jax.Array,  # (..., V) log-probs, or raw logits when fused
    nodes: jax.Array,
    row_pointers: jax.Array,
    edges: jax.Array,
    bmax: int,
    vocab: int,
    width: int,
    *,
    fused_logsoftmax: bool = False,
    **kw,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Candidate-compressed Alg. 2 (DESIGN.md §8): per-beam dense-rank top-C
    selected in VMEM.  Returns ``(scores, tokens, next_states)``, each
    ``(..., width)``; with ``fused_logsoftmax`` the inputs are raw logits and
    normalization happens in-register before selection."""
    return _edges_call(values, nodes, None, row_pointers, edges, bmax, vocab,
                       width, fused_logsoftmax=fused_logsoftmax, **kw)


def vntk_stacked_topk_pallas(
    values: jax.Array,  # (..., V) log-probs, or raw logits when fused
    nodes: jax.Array,
    constraint_ids: jax.Array,
    row_pointers: jax.Array,  # (K, S+1)
    edges: jax.Array,  # (K, E, 2)
    bmax: int,
    vocab: int,
    width: int,
    *,
    fused_logsoftmax: bool = False,
    **kw,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stacked-store candidate-compressed Alg. 2 over a ConstraintStore."""
    return _edges_call(values, nodes, _flat_ids(constraint_ids, nodes.shape),
                       row_pointers, edges, bmax, vocab, width,
                       fused_logsoftmax=fused_logsoftmax, **kw)


def _row_base(base, batch_shape):
    return jnp.broadcast_to(jnp.asarray(base, jnp.int32),
                            batch_shape).reshape(-1)


def vntk_compressed_pallas(
    values: jax.Array,  # (..., V) log-probs, or raw logits when fused
    nodes: jax.Array,
    row_pointers: jax.Array,  # (S+1,)
    tok_delta: jax.Array,  # (E+pad,) int16/int32
    base,  # scalar or (...,) int32 level base for this step
    bmax: int,
    vocab: int,
    *,
    fused_logsoftmax: bool = False,
    **kw,
) -> tuple[jax.Array, jax.Array]:
    """Alg. 2 over the compressed slab (DESIGN.md §11): the speculative burst
    gathers delta tokens (int16 where the vocab permits) and decompresses
    them in front of the kernel.  Bit-identical to :func:`vntk_pallas` /
    :func:`vntk_fused_logsoftmax_pallas` on the same trie."""
    return _compressed_call(
        values, nodes, None, _row_base(base, nodes.shape), row_pointers,
        tok_delta, bmax, vocab, None, fused_logsoftmax=fused_logsoftmax,
        out_dtype=jnp.float32 if fused_logsoftmax else values.dtype, **kw)


def vntk_stacked_compressed_pallas(
    values: jax.Array,
    nodes: jax.Array,
    constraint_ids: jax.Array,
    row_pointers: jax.Array,  # (K, S+1)
    tok_delta: jax.Array,  # (K, E+pad)
    base_k: jax.Array,  # (K,) int32 per-member level base for this step
    bmax: int,
    vocab: int,
    *,
    fused_logsoftmax: bool = False,
    **kw,
) -> tuple[jax.Array, jax.Array]:
    """Stacked-store compressed Alg. 2: the delta burst indexes one extra
    leading constraint axis; each beam's base is its member's."""
    cids = _flat_ids(constraint_ids, nodes.shape)
    return _compressed_call(
        values, nodes, cids, base_k.astype(jnp.int32)[cids], row_pointers,
        tok_delta, bmax, vocab, None, fused_logsoftmax=fused_logsoftmax,
        out_dtype=jnp.float32 if fused_logsoftmax else values.dtype, **kw)


def vntk_compressed_topk_pallas(
    values: jax.Array,
    nodes: jax.Array,
    row_pointers: jax.Array,
    tok_delta: jax.Array,
    base,
    bmax: int,
    vocab: int,
    width: int,
    *,
    fused_logsoftmax: bool = False,
    **kw,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Candidate-compressed selection over the compressed slab: §8's
    ``(nb, C)`` outputs fed by §11's 2 B/slot burst.  Bit-identical to
    :func:`vntk_topk_pallas`."""
    return _compressed_call(
        values, nodes, None, _row_base(base, nodes.shape), row_pointers,
        tok_delta, bmax, vocab, width, fused_logsoftmax=fused_logsoftmax,
        **kw)


def vntk_stacked_compressed_topk_pallas(
    values: jax.Array,
    nodes: jax.Array,
    constraint_ids: jax.Array,
    row_pointers: jax.Array,  # (K, S+1)
    tok_delta: jax.Array,  # (K, E+pad)
    base_k: jax.Array,  # (K,) int32
    bmax: int,
    vocab: int,
    width: int,
    *,
    fused_logsoftmax: bool = False,
    **kw,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stacked-store compressed candidate-compressed Alg. 2."""
    cids = _flat_ids(constraint_ids, nodes.shape)
    return _compressed_call(
        values, nodes, cids, base_k.astype(jnp.int32)[cids], row_pointers,
        tok_delta, bmax, vocab, width, fused_logsoftmax=fused_logsoftmax,
        **kw)
