"""Jitted dispatchers for the Pallas kernels.

``impl`` resolution:
  * "pallas"  — the Pallas kernel (compiled on TPU, interpret-mode on CPU).
  * "xla"     — the pure-jnp oracle (always available, used for training-time
                code paths where XLA fusion is already optimal).
  * None      — "pallas" on TPU, "xla" elsewhere (interpret mode is a
                correctness tool, not a fast path, so CPU defaults to XLA).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref as _ref
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.vntk import (
    vntk_compressed_pallas,
    vntk_compressed_topk_pallas,
    vntk_fused_logsoftmax_pallas,
    vntk_pallas,
    vntk_stacked_compressed_pallas,
    vntk_stacked_compressed_topk_pallas,
    vntk_stacked_fused_logsoftmax_pallas,
    vntk_stacked_pallas,
    vntk_stacked_topk_pallas,
    vntk_topk_pallas,
)

__all__ = ["vntk", "vntk_fused_logsoftmax", "vntk_topk", "vntk_compressed",
           "vntk_compressed_topk", "embedding_bag", "grouped_matmul",
           "resolve"]


def resolve(impl: str | None) -> str:
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


@partial(jax.jit, static_argnames=("bmax", "vocab", "impl"))
def vntk(log_probs, nodes, row_pointers, edges, bmax: int, vocab: int,
         impl: str | None = None, constraint_ids=None):
    """Alg. 2 (VNTK): (masked_log_probs, next_states), both vocab-aligned.

    With ``constraint_ids`` (per-row int32), ``row_pointers``/``edges`` must
    carry a leading constraint axis — (K, S+1) / (K, E, 2) — and each row is
    masked by its own set (DESIGN.md §4).  ``None`` keeps the single-matrix
    path untouched (the branch is resolved at trace time).
    """
    if constraint_ids is None:
        if resolve(impl) == "pallas":
            return vntk_pallas(log_probs, nodes, row_pointers, edges, bmax, vocab)
        return _ref.vntk_ref(log_probs, nodes, row_pointers, edges, bmax, vocab)
    if resolve(impl) == "pallas":
        return vntk_stacked_pallas(
            log_probs, nodes, constraint_ids, row_pointers, edges, bmax, vocab
        )
    return _ref.vntk_stacked_ref(
        log_probs, nodes, constraint_ids, row_pointers, edges, bmax, vocab
    )


@partial(jax.jit, static_argnames=("bmax", "vocab", "impl"))
def vntk_fused_logsoftmax(logits, nodes, row_pointers, edges, bmax: int,
                          vocab: int, impl: str | None = None,
                          constraint_ids=None):
    """Fused LogSoftmax + VNTK masking (single HBM pass over logits)."""
    if constraint_ids is None:
        if resolve(impl) == "pallas":
            return vntk_fused_logsoftmax_pallas(
                logits, nodes, row_pointers, edges, bmax, vocab
            )
        return _ref.vntk_fused_logsoftmax_ref(
            logits, nodes, row_pointers, edges, bmax, vocab
        )
    if resolve(impl) == "pallas":
        return vntk_stacked_fused_logsoftmax_pallas(
            logits, nodes, constraint_ids, row_pointers, edges, bmax, vocab
        )
    return _ref.vntk_stacked_fused_logsoftmax_ref(
        logits, nodes, constraint_ids, row_pointers, edges, bmax, vocab
    )


@partial(jax.jit, static_argnames=("bmax", "vocab", "width", "impl",
                                   "fused_logsoftmax"))
def vntk_topk(values, nodes, row_pointers, edges, bmax: int, vocab: int,
              width: int, impl: str | None = None, constraint_ids=None,
              fused_logsoftmax: bool = False):
    """Candidate-compressed VNTK (DESIGN.md §8): per-beam dense-rank top-C.

    Returns ``(scores, tokens, next_states)``, each ``(..., width)`` — the
    compressed per-beam candidate lists the sparse beam-advance consumes.
    ``values`` are normalized log-probs, or raw logits with
    ``fused_logsoftmax=True`` (the kernel then normalizes in-register).  With
    ``constraint_ids`` the tables carry the stacked leading constraint axis.
    """
    if constraint_ids is None:
        if resolve(impl) == "pallas":
            return vntk_topk_pallas(
                values, nodes, row_pointers, edges, bmax, vocab, width,
                fused_logsoftmax=fused_logsoftmax,
            )
        return _ref.vntk_topk_ref(
            values, nodes, row_pointers, edges, bmax, vocab, width,
            fused_logsoftmax=fused_logsoftmax,
        )
    if resolve(impl) == "pallas":
        return vntk_stacked_topk_pallas(
            values, nodes, constraint_ids, row_pointers, edges, bmax, vocab,
            width, fused_logsoftmax=fused_logsoftmax,
        )
    return _ref.vntk_stacked_topk_ref(
        values, nodes, constraint_ids, row_pointers, edges, bmax, vocab,
        width, fused_logsoftmax=fused_logsoftmax,
    )


@partial(jax.jit, static_argnames=("bmax", "vocab", "impl",
                                   "fused_logsoftmax"))
def vntk_compressed(values, nodes, row_pointers, tok_delta, base, bmax: int,
                    vocab: int, impl: str | None = None, constraint_ids=None,
                    fused_logsoftmax: bool = False):
    """VNTK over the compressed slab (DESIGN.md §11): vocab-aligned outputs.

    ``tok_delta``/``base`` come from a
    :class:`repro.core.compressed_slab.CompressedSlab` (``base`` is that
    step's ``level_base`` entry — scalar, or per-member ``(K,)`` with
    ``constraint_ids``).  Bit-identical to :func:`vntk` /
    :func:`vntk_fused_logsoftmax` on the same trie.
    """
    if constraint_ids is None:
        if resolve(impl) == "pallas":
            return vntk_compressed_pallas(
                values, nodes, row_pointers, tok_delta, base, bmax, vocab,
                fused_logsoftmax=fused_logsoftmax,
            )
        return _ref.vntk_compressed_ref(
            values, nodes, row_pointers, tok_delta, base, bmax, vocab,
            fused_logsoftmax=fused_logsoftmax,
        )
    if resolve(impl) == "pallas":
        return vntk_stacked_compressed_pallas(
            values, nodes, constraint_ids, row_pointers, tok_delta, base,
            bmax, vocab, fused_logsoftmax=fused_logsoftmax,
        )
    return _ref.vntk_stacked_compressed_ref(
        values, nodes, constraint_ids, row_pointers, tok_delta, base, bmax,
        vocab, fused_logsoftmax=fused_logsoftmax,
    )


@partial(jax.jit, static_argnames=("bmax", "vocab", "width", "impl",
                                   "fused_logsoftmax"))
def vntk_compressed_topk(values, nodes, row_pointers, tok_delta, base,
                         bmax: int, vocab: int, width: int,
                         impl: str | None = None, constraint_ids=None,
                         fused_logsoftmax: bool = False):
    """Candidate-compressed VNTK over the compressed slab (§8 x §11)."""
    if constraint_ids is None:
        if resolve(impl) == "pallas":
            return vntk_compressed_topk_pallas(
                values, nodes, row_pointers, tok_delta, base, bmax, vocab,
                width, fused_logsoftmax=fused_logsoftmax,
            )
        return _ref.vntk_compressed_topk_ref(
            values, nodes, row_pointers, tok_delta, base, bmax, vocab, width,
            fused_logsoftmax=fused_logsoftmax,
        )
    if resolve(impl) == "pallas":
        return vntk_stacked_compressed_topk_pallas(
            values, nodes, constraint_ids, row_pointers, tok_delta, base,
            bmax, vocab, width, fused_logsoftmax=fused_logsoftmax,
        )
    return _ref.vntk_stacked_compressed_topk_ref(
        values, nodes, constraint_ids, row_pointers, tok_delta, base, bmax,
        vocab, width, fused_logsoftmax=fused_logsoftmax,
    )


@partial(jax.jit, static_argnames=("mode", "impl"))
def embedding_bag(table, indices, mode: str = "sum", impl: str | None = None):
    """Fixed-arity EmbeddingBag: (B, K) indices -> (B, D) reduced rows."""
    if resolve(impl) == "pallas":
        return embedding_bag_pallas(table, indices, mode=mode)
    return _ref.embedding_bag_ref(table, indices, mode=mode)


def grouped_matmul(lhs, rhs, sizes, impl: str | None = None):
    """Rows of ``lhs`` (m, k) in groups of ``sizes`` (g,), laid end to end,
    each times its ``rhs`` (g, k, n) slab: (m, n) in ``lhs.dtype``, with a
    float32 accumulator.

    "xla" is ``jax.lax.ragged_dot`` (rows past ``sum(sizes)`` read 0);
    "pallas" the grouped-matmul kernel, whose ``m`` is a multiple of its
    row tile (rows past ``sum(sizes)`` are left unwritten and may hold
    anything).
    """
    if resolve(impl) == "pallas":
        return grouped_matmul_pallas(lhs, rhs, sizes)
    return jax.lax.ragged_dot(lhs, rhs, sizes)
