"""STATIC-constrained generative-retrieval server (the paper's use case).

``GenerativeRetriever.retrieve`` takes user-history token sequences, prefills
the model once per request, then runs the constrained beam search of
Algorithm 1 over SID tokens.  Each history's state (K/V, or the latents of
latent attention) is held once and shared by the request's beams; only the
SID suffix is per beam (DESIGN.md §14).
Which constraint method masks each decode level is bound by a
:class:`~repro.decoding.DecodePolicy` — the paper's STATIC matrix (100%
compliance, §5.4), the stacked multi-tenant store, or any §5.2 baseline all
serve through this same jitted path.

Multi-tenant mode (DESIGN.md §4): build the retriever with a stacked policy
(``DecodePolicy.stacked(store)`` — or just pass the ConstraintStore) and a
per-request ``constraint_ids`` vector to ``retrieve`` — each batch row is
then decoded under its own business constraint set in the same jitted beam
search.  The policy rides into jit as a pytree ARGUMENT with swap-invariant
static metadata, so a registry hot-swap (``set_constraints``) never
recompiles.

STATIC policies default to candidate-compressed decoding (DESIGN.md §8):
sparse levels advance beams from per-beam top-C lists instead of
vocab-aligned tensors, bit-identical to the dense path.  Whether a level
compresses is static policy metadata (``supports_topk_at``), so it needs no
plumbing here and cannot flip across a hot-swap.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TransformerConfig
from repro.core import beam_search
from repro.decoding import as_policy
from repro.models import kvcache as kv_lib
from repro.models import transformer
from repro.observability import annotate

__all__ = ["GenerativeRetriever"]


class GenerativeRetriever:
    def __init__(
        self,
        params,
        cfg: TransformerConfig,
        policy=None,  # DecodePolicy | TransitionMatrix | ConstraintStore | None
        sid_length: int = None,
        sid_vocab: int = None,
        beam_size: int = 20,
    ):
        self.params = params
        self.cfg = cfg
        if sid_length is None or sid_vocab is None:
            raise TypeError("sid_length and sid_vocab are required")
        self.policy = as_policy(policy)
        self.L = sid_length
        self.V = sid_vocab
        self.M = beam_size
        # expert decoders: the last batch's rows per held expert over its
        # decode levels, and the row tiles its grouped matmuls visited
        # there (0 on the XLA path); None otherwise
        self.expert_rows: Optional[np.ndarray] = None
        self.row_tiles: Optional[int] = None
        # One jitted end-to-end retrieval step (prefill + L constrained beam
        # steps).  The policy rides in as a pytree ARGUMENT, so a registry
        # hot-swap (new leaf values, identical shapes + static metadata)
        # reuses the compiled executable — zero recompilation.  Jitting once
        # here (not per call) also keeps the layer scans out of the
        # per-request eager path, which used to recompile every batch.
        self._retrieve_jit = jax.jit(self._retrieve_impl)

    # -- constraint plumbing -------------------------------------------------
    @property
    def num_sets(self) -> Optional[int]:
        """Stacked-store member count, or None when single-tenant."""
        return self.policy.num_sets

    def set_constraints(self, obj) -> bool:
        """Install a refreshed matrix/store (the registry swap path).

        A hot swap (same capacity envelope) replaces only pytree leaves —
        shapes and static metadata are invariant — so the jitted retrieve
        step is reused as-is.  A cold swap (regrown envelope, DESIGN.md §7)
        changes static metadata, so the next ``retrieve`` re-specializes
        the jitted step exactly once.  Returns True iff the swap was cold.
        """
        before = jax.tree_util.tree_structure(self.policy)
        self.policy = self.policy.with_constraints(obj)
        return jax.tree_util.tree_structure(self.policy) != before

    @property
    def constraints(self):
        """The underlying TransitionMatrix / ConstraintStore (read-only;
        install refreshed constraints via :meth:`set_constraints`)."""
        return self.policy.constraints

    # -- serving -------------------------------------------------------------
    def retrieve(self, history: np.ndarray,
                 constraint_ids: Optional[np.ndarray] = None):
        """history (B, S) int32 -> (sids (B, M, L), scores (B, M)).

        ``constraint_ids`` (B,) int32 selects each request's constraint set
        from the stacked ConstraintStore bound in ``self.policy``.
        """
        cids = None
        if constraint_ids is not None:
            cids_np = np.asarray(constraint_ids, np.int32)
            num_sets = self.num_sets
            if num_sets is not None and (
                cids_np.min() < 0 or cids_np.max() >= num_sets
            ):
                # an out-of-range id would be silently clamped by the stacked
                # gather — i.e. served under the WRONG business constraint
                raise ValueError(
                    f"constraint_ids must be in [0, {num_sets}), got "
                    f"range [{cids_np.min()}, {cids_np.max()}]"
                )
            cids = jnp.asarray(cids_np)
        with annotate("retrieve.dispatch"):
            tokens, scores, counts = self._retrieve_jit(
                self.params, jnp.asarray(history), self.policy, cids
            )
        with annotate("retrieve.readback"):
            if counts is not None:
                self.expert_rows = np.asarray(counts[0])
                self.row_tiles = int(counts[1])
            return np.asarray(tokens), np.asarray(scores)

    def compile_step(self, batch: int, prompt_width: int):
        """Compile the single-matrix retrieval step ahead of time for
        ``(batch, prompt_width)`` histories; ``retrieve`` at that shape then
        reuses it.  The result's ``memory_analysis()`` and ``as_text()``
        size and inspect the step before it runs."""
        hist = jax.ShapeDtypeStruct((batch, prompt_width), jnp.int32)
        return self._retrieve_jit.lower(
            self.params, hist, self.policy, None).compile()

    def _retrieve_impl(self, params, history, policy, constraint_ids):
        """(tokens (B, M, L), scores (B, M), expert counts): the last is,
        for an expert decoder, the rows each held expert received over the
        decode levels and the row tiles the grouped matmuls visited there,
        else None."""
        B, S = history.shape
        M, cfg = self.M, self.cfg
        Ls = self.L - 1  # a suffix column for each decode level after prefill
        window = cfg.sliding_window
        if window is not None and window < S + Ls:
            raise NotImplementedError(
                "the retrieval step shares each history across its beams "
                "(transformer.gr_decode_step), which serves decoders whose "
                f"window covers history and SID suffix ({S + Ls} "
                f"positions); got sliding_window={window}")
        if not cfg.tie_embeddings:
            # only the SID columns of an untied head are ever scored
            params = dict(params, unemb=params["unemb"][:, : self.V])
        # named_scope: trace-time profiler labels only (DESIGN.md §9) —
        # no runtime cost, no change to the computation.
        with jax.named_scope("prefill"):
            pre_logits, cache = transformer.prefill(params, history, cfg)
        # the history, (n_layers, B, S, ...), once per request for its M
        # beams; the suffix of each beam starts empty
        def empty_suffix(a):
            return jnp.zeros((cfg.n_layers, B * M, Ls) + a.shape[3:],
                             a.dtype)

        rows, tiles = ((jnp.zeros((cfg.moe.n_experts,), jnp.int32),
                        jnp.zeros((), jnp.int32)) if cfg.moe
                       else (None, None))
        step = jnp.zeros((), jnp.int32)
        if isinstance(cache, kv_lib.MLACache):
            cache = kv_lib.LatentHistoryCache(
                hist_c=cache.c_kv, hist_r=cache.k_rope,
                sfx_c=empty_suffix(cache.c_kv),
                sfx_r=empty_suffix(cache.k_rope), step=step,
                expert_rows=rows, row_tiles=tiles)
        else:
            sfx = empty_suffix(cache.k)
            cache = kv_lib.SharedHistoryCache(
                hist_k=cache.k, hist_v=cache.v, sfx_k=sfx, sfx_v=sfx,
                step=step, expert_rows=rows, row_tiles=tiles)

        def logits_fn(c, last_tokens, step):
            logits, c = transformer.decode_step(
                params, c, last_tokens.reshape(B * M, 1), cfg)
            return logits[:, 0, : self.V].reshape(B, M, self.V), c

        def gather_suffix(c, beam_idx):
            return c.take_beams((jnp.arange(B)[:, None] * M
                                 + beam_idx).reshape(-1))

        state, cache = beam_search(
            logits_fn, cache, B, M, self.L, policy,
            carry_gather_fn=gather_suffix,
            first_logits=pre_logits[:, 0, : self.V],
            constraint_ids=constraint_ids,
        )
        counts = (None if cache.expert_rows is None
                  else (cache.expert_rows, cache.row_tiles))
        return state.tokens, state.scores, counts
