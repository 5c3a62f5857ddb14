"""Continuous-batching serving engine (DESIGN.md §10).

``ServingEngine._serve_retrieval`` joins and evicts at *sequence*
boundaries: a batch of B requests runs all L beam-search levels in
lock-step, and a slot that finishes early idles until the whole batch
drains.  This engine joins and evicts at *step* boundaries — every engine
step decodes one SID level for every live slot, slots freed by completion
are refilled from the queue on the very next step, and all of it happens at
fixed static shapes through exactly four jitted functions compiled once at
warmup (the PR 6 recompile monitor asserts zero unexpected compiles across
admissions, evictions and registry hot-swaps).

The three subsystems:

* **Paged history KV** — each slot's prompt KV lives in pool pages indexed
  through a per-slot page table (``repro.models.kvcache``); ownership is a
  host-side free list with refcounts (:class:`PagedKVAllocator`).  The M
  beams of a slot read ONE stored history copy, and identical prompts
  share pages across slots via :class:`PrefixShareTable` — a hit also
  skips the prefill entirely (prefill is row-independent, so the donor's
  pages and first-token logits are bitwise what the skipped prefill would
  have produced).
* **Step scheduler** (:class:`StepScheduler`) — chunked prefill (at most
  ``prefill_chunk`` fresh prefills per step, so long-prompt bursts never
  stall running decodes), SLO deadline shedding at admission, and
  round-robin tenant fairness inherited from ``RequestQueue``'s lanes.
* **Trie-prefix sharing** — rows at heterogeneous decode levels are masked
  in one call via the policy's level-free path (``dense_d == 0`` node ids
  are globally unique, so ``(constraint_id, node)`` alone keys the
  admissible set), and ``DecodePolicy.shared_mask_step`` dedups mask rows
  across beams sitting on the same trie node.

Bit-identity contract: per-request ``(sids, scores)`` equal
``ServingEngine``'s output bit-for-bit (differential-fuzz asserted in
``tests/test_continuous.py``).  The decode step mirrors the sequential
engine's arithmetic exactly — see ``transformer.paged_decode_step`` — and
the beam advance below is the dense advance of ``core.beam_search``
verbatim.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TransformerConfig
from repro.models import kvcache as kv_lib
from repro.models import transformer
from repro.observability import (
    MetricsRegistry,
    annotate,
    compile_events,
    record_policy,
)
from repro.reliability.faults import InjectedFault, fire
from repro.serving.continuous.paged_kv import (
    PagedKVAllocator,
    PrefixShareTable,
)
from repro.serving.continuous.scheduler import StepScheduler, queue_push_back
from repro.serving.engine import _EngineMetrics

__all__ = ["ContinuousServingEngine"]

NEG_INF = -1e30


class ContinuousServingEngine:
    """Step-boundary continuous batching over a constrained retriever.

    Built from the same :class:`GenerativeRetriever` the other engines
    serve (the retriever contributes params/config/policy and the SID
    geometry; its own jitted path is not used).  The policy must support
    level-free masking — build its constraint index with ``dense_d=0``.
    """

    def __init__(self, retriever, *, registry=None, slots: int = 8,
                 prompt_width: int = 8, page_size: int = 8,
                 prefill_chunk: int = 2, share_width: Optional[int] = None,
                 share_capacity: int = 64, deadline_s: Optional[float] = None,
                 metrics: Optional[MetricsRegistry] = None, breaker=None,
                 admit_retry_budget: int = 3):
        self.retriever = retriever
        self.breaker = breaker
        self.admit_retry_budget = int(admit_retry_budget)
        self.params = retriever.params
        self.cfg: TransformerConfig = retriever.cfg
        self.policy = retriever.policy
        self.L, self.V, self.M = retriever.L, retriever.V, retriever.M
        self.S = int(prompt_width)
        self.n_slots = int(slots)
        self.page_size = int(page_size)
        self.share_width = share_width
        self.registry = registry
        self._installed_version = None
        if not self.policy.supports_level_free:
            raise ValueError(
                "continuous batching requires a level-free-capable policy: "
                "build the constraint index with dense_d=0 "
                f"(got [{self.policy.describe()}])"
            )

        self._m = _EngineMetrics(metrics)
        r = self._m.registry
        record_policy(r, self.policy, beams=self.M)
        self._page_util = r.gauge(
            "serving_kv_page_pool_utilization",
            "referenced fraction of the paged history KV pool")
        self._slot_reuse = r.counter(
            "serving_slot_reuse_total",
            "admissions into a slot that already served a request "
            "(continuous batching working: > 0 under any sustained load)")
        self._share_hits = r.counter(
            "serving_prefix_share_hits_total",
            "work units saved by sharing: kind=\"prompt\" = prefills "
            "skipped via the prompt-prefix table; kind=\"mask_row\" = "
            "VNTK mask rows deduped across beams on the same trie node")
        self._admissions = r.counter(
            "serving_admissions_total", "requests admitted into a slot")

        self.sched = StepScheduler(
            self.n_slots, self.L, prefill_chunk=prefill_chunk,
            deadline_s=deadline_s,
        )
        self.n_hist_pages = kv_lib.pages_for(self.S, self.page_size)
        n_pages = 1 + (self.n_slots + self.sched.prefill_chunk
                       + int(share_capacity)) * self.n_hist_pages
        self.alloc = PagedKVAllocator(n_pages)
        self.share = PrefixShareTable(self.alloc, capacity=share_capacity)

        # -- device state (engine-owned arrays, mutated only through jits) --
        cfg = self.cfg
        dtype = transformer._dtype(cfg)
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim()
        self._k_pool, self._v_pool = kv_lib.init_page_pool(
            cfg.n_layers, n_pages, self.page_size, kv, hd, dtype=dtype)
        Ls = self.L - 1
        zeros6 = jnp.zeros(
            (cfg.n_layers, self.n_slots, self.M, Ls, kv, hd), dtype)
        self._suffix_k, self._suffix_v = zeros6, zeros6
        self._tokens = jnp.zeros((self.n_slots, self.M, self.L), jnp.int32)
        self._scores = jnp.full((self.n_slots, self.M), NEG_INF, jnp.float32)
        self._nodes = jnp.ones((self.n_slots, self.M), jnp.int32)
        self._first_lp = jnp.zeros((self.n_slots, self.V), jnp.float32)
        self._share_acc = jnp.zeros((), jnp.int32)
        self._share_flushed = 0
        # host mirrors: page ownership + per-slot constraint ids
        self._page_table = np.zeros(
            (self.n_slots, self.n_hist_pages), np.int32)
        self._slot_pages: list[tuple[int, ...]] = [()] * self.n_slots
        self._cids = np.zeros(self.n_slots, np.int32)

        # -- the four jitted entry points (compiled once at warmup) ---------
        self._prefill_jit = jax.jit(self._prefill_impl)
        self._commit_jit = jax.jit(self._commit_impl)
        self._admit_jit = jax.jit(self._admit_impl)
        self._step_jit = jax.jit(self._step_impl)
        self._warm = False
        self._warmup()

    @property
    def metrics(self) -> MetricsRegistry:
        return self._m.registry

    @property
    def slots(self) -> int:
        """Concurrent-request capacity (the other engines' batch size)."""
        return self.n_slots

    @property
    def num_sets(self) -> Optional[int]:
        return self.policy.num_sets

    # ------------------------------------------------------------------
    # jitted implementations
    # ------------------------------------------------------------------
    def _prefill_impl(self, params, prompts):
        """(A, S) prompts -> (first SID logits (A, V), per-layer K/V rows)."""
        logits, cache = transformer.prefill(
            params, prompts, self.cfg, max_len=self.S)
        return logits[:, 0, : self.V], cache.k, cache.v

    def _commit_impl(self, k_pool, v_pool, ks, vs, page_ids):
        return (kv_lib.scatter_pages(k_pool, ks, page_ids),
                kv_lib.scatter_pages(v_pool, vs, page_ids))

    def _admit_impl(self, tokens, scores, nodes, first_lp, sk, sv,
                    admit, new_first):
        """Reset admitted slots to beam-search initial state (the exact
        ``_init_state`` of ``core.beam_search``: scores [0, -inf, ...],
        nodes at ROOT=1, tokens zeroed)."""
        slots, M = scores.shape
        init_scores = jnp.where(
            jnp.arange(M) == 0, 0.0, NEG_INF).astype(jnp.float32)
        tokens = jnp.where(admit[:, None, None], 0, tokens)
        scores = jnp.where(admit[:, None], init_scores[None, :], scores)
        nodes = jnp.where(admit[:, None], 1, nodes)
        first_lp = jnp.where(admit[:, None], new_first, first_lp)
        adm6 = admit[None, :, None, None, None, None]
        sk = jnp.where(adm6, 0.0, sk).astype(sk.dtype)
        sv = jnp.where(adm6, 0.0, sv).astype(sv.dtype)
        return tokens, scores, nodes, first_lp, sk, sv

    def _step_impl(self, params, policy, k_pool, v_pool, page_table,
                   sk, sv, tokens, scores, nodes, first_lp,
                   levels, live, cids, share_acc):
        """One decode level for every live slot, at its own level.

        Dead slots ride along (static shapes) with frozen outputs: their
        suffix write column lies past the suffix, so nothing is written, and
        their beam state is select-frozen, so they cost compute but never
        change bits.
        """
        slots, M, L = tokens.shape
        S, V, Ls = self.S, self.V, self.L - 1
        N = slots * M
        # a live row at level l >= 1 attends positions [0, S + l - 1] —
        # exactly the sequential cache's cur_pos at decode step l
        pos = S + jnp.clip(levels - 1, 0, L - 1)
        decoding = live & (levels > 0)
        write_col = jnp.where(decoding, levels - 1, Ls)
        col = jnp.clip(levels - 1, 0, L - 1)
        last = jnp.take_along_axis(
            tokens, col[:, None, None], axis=2)[:, :, 0]
        logits_raw, sk, sv = transformer.paged_decode_step(
            params, k_pool, v_pool, page_table, sk, sv, last, pos,
            write_col, self.cfg, hist_len=S)
        logits = logits_raw[:, 0, :V].reshape(slots, M, V)
        # level-0 slots consume the prefill's first-token logits (beam
        # search step 0): identical rows per beam, as the reference
        # broadcast makes them
        logits = jnp.where(
            (levels == 0)[:, None, None], first_lp[:, None, :], logits)

        nodes_flat = nodes.reshape(N)
        cids_flat = (jnp.repeat(cids, M)
                     if policy.requires_constraint_ids else None)
        masked, next_dense, _ = policy.shared_mask_step(
            logits.reshape(N, V), nodes_flat, constraint_ids=cids_flat,
            share_width=self.share_width)

        # dense beam advance, verbatim from core.beam_search
        total = scores[:, :, None] + masked.reshape(slots, M, V)
        top_scores, top_idx = jax.lax.top_k(total.reshape(slots, M * V), M)
        beam_idx = top_idx // V
        token = (top_idx % V).astype(jnp.int32)
        batch_ix = jnp.arange(slots)[:, None]
        new_nodes = next_dense.reshape(slots, M, V)[batch_ix, beam_idx, token]
        new_tokens = tokens[batch_ix, beam_idx]
        wmask = (jnp.arange(L, dtype=jnp.int32)[None, None, :]
                 == levels[:, None, None])
        new_tokens = jnp.where(wmask, token[:, :, None], new_tokens)

        tokens = jnp.where(live[:, None, None], new_tokens, tokens)
        scores = jnp.where(live[:, None], top_scores, scores)
        nodes = jnp.where(live[:, None], new_nodes, nodes)
        # beam-permute the decoded suffixes (the reference permutes its
        # whole cache; history pages are beam-invariant so only suffixes
        # need the gather)
        perm = jnp.where(live[:, None], beam_idx, jnp.arange(M)[None, :])
        idx6 = perm[None, :, :, None, None, None]
        sk = jnp.take_along_axis(sk, idx6, axis=2)
        sv = jnp.take_along_axis(sv, idx6, axis=2)

        # prefix-share accounting among LIVE rows only: dead rows get
        # per-row unique sentinel keys so they neither join a share class
        # nor inflate the saved-row count
        if cids_flat is not None:
            n_states = policy.constraints.n_states
            keys = (cids_flat.astype(jnp.int32)
                    * jnp.int32(n_states + 1) + nodes_flat)
        else:
            keys = nodes_flat.astype(jnp.int32)
        live_flat = jnp.repeat(live, M)
        keys = jnp.where(
            live_flat, keys, -1 - jnp.arange(N, dtype=jnp.int32))
        sk_keys = jnp.sort(keys)
        n_uni = 1 + jnp.sum((sk_keys[1:] != sk_keys[:-1]).astype(jnp.int32))
        n_live = jnp.sum(live_flat.astype(jnp.int32))
        hits = jnp.maximum(n_live - (n_uni - (N - n_live)), 0)
        return tokens, scores, nodes, sk, sv, share_acc + hits

    # ------------------------------------------------------------------
    # host-side plumbing
    # ------------------------------------------------------------------
    def _warmup(self):
        """Compile all four entry points before serving, so steady state is
        compile-free (admission/eviction/live-mask changes are traced-array
        values, never shapes)."""
        A = self.sched.prefill_chunk
        first, ks, vs = self._prefill_jit(
            self.params, jnp.zeros((A, self.S), jnp.int32))
        scratch = np.zeros((A, self.n_hist_pages), np.int32)  # NULL page
        self._k_pool, self._v_pool = self._commit_jit(
            self._k_pool, self._v_pool, ks, vs, jnp.asarray(scratch))
        (self._tokens, self._scores, self._nodes, self._first_lp,
         self._suffix_k, self._suffix_v) = self._admit_jit(
            self._tokens, self._scores, self._nodes, self._first_lp,
            self._suffix_k, self._suffix_v,
            jnp.zeros(self.n_slots, bool),
            jnp.zeros((self.n_slots, self.V), jnp.float32))
        self._run_step()
        jax.block_until_ready(self._tokens)
        self._warm = True

    def _run_step(self):
        (self._tokens, self._scores, self._nodes,
         self._suffix_k, self._suffix_v, self._share_acc) = self._step_jit(
            self.params, self.policy, self._k_pool, self._v_pool,
            jnp.asarray(self._page_table), self._suffix_k, self._suffix_v,
            self._tokens, self._scores, self._nodes, self._first_lp,
            jnp.asarray(self.sched.levels()),
            jnp.asarray(self.sched.live_mask()),
            jnp.asarray(self._cids), self._share_acc)

    def _install_current_store(self):
        """Adopt the registry front buffer (ServingEngine's swap contract:
        hot = leaves only, zero recompile; cold = treedef change, the step
        re-specializes exactly once)."""
        store, version = self.registry.current()
        cold = False
        if version != self._installed_version:
            before = jax.tree_util.tree_structure(self.policy)
            new_policy = self.policy.with_constraints(store)
            if not new_policy.supports_level_free:
                raise ValueError(
                    "registry store lost level-free support (rebuild the "
                    "registry with dense_d=0)")
            self.policy = new_policy
            cold = jax.tree_util.tree_structure(self.policy) != before
            if cold:
                self._m.cold.inc()
                record_policy(self._m.registry, self.policy, beams=self.M)
            else:
                self._m.hot.inc()
            self._installed_version = version
            self._m.store_version.set(version)
        return version, cold

    def _padded_prompt(self, request) -> np.ndarray:
        row = np.zeros(self.S, np.int32)
        n = min(request.prompt.shape[0], self.S)
        row[:n] = request.prompt[:n]
        return row

    def _alloc_pages(self) -> list[int]:
        try:
            return self.alloc.alloc(self.n_hist_pages)
        except (MemoryError, InjectedFault):
            # reclaim cached-but-unused prompt KV and retry once (an
            # injected kv.page_alloc fault models the same transient
            # exhaustion; alloc's fault point fires before any mutation,
            # so the free/referenced invariant is intact here)
            self.share.drop_all()
            return self.alloc.alloc(self.n_hist_pages)

    def _admit(self, queue, admissions, fresh):
        """Run the bounded prefill chunk, wire page ownership, and reset the
        admitted slots' device rows — all through the warmed jits.

        A request whose page allocation fails even after the share-table
        reclaim is NOT admitted and does NOT crash the step: it goes back on
        the queue with a bumped ``admit_attempts``, and once the retry
        budget is spent it is shed with reason ``kv_pages`` (degradation
        ladder, DESIGN.md §13).  Other admissions in the chunk proceed.
        """
        now = time.monotonic()
        admit_mask = np.zeros(self.n_slots, bool)
        new_first = np.zeros((self.n_slots, self.V), np.float32)
        if fresh:
            ok = []
            for slot, r in fresh:
                try:
                    pages = self._alloc_pages()
                except (MemoryError, InjectedFault):
                    if self.breaker is not None:
                        self.breaker.record_failure()
                    r.admit_attempts += 1
                    if r.admit_attempts >= self.admit_retry_budget:
                        queue.shed(r, "kv_pages")
                    else:
                        queue_push_back(queue, r)
                    continue
                self._slot_pages[slot] = tuple(pages)
                ok.append((slot, r))
            dropped = {id(r) for _, r in fresh} - {id(r) for _, r in ok}
            if dropped:
                admissions = [a for a in admissions if id(a[1]) not in dropped]
            fresh = ok
        if fresh:
            A = self.sched.prefill_chunk
            block = np.zeros((A, self.S), np.int32)
            page_ids = np.zeros((A, self.n_hist_pages), np.int32)  # pad->NULL
            for j, (slot, r) in enumerate(fresh):
                block[j] = self._padded_prompt(r)
                page_ids[j] = self._slot_pages[slot]
            first_dev, ks, vs = self._prefill_jit(
                self.params, jnp.asarray(block))
            self._k_pool, self._v_pool = self._commit_jit(
                self._k_pool, self._v_pool, ks, vs, jnp.asarray(page_ids))
            first_host = np.asarray(first_dev)  # (A, V) float32, exact
            for j, (slot, r) in enumerate(fresh):
                new_first[slot] = first_host[j]
                self.share.insert(
                    block[j], self._slot_pages[slot], first_host[j])
        num_sets = self.policy.num_sets
        for slot, r, hit in admissions:
            limit = num_sets if num_sets is not None else 1
            if not 0 <= r.constraint_id < limit:
                raise ValueError(
                    f"request {r.rid}: constraint_id {r.constraint_id} "
                    f"outside [0, {limit})")
            if hit:
                entry = self.share.lookup(self._padded_prompt(r))
                if entry is None:
                    # donor entry vanished between planning and admission
                    # (drop_all reclaim under page pressure): requeue as a
                    # fresh prefill for the next step instead of crashing
                    queue_push_back(queue, r)
                    continue
                pages, first_row = entry
                self._slot_pages[slot] = pages
                new_first[slot] = first_row
                self._share_hits.inc(kind="prompt")
            self._page_table[slot, :] = self._slot_pages[slot]
            self._cids[slot] = r.constraint_id
            if self.sched.slots[slot].served > 0:
                self._slot_reuse.inc()
            self._admissions.inc(lane=str(r.constraint_id))
            admit_mask[slot] = True
            self.sched.admit(slot, r, now)
        (self._tokens, self._scores, self._nodes, self._first_lp,
         self._suffix_k, self._suffix_v) = self._admit_jit(
            self._tokens, self._scores, self._nodes, self._first_lp,
            self._suffix_k, self._suffix_v, jnp.asarray(admit_mask),
            jnp.asarray(new_first))

    def _flush_share_hits(self):
        total = int(np.asarray(self._share_acc))
        if total > self._share_flushed:
            self._share_hits.inc(
                total - self._share_flushed, kind="mask_row")
            self._share_flushed = total

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def serve(self, queue, max_steps: int = 50_000) -> dict:
        """Drain the queue; returns ``{rid: {sids, scores, constraint_id,
        store_version, latency_s, queue_s}}`` — the ServingEngine schema —
        plus ``{rid: {"error": ...}}`` for deadline-shed requests."""
        results: dict[int, dict] = {}
        sched = self.sched
        steps = 0
        self._m.record_shed(queue, results)  # submit-time refusals
        while (len(queue) or sched.n_live) and steps < max_steps:
            version, cold = (self._install_current_store()
                             if self.registry is not None else (None, False))
            sched.shed_expired(queue)  # sweeps ALL lanes, stages into queue
            admissions, _fresh = sched.plan_admissions(
                queue, lambda r: self.share.contains(self._padded_prompt(r)))
            if admissions or _fresh:
                self._admit(queue, admissions, _fresh)
            self._m.record_shed(queue, results)
            self._m.sample_queue(queue)
            if sched.n_live == 0:
                if not len(queue):
                    break
                continue

            c0 = compile_events()
            t0 = time.monotonic()
            try:
                fire("decode.slow_step")  # delay => slow step; error => retry
                with annotate("continuous_step"):
                    self._run_step()
                    jax.block_until_ready(self._tokens)
            except InjectedFault:
                # the fault fired before the jit mutated any engine state,
                # so retrying the step next iteration is bit-identical; the
                # failed attempt still burns a step of the budget so an
                # "always" error fault cannot spin forever
                if self.breaker is not None:
                    self.breaker.record_failure()
                steps += 1
                continue
            except Exception:
                if self.breaker is not None:
                    self.breaker.record_failure()
                raise
            if self.breaker is not None:
                self.breaker.record_success()
            dt = time.monotonic() - t0
            steps += 1
            sched.advance()
            self._m.record_batch(
                n_active=sched.n_live, slots=self.n_slots, steps=1, dt=dt,
                compiles=compile_events() - c0, expected=cold or not self._warm)

            done = sched.completed()
            if done:
                toks = np.asarray(self._tokens)
                scs = np.asarray(self._scores)
                t_done = time.monotonic()
                for i in done:
                    st = sched.evict(i)
                    r = st.request
                    self.alloc.release(self._slot_pages[i])
                    self._slot_pages[i] = ()
                    self._page_table[i, :] = 0
                    results[r.rid] = {
                        "sids": toks[i],
                        "scores": scs[i],
                        "constraint_id": r.constraint_id,
                        "store_version": self._installed_version,
                        **self._m.record_request(
                            r, st.t_admit, t_done, t_first=st.t_first,
                            n_out=self.L),
                    }
            self._m.occupancy.set(sched.n_live / max(self.n_slots, 1))
            self._page_util.set(self.alloc.utilization())
        self._m.record_shed(queue, results)
        self._m.sample_queue(queue)
        self._flush_share_hits()
        return results
