"""SPMD constrained serving: mesh-parallel retrieval + continuous batching.

``SpmdRetriever`` is the :class:`~repro.serving.generative_retrieval
.GenerativeRetriever` made SPMD over a ``Mesh`` from
:mod:`repro.launch.mesh`: one jitted ``shard_map`` step runs prefill + the L
constrained beam steps with the *batch* axis split across the mesh's data
axes (rows are independent in Algorithm 1, so sharded decoding is
bit-identical to single-device — asserted in
``tests/test_differential_fuzz.py``).  The DecodePolicy rides in as a pytree
argument with per-backend placements from its ``shardings(mesh)`` hook:
replicated by default (paper §A.3), or CSR-row-sharded along ``model`` with
``rows="model"`` for tries that outgrow one device (DESIGN.md §6).
Candidate-compressed levels (DESIGN.md §8) compose with both placements:
under the default replicated rows the per-beam top-C lists and the
``(B, M*C)`` reduce are dp-local, and under ``rows="model"`` the
``RowShardedStatic`` wrapper runs the shard-local top-C + one-hop psum
merge of ``vntk_row_sharded_topk`` (DESIGN.md §11), still bit-identical.

``SpmdServingEngine`` replaces the one-request-at-a-time admit loop of
``ServingEngine._serve_retrieval`` with continuous data-parallel batching:

  * a **global batch of fixed ``slots``** (padded up to a multiple of the
    data-parallel ways) — static shapes, so occupancy changes never
    recompile;
  * per-row ``constraint_ids`` and an ``active`` mask ride as jit
    *arguments*: free slots are inactive rows whose scores come back
    ``NEG_INF``, not separate (shape-specialized) executables;
  * admission is round-robin-fair across constraint slots
    (:class:`~repro.serving.engine.RequestQueue` lanes), so one tenant's
    burst cannot monopolize the shared batch;
  * the registry's current store is re-read each batch and installed via
    ``retriever.set_constraints`` — a hot-swap changes only pytree leaves,
    and the mesh-compiled executable is reused with **zero recompilation**
    (asserted in ``tests/test_spmd_serving.py``).
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.vntk import NEG_INF
from repro.decoding.backends import CpuTrieBackend
from repro.distributed.constraint_sharding import (
    pad_policy_rows,
    policy_pspecs,
    to_row_sharded,
)
from repro.distributed.sharding import dp_axes, dp_size
from repro.observability import (
    MetricsRegistry,
    annotate,
    compile_events,
    record_policy,
)
from repro.reliability.faults import InjectedFault, fire
from repro.serving.engine import _EngineMetrics
from repro.serving.generative_retrieval import GenerativeRetriever

__all__ = ["SpmdRetriever", "SpmdServingEngine"]


class SpmdRetriever(GenerativeRetriever):
    """Mesh-parallel constrained retrieval (one shard_map'd jitted step).

    Same constructor surface as :class:`GenerativeRetriever` plus ``mesh``
    and ``rows`` (the CSR placement, see
    :meth:`~repro.decoding.ConstraintBackend.shardings`).  ``retrieve`` pads
    the request batch to a multiple of the mesh's data-parallel ways with
    inactive rows, so any caller batch size maps onto the static SPMD shape.
    """

    def __init__(self, params, cfg, policy=None, sid_length=None,
                 sid_vocab=None, beam_size: int = 20, *, mesh,
                 rows: str = "replicated"):
        super().__init__(params, cfg, policy, sid_length, sid_vocab,
                         beam_size)
        if rows not in ("replicated", "model"):
            raise ValueError(
                f"rows must be 'replicated' or 'model', got {rows!r}"
            )
        for b in self.policy.backends:
            if isinstance(b, CpuTrieBackend):
                raise TypeError(
                    "CpuTrieBackend masks through a host io_callback and "
                    "cannot run inside the SPMD step; use a device-resident "
                    "backend (STATIC, stacked, PPV, bitmap)"
                )
        self.mesh = mesh
        self.rows = rows
        self._dp = dp_axes(mesh)
        self._dp_size = dp_size(mesh)
        if rows == "model":
            # validate early (pallas/fused rejection) + pad CSR rows so the
            # edge slab divides the model axis — deterministic shapes, so
            # re-padding after every hot-swap never recompiles
            to_row_sharded(self.policy)
            self.policy = pad_policy_rows(self.policy, mesh.shape["model"])
        self._build_spmd_step()

    def _build_spmd_step(self) -> None:
        """(Re)build the shard_map'd step for the CURRENT policy structure.

        shard_map in_specs carry the policy's treedef (static metadata
        included), so they are rebuilt whenever the structure changes; the
        jit cache itself still keys on the arguments, so envelope-stable
        hot-swaps (same treedef, new leaves) reuse the old executable.
        """
        self._pol_struct = jax.tree_util.tree_structure(self.policy)
        specs = policy_pspecs(self.policy, self.mesh, rows=self.rows)
        dp = self._dp

        ms = self.mesh.shape["model"] if self.rows == "model" else 1

        def _spmd_impl(params, history, policy, cids, active):
            if self.rows == "model":
                policy = to_row_sharded(policy, n_shards=ms)
            ids = cids if policy.requires_constraint_ids else None
            tokens, scores, _ = self._retrieve_impl(params, history, policy,
                                                    ids)
            # inactive (padding / free-slot) rows: parked at NEG_INF so no
            # consumer can mistake them for results
            scores = jnp.where(active[:, None], scores, NEG_INF)
            return tokens, scores

        self._spmd_jit = jax.jit(jax.shard_map(
            _spmd_impl, mesh=self.mesh,
            in_specs=(P(), P(dp, None), specs, P(dp), P(dp)),
            out_specs=(P(dp, None, None), P(dp, None)),
        ))

    def compile_step(self, batch: int, prompt_width: int):
        """Ahead-of-time compile of the mesh step for a global ``batch`` (a
        multiple of the data-parallel ways); ``memory_analysis()`` of the
        result is per device."""
        def rows(dtype):
            return jax.ShapeDtypeStruct((batch,), dtype)

        hist = jax.ShapeDtypeStruct((batch, prompt_width), jnp.int32)
        return self._spmd_jit.lower(self.params, hist, self.policy,
                                    rows(jnp.int32), rows(jnp.bool_)
                                    ).compile()

    # -- hot-swap ------------------------------------------------------------
    def set_constraints(self, obj) -> bool:
        """Registry swap under the mesh; returns True iff it was cold.

        A hot swap (envelope-stable, the ConstraintRegistry refresh path)
        changes only leaf values: the swapped-in matrix/store is re-padded
        to the deterministic row-sharded envelope, so neither shapes,
        static metadata, nor the spec tree move — the mesh executable is
        reused as-is.  A cold swap (regrown envelope, DESIGN.md §7 — or a
        raw TransitionMatrix with different state counts) changes static
        metadata: the shard_map step is rebuilt and recompiles exactly
        once, matching the single-device retriever's retrace-on-metadata-
        change behavior.
        """
        self.policy = self.policy.with_constraints(obj)
        if self.rows == "model":
            self.policy = pad_policy_rows(
                self.policy, self.mesh.shape["model"]
            )
        if jax.tree_util.tree_structure(self.policy) != self._pol_struct:
            self._build_spmd_step()
            return True
        return False

    # -- serving -------------------------------------------------------------
    def retrieve(self, history: np.ndarray,
                 constraint_ids: Optional[np.ndarray] = None,
                 active_mask: Optional[np.ndarray] = None):
        """history (B, S) -> (sids (B, M, L), scores (B, M)), SPMD.

        ``active_mask`` (B,) bool marks real rows (default: all).  The batch
        is padded to a multiple of the data-parallel ways with inactive
        rows; padding is sliced off the outputs, and inactive rows return
        ``NEG_INF`` scores.
        """
        hist = np.asarray(history, np.int32)
        B = hist.shape[0]
        n = self._dp_size
        Bp = -(-B // n) * n
        num_sets = self.num_sets
        cids = np.zeros(Bp, np.int32)
        if constraint_ids is not None:
            cids_in = np.asarray(constraint_ids, np.int32)
            if num_sets is None:
                raise ValueError(
                    "constraint_ids requires a stacked ConstraintStore policy"
                )
            if cids_in.min() < 0 or cids_in.max() >= num_sets:
                raise ValueError(
                    f"constraint_ids must be in [0, {num_sets}), got "
                    f"range [{cids_in.min()}, {cids_in.max()}]"
                )
            cids[:B] = cids_in
        elif num_sets is not None:
            raise ValueError(
                "stacked ConstraintStore policies need per-row constraint_ids"
            )
        active = np.zeros(Bp, bool)
        active[:B] = True if active_mask is None else \
            np.asarray(active_mask, bool)
        if Bp != B:
            hist = np.concatenate(
                [hist, np.zeros((Bp - B, hist.shape[1]), np.int32)]
            )
        tokens, scores = self._spmd_jit(
            self.params, jnp.asarray(hist), self.policy,
            jnp.asarray(cids), jnp.asarray(active),
        )
        return np.asarray(tokens)[:B], np.asarray(scores)[:B]


class SpmdServingEngine:
    """Continuous data-parallel batched serving over a mesh.

    Drains a :class:`~repro.serving.engine.RequestQueue` through an
    :class:`SpmdRetriever` in fixed-``slots`` global batches.  Result dict
    matches ``ServingEngine.serve``'s retrieval mode:
    ``{rid: {sids, scores, constraint_id, store_version}}``.
    """

    def __init__(self, retriever: SpmdRetriever, *, registry=None,
                 slots: Optional[int] = None, prompt_width: int = 8,
                 metrics: Optional[MetricsRegistry] = None, breaker=None):
        n = retriever._dp_size
        slots = slots if slots is not None else max(2 * n, 4)
        self.slots = -(-slots // n) * n  # static-shape padding rule (§6)
        self.retriever = retriever
        self.registry = registry
        self.breaker = breaker
        self.prompt_width = prompt_width
        self._installed_version = None
        self._m = _EngineMetrics(metrics)
        self._served_batches = 0
        record_policy(self._m.registry, retriever.policy, beams=retriever.M)

    @property
    def metrics(self) -> MetricsRegistry:
        return self._m.registry

    @property
    def cold_swaps(self) -> int:
        """Envelope regrowths routed through this engine (a property over
        the ``serving_cold_swaps_total`` counter, so pre-telemetry callers
        and tests keep working unchanged)."""
        return int(self._m.cold.total())

    def serve(self, queue, max_batches: int = 10_000) -> dict:
        results: dict[int, dict] = {}
        S = self.prompt_width
        batches = 0
        self._m.record_shed(queue, results)  # submit-time refusals
        while len(queue) and batches < max_batches:
            batches += 1
            t_admit = time.monotonic()
            queue.shed_expired()
            batch = queue.pop_batch(self.slots)  # round-robin fair admit
            self._m.record_shed(queue, results)
            self._m.sample_queue(queue)
            if not batch:
                continue
            version, cold = None, False
            if self.registry is not None:
                store, version = self.registry.current()
                if version != self._installed_version:
                    cold = self.retriever.set_constraints(store)
                    if cold:
                        self._m.cold.inc()  # regrown envelope: one rebuild
                        record_policy(self._m.registry,
                                      self.retriever.policy,
                                      beams=self.retriever.M)
                    else:
                        self._m.hot.inc()
                    self._installed_version = version
                    self._m.store_version.set(version)
            num_sets = self.retriever.num_sets
            limit = num_sets if num_sets is not None else 1
            hist = np.zeros((self.slots, S), np.int32)
            cids = np.zeros(self.slots, np.int32)
            active = np.zeros(self.slots, bool)
            for i, r in enumerate(batch):
                if not 0 <= r.constraint_id < limit:
                    # reject just this request (it raced a registry shrink
                    # or is plain bad input) — killing the whole drain would
                    # discard every already-served and already-popped row
                    results[r.rid] = {
                        "error": f"constraint_id {r.constraint_id} outside "
                                 f"[0, {limit})",
                        "constraint_id": r.constraint_id,
                        "store_version": version,
                    }
                    self._m.rejected.inc(lane=str(r.constraint_id))
                    continue
                hist[i, : min(r.prompt.shape[0], S)] = r.prompt[:S]
                cids[i] = r.constraint_id
                active[i] = True
            c0 = compile_events()
            try:
                fire("decode.slow_step")  # delay => slow batch; error => fail
                with annotate("spmd_serve_batch"):
                    beams, scores = self.retriever.retrieve(
                        hist,
                        constraint_ids=cids if num_sets is not None else None,
                        active_mask=active,
                    )
            except InjectedFault:
                # degrade to failed requests, not a crashed drain loop (and
                # never to unconstrained decoding) — DESIGN.md §13
                if self.breaker is not None:
                    self.breaker.record_failure()
                for r in batch:
                    if r.rid in results:
                        continue
                    self._m.rejected.inc(lane=str(r.constraint_id))
                    self._m.shed.inc(reason="decode_fault")
                    results[r.rid] = {
                        "error": "decode step failed (injected fault)",
                        "reason": "decode_fault",
                        "constraint_id": r.constraint_id,
                    }
                continue
            except Exception:
                if self.breaker is not None:
                    self.breaker.record_failure()
                raise
            if self.breaker is not None:
                self.breaker.record_success()
            t_done = time.monotonic()
            self._m.record_batch(
                n_active=int(active.sum()), slots=self.slots,
                steps=self.retriever.L, dt=t_done - t_admit,
                compiles=compile_events() - c0,
                expected=cold or self._served_batches == 0,
            )
            self._served_batches += 1
            for i, r in enumerate(batch):
                if r.rid in results:
                    continue  # rejected above
                results[r.rid] = {
                    "sids": beams[i],
                    "scores": scores[i],
                    "constraint_id": r.constraint_id,
                    "store_version": version,
                    **self._m.record_request(r, t_admit, t_done,
                                             n_out=self.retriever.L),
                }
        self._m.record_shed(queue, results)
        self._m.sample_queue(queue)
        return results
