"""Serving launcher: STATIC-constrained generative retrieval.

    PYTHONPATH=src python -m repro.launch.serve --constraints 20000 \
        --batch 4 --beam 8
    PYTHONPATH=src python -m repro.launch.serve --model static-gr

``--model`` picks the decoder: ``gr-coldstart`` (the reduced 4-layer
cold-start decoder, default) or a generative-retrieval config of
:mod:`repro.configs` by name — ``static-gr`` is the paper's 26-layer x 3072
decoder, served at its own SID geometry (V=2048, L=8, M=70, 2 requests per
chip, ``dense_d=2``) and history length (256).  Weights are random, made
from ``--seed`` on the device.  The builders below (:func:`decoder`,
:func:`build_params`, :func:`build_index`, :func:`build_policy`,
:func:`build_retriever`, :func:`build_engine`) are what ``chip_smoke.py``
calls too.

Which engine when (``--engine``):

===========  ==============================================================
batch        Sequence-boundary ``ServingEngine`` (default).  One fused jit
             per batch — the lowest per-request dispatch overhead.  Best
             for offline/bulk retrieval and uniform prompt lengths, where
             slots finishing together wastes nothing.  Degradation: a
             failed decode fails the whole batch (every request in it gets
             a ``decode_fault`` error result); expired requests shed at
             enqueue and again before each batch forms.
spmd         ``SpmdServingEngine`` over a (data, model) mesh.  Same
             sequence-boundary semantics scaled across devices; pick it
             when one host's devices must serve a single logical batch.
             Degradation: identical to ``batch`` (whole-batch blast
             radius — one mesh, one program).
continuous   Step-boundary ``ContinuousServingEngine`` (DESIGN.md §10).
             Paged history KV + chunked prefill + trie-prefix sharing:
             slots refill the moment a request completes, repeat prompts
             skip their prefill, and per-request TTFT is L steps from
             admission instead of a whole batch drain.  Best under live
             mixed traffic (hot prompts, ragged arrivals, SLO deadlines);
             needs a ``dense_d=0`` constraint index.  Degradation: a
             failed step retries bit-identically next iteration (state is
             only mutated on success); KV exhaustion drops the share
             table, then retries admission, then sheds ``kv_pages``.
===========  ==============================================================

All three engines share one reliability contract (DESIGN.md §13): the
degradation ladder is retry -> serve-stale -> shed at admission, and a
request is NEVER decoded unconstrained as a fallback.  ``--fault-schedule``
arms the deterministic fault injector for chaos drills; ``--health-port-file``
exposes ``/healthz``, ``/readyz`` and ``/livez`` next to ``/metrics``.

Per-request results are bit-identical across all three engines (fuzz-
asserted in tests/test_continuous.py and tests/test_spmd_serving.py).

The old demo modes (``--num-constraint-sets`` mixed-tenant batches and the
``--refresh-interval`` async-churn loop) moved to the scenario registry —
they are the ``multi_constraint`` and ``refresh_churn`` scenarios::

    PYTHONPATH=src python -m repro.launch.run_scenario \
        --scenario multi_constraint --smoke
    PYTHONPATH=src python -m repro.launch.run_scenario \
        --scenario refresh_churn --smoke --set serve.refresh_cycles=4
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import numpy as np

from repro.configs import get_bundle, static_gr
from repro.configs.base import TransformerConfig
from repro.core import TransitionMatrix
from repro.core.vntk import NEG_INF
from repro.decoding import DecodePolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer
from repro.observability import MetricsRegistry, start_http_server
from repro.reliability import CircuitBreaker, FaultInjector, HealthMonitor, install
from repro.scenarios import gr_model_config
from repro.serving.engine import RequestQueue, ServingEngine
from repro.serving.generative_retrieval import GenerativeRetriever

logger = logging.getLogger("repro.launch.serve")

TOY_MODEL = "gr-coldstart"
MODELS = (TOY_MODEL, "static-gr")


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The SID geometry and request shape a decoder is served at."""

    vocab: int  # SID token cardinality V
    sid_length: int  # L
    beam: int  # M
    batch: int  # requests per batch (per chip)
    history: int  # prompt tokens per request
    dense_d: int  # bit-packed dense levels of the constraint index
    constraints: int  # constraint SIDs served by default


def decoder(model: str = TOY_MODEL,
            vocab: int | None = None) -> tuple[TransformerConfig, Geometry]:
    """Decoder config and serving geometry for ``--model``."""
    if model == TOY_MODEL:
        v = vocab or 256
        return gr_model_config(v), Geometry(v, 4, 8, 4, 16, 2, 20_000)
    bundle = get_bundle(model)
    if bundle is not static_gr.BUNDLE:
        raise ValueError(
            f"{model!r} is not a generative-retrieval decoder; "
            f"choose one of {MODELS}")
    shape = next(s for s in bundle.shapes if s.kind == "serve_constrained")
    v = vocab or static_gr.SID_VOCAB
    if v + 2 > bundle.config.vocab_size:
        raise ValueError(f"SID vocab {v} exceeds the decoder's "
                         f"{bundle.config.vocab_size - 2} SID tokens")
    # 1M constraint SIDs: the paper's 20M slab does not fit one 16 GB chip
    # beside the weights and batch 2's beam cache
    return bundle.config, Geometry(
        v, shape.sid_length, shape.beam_size, static_gr.BATCH_PER_CHIP,
        shape.history_len, static_gr.DENSE_D, 1_000_000)


def build_params(cfg: TransformerConfig, seed: int = 0, sharding=None):
    """Random decoder weights from ``seed``, made on the device by one
    jitted program (eager init would stage every stacked layer's float32
    draw on the device before casting it).  ``sharding`` places the result
    (e.g. replicated over a mesh)."""
    init = jax.jit(transformer.init_params, static_argnums=0,
                   out_shardings=sharding)
    return init(cfg, jax.random.key(seed))


def constraint_sids(n: int, geo: Geometry, seed: int = 0) -> np.ndarray:
    """``n`` random constraint SIDs over the geometry's vocab and length."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, geo.vocab, size=(n, geo.sid_length))


def request_histories(n: int, geo: Geometry, seed: int = 0,
                      distinct: int | None = None) -> np.ndarray:
    """``n`` user histories of ``geo.history`` tokens; with ``distinct``
    they cycle through that many prompts (repeat prompts exercise prefix
    sharing in the continuous engine)."""
    rng = np.random.default_rng(seed + 1)
    pool = rng.integers(0, geo.vocab, (distinct or n, geo.history))
    return pool[np.arange(n) % len(pool)].astype(np.int32)


def build_index(sids: np.ndarray, geo: Geometry,
                dense_d: int | None = None) -> TransitionMatrix:
    """The STATIC constraint index (CSR trie + dense levels) over ``sids``."""
    d = geo.dense_d if dense_d is None else dense_d
    return TransitionMatrix.from_sids(sids, geo.vocab, dense_d=d)


def build_policy(tm: TransitionMatrix | None, *, impl: str = "xla",
                 fused: bool = False, topk: bool = True) -> DecodePolicy:
    """STATIC decode policy over ``tm`` (unconstrained when ``tm`` is None)."""
    if tm is None:
        return DecodePolicy.unconstrained()
    return DecodePolicy.static(tm, impl=impl, fused=fused, topk=topk)


def build_retriever(params, cfg: TransformerConfig, policy, geo: Geometry, *,
                    mesh=None, rows: str = "replicated"):
    """Single-device retriever, or the SPMD one over ``mesh``."""
    if mesh is None:
        return GenerativeRetriever(params, cfg, policy, geo.sid_length,
                                   geo.vocab, beam_size=geo.beam)
    from repro.serving.spmd_engine import SpmdRetriever

    return SpmdRetriever(params, cfg, policy, geo.sid_length, geo.vocab,
                         beam_size=geo.beam, mesh=mesh, rows=rows)


def build_engine(kind: str, retriever, geo: Geometry, *, metrics=None,
                 breaker=None, prefill_chunk: int | None = None,
                 share_capacity: int = 64):
    """The serving engine ``kind`` (batch | spmd | continuous) over
    ``retriever``, with ``geo.batch`` slots and ``geo.history``-token
    prompts.  The continuous engine prefills up to ``prefill_chunk``
    prompts per step (default: half the slots)."""
    if kind == "continuous":
        from repro.serving.continuous import ContinuousServingEngine

        return ContinuousServingEngine(
            retriever, slots=geo.batch, prompt_width=geo.history,
            prefill_chunk=prefill_chunk or max(geo.batch // 2, 1),
            share_capacity=share_capacity, metrics=metrics, breaker=breaker)
    if kind == "spmd":
        from repro.serving.spmd_engine import SpmdServingEngine

        return SpmdServingEngine(retriever, slots=geo.batch,
                                 prompt_width=geo.history, metrics=metrics,
                                 breaker=breaker)
    if kind != "batch":
        raise ValueError(f"unknown engine {kind!r}")
    return ServingEngine(retriever.params, retriever.cfg, geo.batch,
                         2 * geo.history, retriever=retriever,
                         metrics=metrics, breaker=breaker)


def compliance(beams: np.ndarray, scores: np.ndarray, valid: set) -> tuple:
    """``(beams checked, beams outside the constraint set)`` over every
    beam that carries a finite score."""
    live = np.asarray(scores) > NEG_INF / 2
    emitted = [tuple(b) for b in np.asarray(beams)[live].tolist()]
    return len(emitted), sum(b not in valid for b in emitted)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=MODELS, default=TOY_MODEL,
                    help="decoder: the reduced cold-start decoder or the "
                         "paper's static-gr-3b at its own SID geometry")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, constraint SIDs and requests")
    ap.add_argument("--constraints", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--sid-length", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--beam", type=int, default=None)
    ap.add_argument("--requests", type=int, default=5,
                    help="batches' worth of requests to serve")
    ap.add_argument("--unconstrained", action="store_true")
    ap.add_argument("--impl", choices=["xla", "pallas"], default="xla",
                    help="VNTK formulation for sparse decode levels")
    ap.add_argument("--fused", action="store_true",
                    help="fuse Phase-1 log-softmax into the masking kernel")
    ap.add_argument("--no-topk", action="store_true",
                    help="disable candidate-compressed decoding and use the "
                         "vocab-aligned dense advance at every level "
                         "(DESIGN.md §8; bit-identical, for A/B timing)")
    ap.add_argument("--engine", choices=["batch", "spmd", "continuous"],
                    default="batch",
                    help="serving engine (see the module docstring's "
                         "which-engine-when table)")
    ap.add_argument("--spmd", action="store_true",
                    help="alias for --engine spmd: serve SPMD over a (data, "
                         "model) mesh spanning every visible device "
                         "(simulate a multi-chip host with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--spmd-rows", choices=["replicated", "model"],
                    default="replicated",
                    help="CSR placement under --spmd: replicate the trie "
                         "(paper §A.3) or row-shard edges along the model "
                         "axis with a one-hop gather (DESIGN.md §6)")
    ap.add_argument("--log-level", default="INFO",
                    choices=["DEBUG", "INFO", "WARNING", "ERROR"],
                    help="stdlib logging level for the repro.* loggers")
    ap.add_argument("--metrics-json", metavar="PATH", default=None,
                    help="append a JSON-lines MetricsRegistry snapshot to "
                         "PATH on exit (DESIGN.md §9)")
    ap.add_argument("--metrics-port-file", metavar="PATH", default=None,
                    help="serve Prometheus text at /metrics on an ephemeral "
                         "localhost port and write the bound port to PATH")
    ap.add_argument("--fault-schedule", metavar="JSON", default=None,
                    help="arm the deterministic fault injector (DESIGN.md "
                         "§13): inline JSON or a path to a JSON file of the "
                         "form {\"seed\": 0, \"faults\": [{\"point\": ..., "
                         "\"mode\": ...}, ...]}")
    ap.add_argument("--health-port-file", metavar="PATH", default=None,
                    help="serve /healthz, /readyz and /livez (plus /metrics) "
                         "on an ephemeral localhost port and write the bound "
                         "port to PATH; readiness reflects the serving "
                         "circuit breaker")
    args = ap.parse_args()

    logging.basicConfig(
        level=getattr(logging, args.log_level),
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    enable_compile_cache()
    metrics = MetricsRegistry()

    injector = None
    if args.fault_schedule:
        injector = FaultInjector.from_json(args.fault_schedule)
        install(injector)
        logger.info("fault injection armed (seed=%d)", injector.seed)

    breaker = CircuitBreaker(name="serve", metrics=metrics)
    if args.metrics_port_file or args.health_port_file:
        health = None
        if args.health_port_file:
            health = HealthMonitor(breaker=breaker, metrics=metrics)
        _, port = start_http_server(metrics, port=0, health=health)
        for path in (args.metrics_port_file, args.health_port_file):
            if path:
                with open(path, "w") as f:
                    f.write(str(port))
        logger.info("metrics: http://127.0.0.1:%d/metrics", port)
        if health is not None:
            logger.info("health:  http://127.0.0.1:%d/healthz", port)

    if args.spmd:
        args.engine = "spmd"

    cfg, geo = decoder(args.model, args.vocab)
    geo = dataclasses.replace(geo, **{
        k: v for k, v in dict(
            sid_length=args.sid_length, batch=args.batch, beam=args.beam,
            constraints=args.constraints).items() if v is not None})
    logger.info("decoder %s (%d layers x %d), V=%d L=%d M=%d, batch %d, "
                "history %d", cfg.name, cfg.n_layers, cfg.d_model, geo.vocab,
                geo.sid_length, geo.beam, geo.batch, geo.history)
    params = build_params(cfg, args.seed)
    sids = constraint_sids(geo.constraints, geo, args.seed)
    tm = None
    if not args.unconstrained or args.engine == "continuous":
        t0 = time.time()
        # the continuous engine's level-free masking needs the all-sparse
        # index (node ids globally unique across levels)
        tm = build_index(sids, geo,
                         dense_d=0 if args.engine == "continuous" else None)
        logger.info("constraint index: %d states (%.2fs build)",
                    tm.n_states, time.time() - t0)
    policy = build_policy(tm, impl=args.impl, fused=args.fused,
                          topk=not args.no_topk)
    logger.info("policy %s", policy.describe())

    mesh = None
    if args.engine == "spmd":
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(model=2 if args.spmd_rows == "model" else 1)
        logger.info("SPMD mesh: %s over %d device(s), CSR rows=%s",
                    dict(mesh.shape), mesh.devices.size, args.spmd_rows)
    r = build_retriever(params, cfg, policy, geo, mesh=mesh,
                        rows=args.spmd_rows)
    engine = build_engine(args.engine, r, geo, metrics=metrics,
                          breaker=breaker)

    n_req = args.requests * geo.batch
    queue = RequestQueue()
    hist = request_histories(n_req, geo, args.seed,
                             distinct=max(n_req // 3, 1))
    rids = [queue.submit(h, geo.sid_length) for h in hist]
    t0 = time.time()
    results = engine.serve(queue)
    wall = time.time() - t0
    done = [i for i in rids if "latency_s" in results[i]]
    if injector is not None and len(done) < n_req:
        logger.info("degraded under faults: %d/%d completed (%s)",
                    len(done), n_req,
                    {results[i].get("reason", "?")
                     for i in rids if i not in set(done)})
    lat = np.array([results[i]["latency_s"] for i in done] or [float("nan")])
    logger.info(
        "%s: %d requests in %.1f ms (p50 %.1f ms, p99 %.1f ms)%s",
        args.engine, len(done), wall * 1e3,
        float(np.quantile(lat, 0.5)) * 1e3,
        float(np.quantile(lat, 0.99)) * 1e3,
        "" if args.engine == "continuous"
        else "; the first batch includes compilation")
    if tm is not None and done:
        valid = {tuple(x) for x in sids.tolist()}
        checked, bad = compliance(
            np.stack([results[i]["sids"] for i in done]),
            np.stack([results[i]["scores"] for i in done]), valid)
        logger.info("compliance: %d/%d beams inside the constraint set",
                    checked - bad, checked)
    if args.engine == "continuous":
        hits = engine.metrics.counter("serving_prefix_share_hits_total")
        logger.info(
            "slot reuse %d, share hits prompt=%d mask_row=%d",
            int(engine.metrics.counter("serving_slot_reuse_total").total()),
            int(hits.value(kind="prompt")), int(hits.value(kind="mask_row")))
    if done:
        logger.info("top-1 SIDs (request %d): %s", done[0],
                    results[done[0]]["sids"][0].tolist())
    if injector is not None:
        logger.info("injected faults fired: %d", injector.n_fires())
    if args.metrics_json:
        metrics.write_snapshot(args.metrics_json)
        logger.info("metrics snapshot appended to %s", args.metrics_json)


if __name__ == "__main__":
    main()
