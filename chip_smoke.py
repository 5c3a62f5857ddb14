#!/usr/bin/env python3
"""Bring-up smoke test on the chip.

Serves STATIC-constrained generative retrieval with the paper's
``static-gr-3b`` decoder at full width (26 layers x 3072, GQA 24/8, d_ff
12288, bf16; random weights from ``--seed``) and the paper's SID geometry
(V=2048, L=8, M=70, a history of 256, ``dense_d=2``), through the same
builders as ``python -m repro.launch.serve --model static-gr``::

    python chip_smoke.py                # one chip: phases A, B and C
    python chip_smoke.py --four-chips   # four chips: the SPMD phase only

Phase A   ``ServingEngine`` on the XLA constraint path: every emitted beam
          is a constraint SID.
Phase B   the same requests with the Pallas top-C kernel: the compiled step
          holds a ``tpu_custom_call``, and beams and scores equal phase A's
          exactly.
Phase C   ``ContinuousServingEngine`` on a ``dense_d=0`` index of the same
          SIDs: equal to ``GenerativeRetriever`` on that index, and every
          beam is a constraint SID.
--four-chips  ``SpmdRetriever`` on a (data=4, model=1) mesh with the trie
          replicated on every chip (paper §A.3): each chip's slice of the
          beams and scores equals ``GenerativeRetriever`` on one device for
          the same histories.

Each cut from the paper's deployment is printed before the phases run.
Every number printed names the device it was measured on.  Unless JAX's
first device is a TPU the script exits non-zero without a result line; so
it does after any failed check or error.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import static_gr  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.engine import RequestQueue  # noqa: E402

GIB = 2 ** 30
HEADROOM = 128 * 2 ** 20  # device bytes left free beyond a step's own need
BATCHES = 3  # batches served per phase after the warm-up


class Failed(RuntimeError):
    """The smoke cannot go on."""


FAILED: list[str] = []


def check(cond, msg: str) -> bool:
    """Record a failed check; the run goes on so that one chip run reports
    every phase, and ends with a non-zero exit and no result line."""
    if not cond:
        FAILED.append(msg)
        print(f"CHECK FAILED: {msg}", file=sys.stderr, flush=True)
    return bool(cond)


class CompileClock:
    """Backend compiles (count and seconds) from ``jax.monitoring``."""

    def __init__(self):
        self.count, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if "backend_compile" in name:
            self.count += 1
            self.secs += secs

    def mark(self):
        return self.count, self.secs

    def since(self, mark):
        return self.count - mark[0], self.secs - mark[1]


def say(dev, msg: str) -> None:
    print(f"[{dev.device_kind}] {msg}", flush=True)


def pick_batch(dev, compile_step, paper_batch, log) -> int:
    """The paper's requests per chip if ``compile_step(b)`` compiles a step
    that fits the device memory free now, else 1."""
    for b in sorted({paper_batch, 1}, reverse=True):
        try:
            compiled = compile_step(b)
        except Exception as e:  # the compiler refuses what cannot fit
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            log(f"batch {b} per chip: the compiler refused it "
                f"({str(e)[:160]})")
            continue
        ma = compiled.memory_analysis()
        stats = dev.memory_stats()
        free = stats["bytes_limit"] - stats["bytes_in_use"]
        need = ma.temp_size_in_bytes + ma.output_size_in_bytes
        log(f"batch {b} per chip: step arguments "
            f"{ma.argument_size_in_bytes / GIB:.3f} GiB, temporaries "
            f"{ma.temp_size_in_bytes / GIB:.3f} GiB; device limit "
            f"{stats['bytes_limit'] / GIB:.3f} GiB, in use "
            f"{stats['bytes_in_use'] / GIB:.3f} GiB")
        if need + HEADROOM <= free:
            return b
    raise Failed("not even one request per chip fits the device")


def serve_requests(engine, hist, sid_length):
    """Serve ``hist`` through ``engine``; ``(beams, scores, service_s)``
    in submission order, where service is admit-to-complete per request."""
    q = RequestQueue()
    rids = [q.submit(h, sid_length) for h in hist]
    res = engine.serve(q)
    failed = {i: res[i].get("error") for i in rids if "sids" not in res[i]}
    if failed:
        raise Failed(f"requests failed: {failed}")
    return (np.stack([res[i]["sids"] for i in rids]),
            np.stack([res[i]["scores"] for i in rids]),
            np.array([res[i]["latency_s"] - res[i]["queue_s"]
                      for i in rids]))


def check_compliance(log, phase, beams, scores, valid) -> None:
    checked, bad = serve.compliance(beams, scores, valid)
    log(f"phase {phase}: compliance {checked - bad}/{checked} beams inside "
        "the constraint set")
    check(checked > 0 and bad == 0, f"phase {phase}: {bad} beams violate "
                                    "the constraints")


def check_equal(phase, what, got, want) -> bool:
    ok = True
    for name, g, w in (("beams", got[0], want[0]), ("scores", got[1],
                                                      want[1])):
        g, w = np.asarray(g), np.asarray(w)
        diff = int(np.sum(g != w))
        worst = float(np.max(np.abs(g.astype(np.float64) - w))) if diff else 0
        ok &= check(diff == 0, f"phase {phase}: {name} differ from {what} "
                               f"in {diff} of {g.size} entries (largest "
                               f"difference {worst!r})")
    return ok


def batch_phase(log, clock, mark, phase, retriever, geo, hist, valid):
    """Serve ``hist`` through ``ServingEngine``; prints the compile seconds
    since ``mark`` and the steady median batch time."""
    engine = serve.build_engine("batch", retriever, geo)
    t0 = time.perf_counter()
    serve_requests(engine, hist[:geo.batch], geo.sid_length)  # warm-up
    warm = time.perf_counter() - t0
    n_compiles, compile_s = clock.since(mark)
    mark = clock.mark()
    beams, scores, service = serve_requests(engine, hist, geo.sid_length)
    steady_compiles, _ = clock.since(mark)
    log(f"phase {phase}: backend compile {compile_s:.3f} s ({n_compiles} "
        f"programs), warm-up batch {warm:.3f} s, steady median batch "
        f"{np.median(service) * 1e3:.3f} ms over {len(hist) // geo.batch} "
        f"batches of {geo.batch}, compiles while serving {steady_compiles}")
    check(steady_compiles == 0, f"phase {phase} recompiled while serving")
    check_compliance(log, phase, beams, scores, valid)
    return beams, scores


def one_chip(dev, args, log, clock):
    cfg, geo = serve.decoder("static-gr")
    log(f"decoder {cfg.name}: {cfg.n_layers} layers x d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, "
        f"{cfg.dtype}, {cfg.param_count() / 1e9:.2f}B parameters; V="
        f"{geo.vocab} L={geo.sid_length} M={geo.beam} history {geo.history} "
        f"dense_d={geo.dense_d}")
    log(f"cut: constraint set {geo.constraints:,} SIDs from seed "
        f"{args.seed} (paper: {static_gr.N_CONSTRAINTS:,})")

    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.build_params(cfg, args.seed))
    log(f"weights on device in {time.perf_counter() - t0:.3f} s")
    sids = serve.constraint_sids(geo.constraints, geo, args.seed)
    valid = {tuple(s) for s in sids.tolist()}
    t0 = time.perf_counter()
    tm = serve.build_index(sids, geo)
    log(f"index dense_d={geo.dense_d}: {tm.n_states:,} states, built on the "
        f"host in {time.perf_counter() - t0:.3f} s")

    r_a = serve.build_retriever(params, cfg, serve.build_policy(tm), geo)
    mark = clock.mark()
    batch = pick_batch(dev, lambda b: r_a.compile_step(b, geo.history),
                       geo.batch, log)
    if batch != geo.batch:
        log(f"cut: {batch} request per chip (paper: {geo.batch}); the "
            "compiled step does not fit beside the weights")
    geo = dataclasses.replace(geo, batch=batch)
    hist = serve.request_histories(BATCHES * batch, geo, args.seed)
    a = batch_phase(log, clock, mark, "A", r_a, geo, hist, valid)

    mark = clock.mark()
    r_b = serve.build_retriever(
        params, cfg, serve.build_policy(tm, impl="pallas"), geo)
    n_kernels = r_b.compile_step(batch, geo.history).as_text().count(
        "tpu_custom_call")
    log(f"phase B: compiled step holds {n_kernels} tpu_custom_call ops")
    check(n_kernels > 0, "phase B: the Pallas kernel is not in the step")
    b = batch_phase(log, clock, mark, "B", r_b, geo, hist, valid)
    if check_equal("B", "phase A", b, a):
        log("phase B: beams and scores equal phase A's exactly")

    del r_a, r_b, tm
    gc.collect()
    t0 = time.perf_counter()
    tm0 = serve.build_index(sids, geo, dense_d=0)
    log(f"index dense_d=0: {tm0.n_states:,} states, built on the host in "
        f"{time.perf_counter() - t0:.3f} s")
    r_c = serve.build_retriever(params, cfg, serve.build_policy(tm0), geo)
    mark = clock.mark()
    batch_c = pick_batch(dev, lambda b: r_c.compile_step(b, geo.history),
                         geo.batch, log)
    if batch_c != batch:
        log(f"cut: phase C serves {batch_c} request per batch and slot set "
            "(the dense_d=0 reference step does not fit at "
            f"{batch}); phases A-B served {batch}")
    geo_c = dataclasses.replace(geo, batch=batch_c)
    ref = [r_c.retrieve(hist[i:i + batch_c])
           for i in range(0, len(hist), batch_c)]
    ref = (np.concatenate([r[0] for r in ref]),
           np.concatenate([r[1] for r in ref]))
    n_compiles, compile_s = clock.since(mark)
    log(f"phase C: reference GenerativeRetriever, backend compile "
        f"{compile_s:.3f} s ({n_compiles} programs)")
    mark = clock.mark()
    # prefilling a whole batch per step runs the reference's prefill shape;
    # a smaller chunk changes the prefill's rounding (DESIGN.md §10)
    engine = serve.build_engine("continuous", r_c, geo_c,
                                prefill_chunk=batch_c,
                                share_capacity=2 * batch_c)
    n_compiles, compile_s = clock.since(mark)
    mark = clock.mark()
    beams, scores, service = serve_requests(engine, hist, geo.sid_length)
    steady_compiles, _ = clock.since(mark)
    log(f"phase C: backend compile {compile_s:.3f} s ({n_compiles} "
        f"programs), steady median request {np.median(service) * 1e3:.3f} ms"
        f" over {len(hist)} requests in {batch_c} slots, compiles while "
        f"serving {steady_compiles}")
    check(steady_compiles == 0, "phase C recompiled while serving")
    check_compliance(log, "C", beams, scores, valid)
    if check_equal("C", "GenerativeRetriever on the dense_d=0 index",
                   (beams, scores), ref):
        log("phase C: beams and scores equal GenerativeRetriever's exactly")


def four_chips(dev, args, log, clock):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_debug_mesh

    if len(jax.devices()) != 4:
        raise Failed(f"--four-chips needs 4 devices, JAX has "
                     f"{len(jax.devices())}")
    cfg, geo = serve.decoder("static-gr")
    log(f"decoder {cfg.name}: {cfg.n_layers} layers x d_model {cfg.d_model}; "
        f"V={geo.vocab} L={geo.sid_length} M={geo.beam} history "
        f"{geo.history} dense_d={geo.dense_d}")
    log(f"cut: constraint set {geo.constraints:,} SIDs from seed "
        f"{args.seed} (paper: {static_gr.N_CONSTRAINTS:,})")
    mesh = make_debug_mesh(model=1)
    log(f"mesh {dict(mesh.shape)} over {mesh.devices.size} chips, trie "
        "replicated on every chip")
    rep = NamedSharding(mesh, P())
    params = jax.block_until_ready(
        serve.build_params(cfg, args.seed, sharding=rep))
    sids = serve.constraint_sids(geo.constraints, geo, args.seed)
    valid = {tuple(s) for s in sids.tolist()}
    policy = jax.device_put(serve.build_policy(serve.build_index(sids, geo)),
                            rep)

    # the single-device reference reads chip 0's replica: no extra copy
    d0 = mesh.devices.flat[0]

    def on_d0(tree):
        return jax.tree.map(
            lambda x: next(s.data for s in x.addressable_shards
                           if s.device == d0), tree)

    n = mesh.shape["data"]
    r = serve.build_retriever(params, cfg, policy, geo, mesh=mesh)
    mark = clock.mark()
    batch = pick_batch(d0, lambda b: r.compile_step(n * b, geo.history),
                       geo.batch, log)
    if batch != geo.batch:
        log(f"cut: {batch} request per chip (paper: {geo.batch}); the SPMD "
            "step does not fit beside the weights")
    n_compiles, compile_s = clock.since(mark)
    geo = dataclasses.replace(geo, batch=batch)
    hist = serve.request_histories(n * batch, geo, args.seed)
    mark = clock.mark()
    r_ref = serve.build_retriever(on_d0(params), cfg, on_d0(policy), geo)
    ref = [r_ref.retrieve(hist[i * batch:(i + 1) * batch]) for i in range(n)]
    log(f"SPMD: single-device reference on chip {d0.id}, backend compile "
        f"{clock.since(mark)[1]:.3f} s")
    del r_ref
    gc.collect()

    mark = clock.mark()
    t0 = time.perf_counter()
    beams, scores = r.retrieve(hist)
    first = time.perf_counter() - t0
    n_first, first_compile_s = clock.since(mark)
    n_compiles, compile_s = n_compiles + n_first, compile_s + first_compile_s
    times = []
    for _ in range(BATCHES):
        t0 = time.perf_counter()
        again = r.retrieve(hist)
        times.append(time.perf_counter() - t0)
    check_equal("SPMD", "its own first call", again, (beams, scores))
    log(f"SPMD: backend compile {compile_s:.3f} s ({n_compiles} programs), "
        f"first call {first:.3f} s, steady median batch "
        f"{np.median(times) * 1e3:.3f} ms over {BATCHES} batches of "
        f"{n * batch} ({batch} per chip)")
    check_compliance(log, "SPMD", beams, scores, valid)
    ok = True
    for i in range(n):
        sl = slice(i * batch, (i + 1) * batch)
        ok &= check_equal("SPMD", "GenerativeRetriever on one device "
                          f"(chip {i})", (beams[sl], scores[sl]), ref[i])
    if ok:
        log(f"SPMD: every chip's requests ({batch} per chip) equal "
            "GenerativeRetriever on one device exactly")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel phase on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, constraint SIDs and requests")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform}", file=sys.stderr)
        return 2
    enable_compile_cache()
    clock = CompileClock()

    def log(msg):
        say(dev, msg)

    (four_chips if args.four_chips else one_chip)(dev, args, log, clock)
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"device {d.id}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
