#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix and metrics are found by name from
``BENCHMARK.json``.  The run builds the served system with the program's
own builders (weights on the device from the seed), warms up the shapes
the cell uses, drives the engine for ``--seconds`` with the mix's load
generator, and then checks what the window served against a plain float32
reference (``bench/check.py``).  With ``--trace 1`` the window runs under
the profiler and the cell's per-layer metrics are reported instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``, each compared number with its limit.
The same numbers close standard error.  Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import spec as spec_lib  # noqa: E402

CACHE = ROOT / "bench" / ".cache"


class CompileCounter:
    """Backend compiles seen by ``jax.monitoring`` (the benchmark's own
    listener, so that the count cannot move when the program does)."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if "backend_compile" in name:
            with self._lock:
                self.count += 1


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    cell: spec_lib.Cell
    window: object  # bench.traffic.Window
    seconds: float
    setup_s: float
    slots: int
    work: object  # bench.work.Retrieval
    peaks: dict
    trace: object = None  # bench.trace.Summary, with --trace 1


def enable_compile_cache() -> None:
    """JAX's persistent cache at one fixed path inside the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def drive(cell, system, seed, seconds, on_start=None, on_stop=None):
    from bench import traffic as tr
    from repro.serving.engine import RequestQueue

    cfg, mix = cell.config, cell.traffic
    kw = dict(seed=seed, seconds=seconds, width=cfg["history"],
              vocab=cfg["vocab"], sid_length=cfg["sid_length"],
              on_start=on_start, on_stop=on_stop)
    if mix["loop"] == "closed":
        return tr.closed_loop(system.engine, RequestQueue, mix,
                              slots=system.slots, **kw)
    raise ValueError(f"unknown loop {mix['loop']!r}")


def check_sample(cell, window, seed):
    """Finished requests the reference checks: ``check_requests`` of them,
    drawn from the seed."""
    import numpy as np

    done = [r for r in window.records if r.ok]
    k = min(int(cell.config["check_requests"]), len(done))
    rng = np.random.default_rng([seed, 3])
    pick = sorted(rng.choice(len(done), k, replace=False).tolist())
    return [done[i] for i in pick]


def compare(cell, seed, sids, window, sample):
    """The compared numbers of bench/check.py (module docstring), and the
    reference's log-probs over the sample."""
    import numpy as np

    from bench import check, system

    cs = check.ConstraintSet(sids)
    served = [r for r in window.records if r.ok]
    numbers = {"violations": float(check.violations(
        cs, np.stack([r.sids for r in served]),
        np.stack([r.scores for r in served])))}
    ref = system.reference(cell.config)
    prompts = np.stack([r.prompt for r in sample])
    beams = np.stack([r.sids for r in sample]).astype(np.int64)
    scores = np.stack([r.scores for r in sample])
    lp = ref.logprobs(cell.config, seed, prompts, beams)
    numbers["score_gap"] = check.score_gap(beams, scores, lp)
    numbers["select_gap"] = check.select_gap(cs, beams, lp,
                                             cell.config["beam"])
    return numbers, lp


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float = T_START) -> dict:
    """One run of ``cell``; returns the result line's object."""
    return run_and_check(cell, seed, seconds, trace, t_start)[0]


def run_and_check(cell, seed, seconds, trace, t_start):
    """One run; returns the result line's object, the checked sample, the
    constraint SIDs and the reference's log-probs over the sample."""
    import jax

    from bench import check
    from bench import system as sys_lib
    from bench import work
    from bench.traffic import warm_up
    from repro.serving.engine import RequestQueue

    dev = jax.devices()[0]
    peaks = {}
    if dev.platform == "tpu":
        peaks = spec_lib.peaks(dev.device_kind)
        enable_compile_cache()
    compiles = CompileCounter()
    cfg, mix = cell.config, cell.traffic
    sids = sys_lib.constraint_sids(cfg, seed)
    system = sys_lib.build(cfg, mix["engine"], seed, sids)
    warm_up(system.engine, RequestQueue, system.slots, seed,
            cfg["history"], cfg["vocab"], cfg["sid_length"])
    setup_s = time.monotonic() - t_start

    trace_dir = CACHE / "trace" / cell.name
    on_start = on_stop = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        on_start = lambda: jax.profiler.start_trace(str(trace_dir))  # noqa
        on_stop = jax.profiler.stop_trace
    before = compiles.count
    window = drive(cell, system, seed, seconds, on_start, on_stop)
    in_window = compiles.count - before

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    run = Run(cell, window, seconds, setup_s, system.slots,
              work.retrieval(cfg), peaks)
    if trace:
        from bench import trace as trace_lib

        run.trace = trace_lib.summarize(trace_lib.load(trace_dir),
                                        window.trace_span)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader()(run)
        if value is None or (isinstance(value, float) and math.isnan(value)):
            if m.end_to_end:
                raise RuntimeError(f"end-to-end metric {m.name} read nothing")
            continue
        metrics[m.name] = {"value": value, "unit": m.unit}

    # the program's state goes before the reference runs
    sample = check_sample(cell, window, seed)
    del system
    gc.collect()
    jax.clear_caches()
    t_ref = time.monotonic()
    numbers, lp = compare(cell, seed, sids, window, sample)
    t_ref = time.monotonic() - t_ref
    numbers["compiles_in_window"] = float(in_window)
    limits = dict(cfg["limits"], compiles_in_window=0)

    out = {"correct": check.judge(numbers, limits),
           "attempted": window.attempted, "failed": window.failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = run.trace.breakdown()
    out["reference_s"] = t_ref
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    return out, sample, sids, lp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec_lib.load(args.workload)

    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX has "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(f"reference: {out['reference_s']!r} s", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
