#!/usr/bin/env python3
"""Record the profiler trace of one batch of a cell, for the trace tests.

    python3 bench/record_one_batch.py --workload <name> --seed <n> \
        --out bench/testdata/<name>.xplane.pb.gz

Builds the cell's served system as ``bench/run.py`` does, warms it up, and
traces one ``engine.serve`` of one full batch under a ``bench.serve`` span,
then writes the trace's ``.xplane.pb`` gzipped to ``--out``.  Needs the
chip; without a TPU it exits non-zero and writes nothing.
"""
from __future__ import annotations

import argparse
import gzip
import os
import pathlib
import shutil
import sys
import tempfile

from run import CACHE, enable_compile_cache, spec_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = spec_lib.load(args.workload)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_one_batch: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    from bench import system as sys_lib
    from bench import traffic as tr
    from repro.serving.engine import RequestQueue

    cfg = cell.config
    system = sys_lib.build(cfg, cell.traffic["engine"], args.seed,
                           sys_lib.constraint_sids(cfg, args.seed))
    tr.warm_up(system.engine, RequestQueue, system.slots, args.seed,
               cfg["history"], cfg["vocab"], cfg["sid_length"])
    q = RequestQueue()
    for i in range(system.slots):
        q.submit(tr.history(args.seed, tr.WINDOW_STREAM, i, cfg["history"],
                            cfg["vocab"]), cfg["sid_length"])
    CACHE.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=CACHE) as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.serve"):
            res = system.engine.serve(q)
        jax.profiler.stop_trace()
        if not all("sids" in r for r in res.values()):
            print("record_one_batch: a request failed", file=sys.stderr)
            return 1
        (path,) = pathlib.Path(d).rglob("*.xplane.pb")
        with open(path, "rb") as a, gzip.open(args.out, "wb") as b:
            shutil.copyfileobj(a, b)
    print(args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
