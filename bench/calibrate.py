#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seed <n> --seconds 3 \
        [--control 1]

One run of the cell as ``bench/run.py`` makes it (the program's own served
path, at the cell's load, for ``--seconds``), and the compared numbers it
gives.  With ``--control 1`` also the control's numbers: the reference
computed with float8 (e4m3) operands in the program's place, read at the
same prompts and served beams, and whether the cell's limits judge them
correct.  One JSON line per run.  The lower reading of a number is the
largest over a dozen sound seeds, the upper the smallest over the control's
seeds.  Run one seed per process: every run builds and loads the whole
served system.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from run import spec_lib, run_and_check  # noqa: E402  (bench/ on the path)


def control_numbers(cell, seed, sids, sample, lp):
    from bench import check, system

    ref = system.reference(cell.config)
    prompts = np.stack([r.prompt for r in sample])
    beams = np.stack([r.sids for r in sample]).astype(np.int64)
    lp8 = ref.logprobs(cell.config, seed, prompts, beams, precision="fp8")
    served8 = np.take_along_axis(lp8, beams[..., None], -1)[..., 0].sum(-1)
    cs = check.ConstraintSet(sids)
    return {"score_gap": check.score_gap(beams, served8, lp),
            "select_gap": check.select_gap(cs, beams, lp,
                                           cell.config["beam"], lp_pick=lp8)}


def main(argv=None) -> int:
    import argparse

    from bench import check

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = spec_lib.load(args.workload)
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    t = time.monotonic()
    out, sample, sids, lp = run_and_check(cell, args.seed, args.seconds,
                                          False, t)
    row = {"seed": args.seed, "correct": out["correct"],
           "program": {k: c["value"] for k, c in out["checks"].items()}}
    if args.control:
        row["control"] = control_numbers(cell, args.seed, sids, sample, lp)
        limits = cell.config["limits"]
        row["control_correct"] = check.judge(
            row["control"], {k: limits[k] for k in row["control"]})
    row["seconds"] = time.monotonic() - t
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
