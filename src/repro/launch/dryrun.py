import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks device count on first init).
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.distributed.collectives import parse_collective_bytes  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_cell, list_cells  # noqa: E402

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, lower + compile the step
function on the production mesh — 16x16 (256 chips) AND 2x16x16 (512 chips,
multi-pod) — and record memory_analysis / cost_analysis / the collective
schedule.  A failure here (sharding mismatch, OOM at compile, unsupported
collective) is a bug in the system, not in the harness.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape long_500k --mesh multi
  PYTHONPATH=src python -m repro.launch.dryrun --out reports/dryrun.jsonl
"""


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(arch, shape, mesh)
    with jax.set_mesh(mesh):
        jf = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jf.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    coll = parse_collective_bytes(compiled.as_text())
    rec = {
        "arch": arch,
        "shape": shape,
        "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": mesh.size,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "arg_bytes_per_chip": int(ma.argument_size_in_bytes),
        "temp_bytes_per_chip": int(ma.temp_size_in_bytes),
        "out_bytes_per_chip": int(ma.output_size_in_bytes),
        "hlo_flops_per_chip": float(ca.get("flops", 0.0)),
        "hlo_bytes_per_chip": float(ca.get("bytes accessed", 0.0)),
        "collectives": coll,
        "model_flops_per_chip": float(cell.model_flops_per_chip),
        "notes": cell.notes,
    }
    if verbose:
        print(f"[{rec['mesh']}] {arch} x {shape} ({cell.kind}): "
              f"compile {t_compile:.1f}s")
        print(f"  memory_analysis: args {ma.argument_size_in_bytes/1e9:.2f} GB/chip, "
              f"temp {ma.temp_size_in_bytes/1e9:.2f} GB/chip, "
              f"out {ma.output_size_in_bytes/1e9:.2f} GB/chip")
        print(f"  cost_analysis: {ca.get('flops', 0)/1e9:.1f} GFLOP/chip, "
              f"{ca.get('bytes accessed', 0)/1e9:.2f} GB accessed/chip")
        print(f"  collectives: {coll['counts_by_op']} "
              f"link_bytes/chip {coll['link_bytes']/1e6:.1f} MB")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="reports/dryrun.jsonl")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()
    enable_compile_cache()

    runnable, skipped = list_cells()
    cells = [
        (a, s) for a, s, _ in runnable
        if (args.arch == "all" or a == args.arch)
        and (args.shape == "all" or s == args.shape)
    ]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if args.resume and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                r = json.loads(line)
                if r.get("ok"):
                    done.add((r["arch"], r["shape"], r["mesh"]))

    n_fail = 0
    with open(args.out, "a") as f:
        for arch, shape in cells:
            for multi in meshes:
                mesh_name = "2x16x16" if multi else "16x16"
                if (arch, shape, mesh_name) in done:
                    print(f"[skip cached] {arch} x {shape} @ {mesh_name}")
                    continue
                try:
                    rec = run_cell(arch, shape, multi)
                except Exception as e:  # noqa: BLE001
                    n_fail += 1
                    rec = {
                        "arch": arch, "shape": shape,
                        "mesh": mesh_name, "ok": False,
                        "error": f"{type(e).__name__}: {e}",
                    }
                    print(f"[FAIL] {arch} x {shape} @ {rec['mesh']}: "
                          f"{rec['error']}")
                    traceback.print_exc()
                f.write(json.dumps(rec) + "\n")
                f.flush()
        for arch, shape, why in skipped:
            f.write(json.dumps({
                "arch": arch, "shape": shape, "mesh": "-", "ok": None,
                "skipped": why,
            }) + "\n")
    print(f"\ndone; {n_fail} failures; skipped cells: "
          f"{[(a, s) for a, s, _ in skipped]}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
