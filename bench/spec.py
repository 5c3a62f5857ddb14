"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each lives in a file of its own (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``), and every metric is computed by a reader
of its own (``bench/metrics/<metric>.py``, a function ``read(run)`` that
returns a number, or None where it finds nothing to read).  Adding a cell,
a configuration, a mix or a metric adds files and entries; it edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    end_to_end: bool
    moves: str | None
    workloads: tuple | None

    def reader(self):
        path = BENCH / "metrics" / f"{self.name}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{self.name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # Metric, reported with --trace 0
    per_layer: tuple  # Metric, reported with --trace 1


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics(entries, end_to_end):
    return [Metric(m["name"], m["unit"], end_to_end, m.get("moves"),
                   tuple(m["workloads"]) if "workloads" in m else None)
            for m in entries]


def load(workload: str, path: pathlib.Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``workload`` with its files read and its metrics chosen."""
    spec = _load_json(path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {path.name}; "
                       f"choose one of {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(ROOT / configs[w["config"]]["file"])
    traffic = _load_json(BENCH / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return m.workloads is None or workload in m.workloads

    e2e = [m for m in _metrics(spec["end_to_end"], True) if mine(m)]
    e2e_names = {m.name for m in e2e}
    per_layer = [m for m in _metrics(spec["per_layer"], False)
                 if mine(m) and m.moves in e2e_names]
    return Cell(w["name"], w["config"], w["traffic"], int(w["chips"]),
                config, traffic, tuple(e2e), tuple(per_layer))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    table = _load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]
