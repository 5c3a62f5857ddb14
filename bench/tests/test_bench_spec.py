"""Every cell of BENCHMARK.json resolves to files the harness can run."""
import dataclasses
import json
import re

import pytest

from bench import spec, system

ROOT = spec.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = spec.load(cell)
    assert c.traffic["engine"] in c.config["requests_per_chip"]
    assert c.traffic["loop"] == "closed"
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(m.reader())
    for m in c.per_layer:
        assert m.moves in names


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_is_the_programs(cfg):
    """The file's decoder fields are what the program's config holds, and
    the reference named beside them exists."""
    c = json.loads((ROOT / cfg["file"]).read_text())
    pcfg = system.program_config(c)
    for k, v in c["decoder"].items():
        if isinstance(v, dict):
            for kk, vv in v.items():
                assert getattr(getattr(pcfg, k), kk) == vv, f"{k}.{kk}"
        elif k != "base":
            assert getattr(pcfg, k) == v, k
    if "head_dim" in c["decoder"]:
        assert pcfg.resolved_head_dim() == c["decoder"]["head_dim"]
    assert (spec.BENCH / "references" / f"{c['reference']}.py").exists()
    assert (spec.BENCH / "counts" / f"{c['reference']}.py").exists()
    assert set(cfg["reduced"]) <= set(c) | set(c["decoder"])
    assert set(c["limits"]) == {"violations", "score_gap", "select_gap"}
    assert c["limits"]["violations"] == 0


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert (spec.BENCH / "metrics" / f"{m['name']}.py").exists()
        for w in m.get("workloads", []):
            assert w in CELLS
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.load("no-such-cell")


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_cell_is_frozen_data():
    c = spec.load(CELLS[0])
    with pytest.raises(dataclasses.FrozenInstanceError):
        c.name = "x"
