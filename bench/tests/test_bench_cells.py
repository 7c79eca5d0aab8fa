"""Every cell of BENCHMARK.json resolves to its configuration, traffic and
metric files, and the file keeps to the benchmark's format."""
from __future__ import annotations

import json
import re

import pytest

from bench_tiny import BENCH, ROOT, cells

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24                       # a full check with 24 cells fits
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(CELLS) // 2)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_metrics():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = cells.load(name)
    spec = {c["name"]: c for c in SPEC["configs"]}
    w = next(w for w in SPEC["workloads"] if w["name"] == name)
    config_file = ROOT / spec[w["config"]]["file"]
    assert config_file.parent == BENCH / "configs"
    for key in spec[w["config"]]["reduced"]:
        assert key in cell.config["workload"] or key in cell.config.get("architecture", {})
    assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    assert cell.grid() and len(cell.grid()) == len({tuple(c.values()) for c in cell.grid()})
    assert set(cell.traffic["check"]["limits"]) == {
        "answers_inconsistent", "count_mismatches", "cycles_rel_gap"}
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["reader"].NEEDS in ("stages", "device_trace")
        assert callable(m["reader"].read)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "configs_per_s"}
    warm = cell.traffic["warmup_seeds"]
    assert warm and max(warm) < cells.UNIT_SEED_LOW


@pytest.mark.parametrize("run_seed", [2**31 + 5, 7, 2**40 + 3])
def test_unit_seeds_are_fresh(run_seed):
    """Each unit of a run, and each run, sweeps a trace of its own, never a
    warm-up trace; the same run seed gives the same traces."""
    cell = cells.load(CELLS[0])
    seeds = [cell.unit_seed(run_seed, i) for i in range(64)]
    assert len(set(seeds)) == len(seeds)
    assert min(seeds) >= cells.UNIT_SEED_LOW and max(seeds) < 2**31
    assert seeds == [cell.unit_seed(run_seed, i) for i in range(64)]
    assert not set(seeds) & {cell.unit_seed(run_seed + 1, i) for i in range(64)}


def test_unknown_cell_exits():
    with pytest.raises(SystemExit):
        cells.load("no_such.cell")
