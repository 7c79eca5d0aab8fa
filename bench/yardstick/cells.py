"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

A cell names a configuration and a traffic mix; the per-layer metrics that
list the cell (or list no cells) are its metrics in a traced run. Each is
found by name: ``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``
and ``bench/metrics/<metric>.py``. A configuration's ``workload.kind`` names
the reference's module for it, ``bench/kinds/<kind>.py``, and its
``program.workload`` the module that builds the simulator's workload,
``bench/programs/<constructor>.py``. Adding a cell, a configuration, a mix,
a metric or a workload kind adds files and entries; no existing file
changes.
"""
from __future__ import annotations

import importlib.util
import itertools
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
METRICS = BENCH / "metrics"
KINDS = BENCH / "kinds"
PROGRAMS = BENCH / "programs"
UNIT_SEED_LOW = 2**20   # warm-up seeds lie below, the window's at or above


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]       # each entry gains "reader", its module

    def grid(self) -> List[dict]:
        """Every configuration one unit evaluates, in axis order."""
        t = self.traffic
        return [dict(policy=p, capacity_bytes=c, ways=w, zipf_s=z, num_cores=n)
                for z, p, c, w, n in itertools.product(
                    t["zipf_s"], t["policies"], t["capacities"], t["ways"],
                    t["num_cores"])]

    def unit_seed(self, run_seed: int, index: int) -> int:
        """Trace seed of unit ``index`` of a run: drawn from the run seed
        and the index, so every unit of every run sweeps a trace of its
        own. It never falls among the mix's ``warmup_seeds``, which lie
        below ``UNIT_SEED_LOW``."""
        rng = np.random.default_rng([run_seed % 2**64, index])
        return int(rng.integers(UNIT_SEED_LOW, 2**31))


def _load(directory: Path, name: str, what: str):
    """The module ``<directory>/<name>.py``, loaded under a name of its own."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no {what} {name!r}: {path.name} is not in {directory}")
    spec = importlib.util.spec_from_file_location(f"bench_{directory.name}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    """A per-layer metric's reader: ``NEEDS`` and ``read(obs)``."""
    return _load(METRICS, name, "per-layer metric")


def load_kind(name: str):
    """A workload kind's reference module (see ``reference.kind``)."""
    return _load(KINDS, name, "workload kind")


def load_program(name: str):
    """A workload constructor's module: ``build(cfg) -> (workload, hardware)``."""
    return _load(PROGRAMS, name, "workload constructor")


def load(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    per_layer = []
    for m in bench["per_layer"]:
        if name in m.get("workloads", [name]):
            per_layer.append(dict(m, reader=load_metric(m["name"])))
    end_to_end = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    return Cell(name=name, chips=int(w["chips"]), config=config, traffic=traffic,
                end_to_end=end_to_end, per_layer=per_layer)
