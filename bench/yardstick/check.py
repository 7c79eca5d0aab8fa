"""The comparison that decides ``correct``.

Every answer of the window (each configuration's ``SimResult`` record in
every unit) is checked for completeness and for the accounting identities
that hold whatever the policy; a sample drawn from the run seed, one unit
and a few configurations of it stratified by the traffic's ``check.per``
axes, is compared field by field with the plain reference
(``reference.simulate``). Each number compared has its limit in the traffic
file (``check.limits``); PERF.md gives the readings each limit was set from.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import reference

INT_FIELDS = ("onchip_reads", "onchip_writes", "offchip_reads", "vector_ops",
              "cache_hits", "cache_misses", "dram_row_hits", "dram_row_misses",
              "tlb_hits", "tlb_misses", "tlb_walks")
FLOAT_FIELDS = ("embedding_cycles", "matrix_cycles", "total_cycles",
                "translation_cycles")
SUMMARY_INT = ("onchip_reads", "onchip_writes", "offchip_reads", "cache_hits",
               "cache_misses", "num_batches")
SUMMARY_FLOAT = ("total_cycles", "embedding_cycles", "matrix_cycles", "energy_pj")


def sample(cell, units: int, run_seed: int):
    """``(unit index, [configs])`` to compare with the reference: for each
    value combination of the ``per`` axes one configuration, plus ``extra``
    more, all drawn from the run seed."""
    rng = np.random.default_rng([run_seed, 0x5eed])
    unit = int(rng.integers(units))
    grid = cell.grid()
    per, extra = cell.traffic["check"]["per"], cell.traffic["check"]["extra"]
    groups: Dict[tuple, List[int]] = {}
    for i, c in enumerate(grid):
        groups.setdefault(tuple(c[k] for k in per), []).append(i)
    picked = [int(rng.choice(g)) for g in groups.values()]
    rest = [i for i in range(len(grid)) if i not in picked]
    picked += [int(i) for i in rng.choice(rest, size=min(extra, len(rest)), replace=False)]
    return unit, [grid[i] for i in sorted(picked)]


def identities(cfg: dict, grid: List[dict], answers: List[Dict[tuple, dict]],
               unit_seeds: List[int]) -> int:
    """Answers missing, or breaking the accounting every policy keeps: per
    batch, hits + misses = the batch's line accesses (the workload kind's
    count for the unit's seed), off-chip reads = misses + matrix lines, DRAM
    row hits + misses = misses, totals = embedding + matrix cycles."""
    kind = reference.kind(cfg)
    mat = reference.matrix_summary(kind.matrix_ops(cfg), cfg["hardware"])
    bad = 0
    for unit, seed in zip(answers, unit_seeds, strict=True):
        lines = kind.batch_lines(cfg, seed)
        bad += len(grid) - sum(reference.config_key(c) in unit for c in grid)
        for rec in unit.values():
            batches = rec["batches"]
            bad += len(batches) != len(lines)
            for b, n in zip(batches, lines):
                m = b["cache_misses"]
                bad += (b["cache_hits"] + m != n
                        or b["onchip_reads"] != mat["reads"] + n
                        or b["offchip_reads"] != mat["dram_lines"] + m
                        or b["dram_row_hits"] + b["dram_row_misses"] != m
                        or b["matrix_cycles"] != mat["cycles"]
                        or b["total_cycles"] != b["embedding_cycles"] + mat["cycles"])
    return bad


def compare(got: Dict[tuple, dict], want: Dict[tuple, dict]):
    """``(count mismatches, widest relative gap of a cycle or energy
    total)`` of ``got`` against ``want`` over ``want``'s configurations."""
    mismatches, gap = 0, 0.0

    def rel(a, b):
        return abs(a - b) / max(abs(b), 1e-30) if a != b else 0.0

    for key, w in want.items():
        g = got.get(key)
        if g is None:
            mismatches += 1
            continue
        gs, ws = g["summary"], w["summary"]
        mismatches += sum(gs[k] != ws[k] for k in SUMMARY_INT)
        gap = max([gap] + [rel(gs[k], ws[k]) for k in SUMMARY_FLOAT])
        if len(g["batches"]) != len(w["batches"]):
            mismatches += 1
            continue
        for gb, wb in zip(g["batches"], w["batches"]):
            mismatches += sum(gb[k] != wb[k] for k in INT_FIELDS)
            gap = max([gap] + [rel(gb[k], wb[k]) for k in FLOAT_FIELDS])
    return mismatches, gap


def numbers(cell, answers: List[Dict[tuple, dict]], unit_seeds: List[int],
            run_seed: int, got_override=None) -> Dict[str, dict]:
    """The numbers compared, each with its limit. ``got_override`` puts
    other answers for the sampled configurations in the program's place
    (the control and the planted faults of ``bench/limits.py``)."""
    limits = cell.traffic["check"]["limits"]
    out = {"answers_inconsistent": identities(cell.config, cell.grid(), answers,
                                              unit_seeds)}
    if answers:
        unit, configs = sample(cell, len(answers), run_seed)
        want = reference.simulate(cell.config, configs, unit_seeds[unit])
        got = answers[unit] if got_override is None else got_override(configs, unit_seeds[unit])
        out["count_mismatches"], out["cycles_rel_gap"] = compare(got, want)
    else:
        out["count_mismatches"], out["cycles_rel_gap"] = 1, 1.0
    return {k: {"value": v, "limit": limits[k]} for k, v in out.items()}


def passed(nums: Dict[str, dict]) -> bool:
    return all(n["value"] <= n["limit"] for n in nums.values())
