"""A workload kind joins the benchmark as two new files: its reference module
(``kinds/<kind>.py``, found by the configuration's ``workload.kind``) and its
workload constructor (``programs/<constructor>.py``, found by
``program.workload``). The kinds used here live under ``tests/data``, and
the lookup is pointed there, as it looks in ``bench/kinds`` and
``bench/programs`` for a kind added to the benchmark."""
from __future__ import annotations

import copy

import pytest

from bench_tiny import BENCH, measure_on_cpu, tiny
from test_bench_limits import _answer_altered, _half_batch
from yardstick import cells, check, program, reference

DATA = BENCH / "tests" / "data"


@pytest.fixture
def kinds_under_tests_data(monkeypatch):
    monkeypatch.setattr(cells, "KINDS", DATA / "kinds")
    monkeypatch.setattr(cells, "PROGRAMS", DATA / "programs")


def twin_cell():
    """Command R+ decode traffic, cut to batch 4 x 2 steps, run as the
    test-only kind ``lm_twin`` with its own constructor."""
    cell = tiny("cmdr_plus.decode_grid24", kind="lm_twin", batch_size=4, num_batches=2)
    cell.config["program"]["workload"] = "lm_twin"
    cell.traffic["warmup_seeds"] = cell.traffic["warmup_seeds"][:1]
    return cell


def test_new_kind_runs_a_cell(monkeypatch, kinds_under_tests_data):
    cell = twin_cell()
    assert reference.kind(cell.config).__file__ == str(DATA / "kinds" / "lm_twin.py")
    out = measure_on_cpu(monkeypatch, cell, 0.3, False)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert all(n["value"] == 0 for n in out["checks"].values())


@pytest.mark.parametrize("fault", [_half_batch, _answer_altered])
def test_new_kind_fails_a_broken_path(monkeypatch, kinds_under_tests_data, fault):
    cell = twin_cell()
    fault(monkeypatch)
    out = measure_on_cpu(monkeypatch, cell, 0.2, False)
    assert out["correct"] is False


def _answers(cfg, grid, seed):
    """Answers that keep every identity for the stub kind's line counts."""
    mat = reference.matrix_summary(reference.kind(cfg).matrix_ops(cfg), cfg["hardware"])
    unit = {}
    for c in grid:
        batches = []
        for n in reference.kind(cfg).batch_lines(cfg, seed):
            m = n // 3
            batches.append(dict(
                cache_hits=n - m, cache_misses=m, onchip_reads=mat["reads"] + n,
                offchip_reads=mat["dram_lines"] + m, dram_row_hits=m // 2,
                dram_row_misses=m - m // 2, matrix_cycles=mat["cycles"],
                embedding_cycles=100.0, total_cycles=100.0 + mat["cycles"]))
        unit[reference.config_key(c)] = {"batches": batches}
    return unit


def test_identities_take_line_counts_from_the_kind(kinds_under_tests_data):
    cfg = copy.deepcopy(tiny().config)
    cfg["workload"].update(kind="routed_stub", num_batches=3)
    grid = tiny().grid()[:3]
    seeds = [5, 8]
    kind = reference.kind(cfg)
    counts = [kind.batch_lines(cfg, s) for s in seeds]
    assert len(set(counts[0])) > 1 and counts[0] != counts[1]
    answers = [_answers(cfg, grid, s) for s in seeds]
    assert check.identities(cfg, grid, answers, seeds) == 0
    assert check.identities(cfg, grid, answers, seeds[::-1]) > 0
    off = copy.deepcopy(answers)
    off[1][reference.config_key(grid[2])]["batches"][1]["cache_hits"] += 1
    assert check.identities(cfg, grid, off, seeds) == 1


@pytest.mark.parametrize("what", ["kind", "program"])
def test_unknown_kind_or_constructor_names_the_directory(what):
    cell = tiny()
    if what == "kind":
        cell.config["workload"]["kind"] = "no_such_kind"
        call, directory = lambda: reference.simulate(cell.config, cell.grid(), 1), cells.KINDS
    else:
        cell.config["program"]["workload"] = "no_such_constructor"
        call, directory = lambda: program.Unit(cell, devices=1), cells.PROGRAMS
    with pytest.raises(LookupError, match=str(directory)):
        call()
