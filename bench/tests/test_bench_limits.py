"""The comparison that decides ``correct`` fails what it must: the control
(the reference with bfloat16 DRAM cycles) in the program's place, and a
whole run with the timed path broken underneath, once for each fault the
cells can have."""
from __future__ import annotations

import importlib.util

import pytest

from bench_tiny import BENCH, SEED, measure_on_cpu, sharded_tiny, tiny
from yardstick import check, program

_spec = importlib.util.spec_from_file_location("bench_limits", BENCH / "limits.py")
limits = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(limits)


def test_control_is_not_correct():
    cell = tiny()
    seeds = [cell.unit_seed(SEED, 0)]
    answers = [program.Unit(cell, devices=1)(seeds[0])]
    sound = check.numbers(cell, answers, seeds, SEED)
    assert check.passed(sound)
    nums = check.numbers(cell, answers, seeds, SEED,
                         got_override=limits.control_answers(cell))
    assert not check.passed(nums)
    assert nums["cycles_rel_gap"]["value"] > 1e3 * nums["cycles_rel_gap"]["limit"]
    reading = limits.control_reading(cell, SEED)
    assert reading["cycles_rel_gap"] > cell.traffic["check"]["limits"]["cycles_rel_gap"]


def _state_unchanged(monkeypatch):
    """Every cache keeps its state empty: no access ever hits."""
    import numpy as np
    from repro.core.memory import policies

    monkeypatch.setattr(policies, "classify_streams",
                        lambda streams, *a, **k: [np.zeros(len(s), bool) for s in streams])


def _half_batch(monkeypatch):
    """Half of each batch's samples left out of the trace."""
    from repro.core import engine

    orig = engine.expand_trace
    monkeypatch.setattr(engine, "expand_trace",
                        lambda it, spec, batch, seed=1: orig(
                            it[: len(it) // 2], spec, batch // 2, seed=seed))


def _answer_altered(monkeypatch):
    """One count altered where the result is assembled."""
    sweep_mod = importlib.import_module("repro.core.sweep")
    orig = sweep_mod.assemble_result

    def altered(*a, **k):
        res = orig(*a, **k)
        res.batches[0].cache_hits += 1
        return res
    monkeypatch.setattr(sweep_mod, "assemble_result", altered)


def _exchange_left_out(monkeypatch):
    """The gather of the shards' results leaves one shard's keys out."""
    from repro.distributed import sweep_shard

    orig = sweep_shard.evaluate_sharded

    def dropped(*a, **k):
        out = orig(*a, **k)
        return {key: v for i, (key, v) in enumerate(out.items()) if i % 4}
    monkeypatch.setattr(sweep_shard, "evaluate_sharded", dropped)


@pytest.mark.parametrize("make,fault", [
    (tiny, _state_unchanged),
    (tiny, _half_batch),
    (tiny, _answer_altered),
    (sharded_tiny, _exchange_left_out),
])
def test_broken_path_is_not_correct(monkeypatch, make, fault):
    cell = make()
    fault(monkeypatch)
    out = measure_on_cpu(monkeypatch, cell, 0.2, False)
    assert out["correct"] is False
