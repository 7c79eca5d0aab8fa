"""A run of the harness at a tiny size on the CPU: the program's units equal
the plain reference bit for bit, the last line has exactly the keys the
benchmark's format asks for, and the measurement path refuses to run
without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import BENCH, ROOT, SEED, measure_on_cpu, sharded_tiny, tiny
from yardstick import check, program, reference

TOP_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("make", [tiny, sharded_tiny], ids=["grid24", "sharded"])
def test_unit_equals_reference(make):
    cell = make()
    seed = cell.unit_seed(SEED, 0)
    got = program.Unit(cell, devices=cell.chips)(seed)
    want = reference.simulate(cell.config, cell.grid(), seed)
    assert set(got) == set(want)
    assert check.compare(got, want) == (0, 0.0)
    assert check.identities(cell.config, cell.grid(), [got], [seed]) == 0


def test_lm_decode_unit_equals_reference():
    cell = tiny("cmdr_plus.decode_grid24", num_batches=2)
    seed = cell.unit_seed(SEED, 3)
    configs = [c for c in cell.grid() if c["capacity_bytes"] == 1 << 20 and c["ways"] == 8]
    got = program.Unit(cell, devices=1)(seed)
    want = reference.simulate(cell.config, configs, seed)
    assert check.compare(got, want) == (0, 0.0)


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_keys(monkeypatch, trace):
    cell = tiny()
    out = measure_on_cpu(monkeypatch, cell, 0.5, trace)
    assert list(out) == TOP_KEYS            # the CPU trace holds no device plane
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    want = ({m["name"] for m in cell.per_layer if m["reader"].NEEDS == "stages"}
            if trace else {m["name"] for m in cell.end_to_end})
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert all(set(n) == {"value", "limit"} for n in out["checks"].values())
    json.dumps(out, allow_nan=False)


def _run_cell(root):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dlrm_t1.grid24",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)


def test_exits_without_a_tpu():
    proc = _run_cell(ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_exits_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cell(tmp_path)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
