"""chip_smoke.py rehearsed on the CPU at a tiny size.

The phases run here exactly as on the chip, with the Pallas kernels in
interpret mode: the control flow, the bitwise comparisons and the reference
check are exercised; the chip-only assertions (kernels lowered for the
chip, one chip per shard) are shown to fail on the CPU, which is what keeps
them from passing vacuously.
"""
import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

TINY = cs.Size(tables=2, rows=3000, lookups=4, batch=4, num_batches=2)
GRID = cs.Grid(capacities=(1 << 16,), ways=(4,))
GEOMETRY = (1 << 16, 4)


@pytest.fixture(scope="module")
def simulated():
    return cs.phase_simulate(TINY, GRID)


@pytest.fixture(scope="module")
def pallas(simulated):
    return cs.phase_pallas(TINY, simulated, geometry=GEOMETRY)


def test_simulate_phase_goes_through_the_cli(simulated):
    assert sorted(simulated) == sorted(cs.config_name(*c) for c in GRID.configs())
    lru = simulated[cs.config_name("lru", *GEOMETRY)]
    assert lru.policy == "lru" and len(lru.batches) == TINY.num_batches
    assert lru.cache_hits + lru.cache_misses > 0


def test_sweep_phase_matches_simulate(simulated):
    assert cs.phase_sweep(TINY, GRID, simulated) == []


def test_sweep_phase_reports_a_mismatch(simulated):
    bad = dict(simulated)
    name = cs.config_name("lru", *GEOMETRY)
    bad[name] = copy.deepcopy(bad[name])
    bad[name].batches[0].cache_hits += 1
    problems = cs.phase_sweep(TINY, GRID, bad)
    assert len(problems) == 1 and problems[0].startswith(name)


def test_pallas_phase_matches_stack(pallas):
    results, problems = pallas
    assert problems == []
    assert set(results) == {"pallas/lru", "pallas/srrip", "pallas/fifo",
                            "stack_pallas/lru", "stack/fifo"}


def test_kernels_are_interpreted_off_the_chip():
    assert set(cs.kernels_compiled().values()) == {False}


def test_reference_check_passes_and_catches_one_flipped_count(simulated, pallas):
    results = {**simulated, **pallas[0]}
    reference = json.loads(json.dumps(cs.reference_payload(TINY, simulated)))
    reference["results"].update(json.loads(json.dumps(cs.reference_payload(
        TINY, {cs.config_name("fifo", *GEOMETRY): pallas[0]["stack/fifo"]}
    )["results"])))
    assert cs.compare_reference(TINY, results, reference, GEOMETRY) == []

    name = cs.config_name("srrip", *GEOMETRY)
    reference["results"][name]["batches"][1]["cache_misses"] += 1
    problems = cs.compare_reference(TINY, results, reference, GEOMETRY)
    # phase a's srrip and the pallas/srrip run are both held to it
    assert len(problems) == 2
    assert all("batches[1].cache_misses" in p for p in problems)


def test_reference_check_rejects_another_size(simulated):
    reference = cs.reference_payload(TINY, simulated)
    other = cs.Size(tables=3, rows=3000, lookups=4, batch=4, num_batches=2)
    problems = cs.compare_reference(other, {}, reference, GEOMETRY)
    assert problems and problems[0].startswith("reference size")


def test_sharded_phase_flags_shards_sharing_a_device():
    """One CPU device: both shards land on it, and the check says so while
    the results still match."""
    axes = dict(policies=("spm", "lru"), capacities=(1 << 16,), ways=(4,),
                zipf_s=0.9, seed=0)
    n, problems = cs.phase_sharded(TINY, axes, devices=2)
    assert n == 2
    assert len(problems) == 1 and "distinct device" in problems[0]


def test_committed_reference_matches_the_smoke_sizes():
    reference = json.loads(cs.REFERENCE.read_text())
    assert reference["size"] == {"tables": 60, "rows": 1_000_000,
                                 "lookups": 120, "batch": 32, "num_batches": 4}
    want = {cs.config_name(*c) for c in cs.BASE_GRID.configs()}
    want.add(cs.config_name("fifo", *cs.PALLAS_GEOMETRY))
    assert set(reference["results"]) == want


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(path)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_entry_point_fails_without_a_tpu():
    proc = _run_script(ROOT / "chip_smoke.py", ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_entry_point_fails_alone(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_the_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_leaves_the_environment_dir_to_jax(
        monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
