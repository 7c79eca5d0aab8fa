"""Shared set-up of the benchmark's CPU tests: import paths, the harness
loaded under a name of its own, and cells cut to a size a test can hold."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from yardstick import cells  # noqa: E402

TINY_DLRM = dict(num_tables=3, rows_per_table=4000, batch_size=4, num_batches=2)
SEED = 2**31 + 11          # the driver's seeds are this large


def harness():
    """``bench/run.py`` as a module (``run`` alone is too common a name)."""
    mod = sys.modules.get("bench_run")
    if mod is None:
        spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_run"] = mod
        spec.loader.exec_module(mod)
    return mod


def measure_on_cpu(monkeypatch, cell, seconds: float, trace: bool) -> dict:
    """``run.measure`` with its look for a TPU replaced by the CPU's devices."""
    import jax

    mod = harness()
    monkeypatch.setattr(mod, "require_chips", lambda n: jax.devices()[:n])
    return mod.measure(cell, SEED, seconds, trace)


def tiny(name: str = "dlrm_t1.grid24", **workload):
    """Cell ``name`` with its workload cut to ``workload`` (default: a DLRM
    of 3 tables x 4000 rows, batch 4, 2 batches)."""
    cell = cells.load(name)
    cell.config["workload"].update(workload or TINY_DLRM)
    return cell


def sharded_tiny():
    """The tiny grid over zipf 0.8/1.0 and 1/2 cores, split over four shards
    as ``sweep(devices=4)`` splits it: the four-chip cell's shape, kept for
    the cell that PERF.md leaves for later."""
    cell = tiny()
    cell.chips = 4
    cell.traffic.update(zipf_s=[0.8, 1.0], num_cores=[1, 2])
    cell.traffic["check"]["per"] = ["policy", "num_cores"]
    return cell
