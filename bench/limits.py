"""Readings that set the limits of the numbers ``correct`` compares.

    python bench/limits.py --workload dlrm_t1.grid24 --seeds 11 12 13 --control
    python bench/limits.py --workload dlrm_t1.grid24 --seeds 21 22 ... --program

``--control`` puts the control in the program's place: the plain reference
with its DRAM cycle arithmetic in bfloat16, the precision below the float32
the configuration states. It has to come out as not correct; the smallest
gap it reads is the upper reading of ``cycles_rel_gap``.

``--program`` runs, in one process on the chips the cell needs, the first
unit of a run of each seed and compares it as that run would compare a
window of one unit: the largest readings over the seeds are the lower
readings. The benchmark's own runs never run this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from yardstick import cells, check, reference  # noqa: E402


def control_answers(cell):
    """The control as ``check.numbers``'s ``got_override``."""
    import ml_dtypes

    def answers(configs, seed):
        return reference.simulate(cell.config, configs, seed, ftype=ml_dtypes.bfloat16)
    return answers


def control_reading(cell, run_seed: int) -> dict:
    seed = cell.unit_seed(run_seed, 0)
    _, configs = check.sample(cell, 1, run_seed)
    got = control_answers(cell)(configs, seed)
    want = reference.simulate(cell.config, configs, seed)
    mismatches, gap = check.compare(got, want)
    return {"count_mismatches": mismatches, "cycles_rel_gap": gap}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    if args.control:
        for s in args.seeds:
            print(json.dumps({"seed": s, "control": control_reading(cell, s)}), flush=True)
    if args.program:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from repro.launch.compile_cache import enable_compile_cache
        from yardstick import program

        import run
        enable_compile_cache()
        run.require_chips(cell.chips)
        unit = program.Unit(cell, devices=cell.chips)
        for s in args.seeds:
            seed = cell.unit_seed(s, 0)
            nums = check.numbers(cell, [unit(seed)], [seed], s)
            print(json.dumps({"seed": s, "program": {k: v["value"] for k, v in nums.items()}}),
                  flush=True)


if __name__ == "__main__":
    main()
