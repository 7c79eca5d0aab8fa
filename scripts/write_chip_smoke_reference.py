"""Write ``chip_smoke_reference.json``: the CPU's answers for ``chip_smoke.py``.

    PYTHONPATH=src JAX_PLATFORMS=cpu python scripts/write_chip_smoke_reference.py

Runs ``chip_smoke``'s phase a (every base-grid config at the Table I size)
plus a stack-engine run of each Pallas phase policy that phase a lacks, on
the CPU, and stores every result's counts and cycle totals. ``chip_smoke.py``
holds the chip's results to them exactly. Takes a few minutes on one core.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke as cs  # noqa: E402


def main() -> None:
    results = cs.phase_simulate(cs.TABLE_I, cs.BASE_GRID)
    policies = tuple(sorted({p for _, p in cs.PALLAS_RUNS}))
    geometry = cs.Grid(policies=policies, capacities=(cs.PALLAS_GEOMETRY[0],),
                       ways=(cs.PALLAS_GEOMETRY[1],))
    results.update(cs.phase_simulate(cs.TABLE_I, geometry))
    payload = cs.reference_payload(cs.TABLE_I, results)
    cs.REFERENCE.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    print(f"wrote {cs.REFERENCE} ({len(results)} results)")


if __name__ == "__main__":
    main()
