"""EONSim CLI — run the simulator on a workload.

    PYTHONPATH=src python -m repro.launch.simulate --workload dlrm \
        --tables 60 --rows 1000000 --batch 32 --policy lru
    PYTHONPATH=src python -m repro.launch.simulate --workload lm \
        --arch command_r_plus_104b --shape decode_32k --policy pinning

``run(parser().parse_args(argv))`` is the same path as a callable: it
simulates the parsed arguments and returns the ``SimResult``.
"""
from __future__ import annotations

import argparse

from repro.core import OnChipPolicy, SimResult, dlrm_rmc2_small, simulate, tpuv6e
from repro.core.hardware import CACHE_BACKENDS
from repro.core.lm_mapper import lm_workload
from repro.core.trace import REUSE_LEVELS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import SHAPES_BY_NAME, get_config


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="dlrm", choices=["dlrm", "lm"])
    ap.add_argument("--policy", default="spm",
                    choices=[p.value for p in OnChipPolicy])
    ap.add_argument("--capacity-bytes", type=int, default=None,
                    help="on-chip capacity (default: the tpuv6e preset)")
    ap.add_argument("--ways", type=int, default=None,
                    help="cache associativity (default: the tpuv6e preset)")
    ap.add_argument("--cache-backend", default=None, choices=CACHE_BACKENDS,
                    help="cache engine (default: the tpuv6e preset)")
    ap.add_argument("--tables", type=int, default=60)
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--lookups", type=int, default=120)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--num-batches", type=int, default=1)
    ap.add_argument("--zipf", type=float, default=REUSE_LEVELS["reuse_mid"])
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--json", action="store_true")
    return ap


def run(args: argparse.Namespace) -> SimResult:
    """Simulate the workload and hardware that ``parser()``'s ``args`` name."""
    onchip = {k: v for k, v in (("capacity_bytes", args.capacity_bytes),
                                ("ways", args.ways)) if v is not None}
    hw = tpuv6e().with_policy(OnChipPolicy(args.policy), **onchip)
    if args.cache_backend is not None:
        hw = hw.with_cache_backend(args.cache_backend)
    if args.workload == "dlrm":
        wl = dlrm_rmc2_small(
            num_tables=args.tables, rows_per_table=args.rows,
            lookups=args.lookups, batch_size=args.batch,
            num_batches=args.num_batches,
        )
    else:
        cfg = get_config(args.arch)
        wl = lm_workload(cfg, SHAPES_BY_NAME[args.shape], num_batches=args.num_batches)
    return simulate(wl, hw, zipf_s=args.zipf)


def main(argv=None):
    args = parser().parse_args(argv)
    enable_compile_cache()
    res = run(args)
    if args.json:
        print(res.to_json())
    else:
        for k, v in res.summary().items():
            print(f"{k:20s} {v}")


if __name__ == "__main__":
    main()
