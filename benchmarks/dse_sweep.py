"""DSE sweep benchmark: grid evaluation throughput + sharing speedup.

Runs a (policy x capacity x ways) grid through ``sweep()`` in one pass, then
times a sample of the same configs as independent ``simulate()`` calls to
measure the benefit of sharing traces / matrix results / compiled scans, and
re-times the sweep with ``batch_scans=False`` to isolate the vmapped
same-policy scan-batching win. Emits one ``kind=perf`` record plus one row
per grid point, saved BOTH under results/bench/ and as BENCH_sweep.json at
the repo root — the root copy is checked in (and uploaded by CI every run)
so the per-config perf trajectory is tracked across PRs.

Every timed slice is best-of-2 (single-shot walls on small shared runners
carry ~20% scheduler noise, enough to fake a regression), and the perf row
records ``device_count`` / ``host_cpus`` / ``sharded`` so trajectories from
different runners stay comparable.

``--profile`` re-times the sweep inside a stage-profiling session
(``repro.core.profiling``) and adds a per-stage wall-time breakdown to the
perf record — trace gen / classify / cache scan / DRAM / host sync — so the
next perf PR starts from data instead of guesses.

A separate NUMA placement-axes slice (channel_affinity x placement on a
2-core table_hash cluster) is timed into ``placement_per_config_ms`` without
touching the historical perf-gate grid, and a serving-scenario slice sweeps
the closed-loop request-level scheduler (steady vs overload-with-robustness
traffic as first-class axes) into ``kind=serving`` rows — per-(hardware x
scenario) p50/p95/p99 latency, goodput and shed/timeout/retry counters.

The **sharded probe** measures the device-sharded sweep in the same process:
with two or more devices it runs a 96-config grid unsharded and sharded over
every device, checks bitwise equality and fault-free telemetry, and reports
``sharded_speedup`` into the perf row. With one device it is skipped, and the
script says so. A probe failure fails the script.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List

from repro.core import (
    OnChipPolicy,
    TrafficConfig,
    dlrm_rmc2_small,
    simulate,
    sweep,
    tpuv6e,
)
from repro.core import profiling
from repro.serving import RobustnessPolicy, ServingScenario

TABLES, ROWS, BATCH = 4, 100_000, 48
POLICIES = ("spm", "lru", "srrip", "pinning")
CAPACITIES = (1 << 20, 4 << 20, 16 << 20)
WAYS = (8, 16)
ZIPF = 1.0
N_INDEPENDENT_SAMPLE = 6

# The placement-axes slice grid.
PLACEMENT_TABLES = 6
PLACEMENT_AXES = dict(
    policies=("spm", "lru"), zipf_s=ZIPF, seed=0,
    channel_affinities=("symmetric", "per_core", "per_table"),
    placements=("interleave", "table_rank", "hot_replicate"),
)

# The sharded probe's grid: the perf-gate grid widened by zipf x cores to
# 96 configs (4 x 3 x 2 x 2 x 2) so the shard partition has enough memo-key
# groups to spread across every device of a host.
SHARDED_AXES = dict(
    policies=POLICIES, capacities=CAPACITIES, ways=WAYS,
    zipf_s=(0.8, 1.0), num_cores=(1, 2), seed=0,
)

# Serving-scenario slice: the closed-loop request-level scheduler as DSE
# axes (traffic pattern x robustness policy) over the perf-gate policies.
# Each (hardware x scenario) point emits a ``kind=serving`` row carrying the
# latency distribution (p50/p95/p99), goodput and the shed/timeout/retry
# counters — the serving trajectory tracked in BENCH_sweep.json.
SERVING_TABLES, SERVING_ROWS = 4, 20_000
SERVING_AXES = dict(policies=POLICIES, capacities=(1 << 20,), ways=(8,))
SERVING_SCENARIOS = (
    ServingScenario(
        name="steady",
        traffic=TrafficConfig(pattern="poisson", mean_gap_cycles=1_500.0,
                              num_requests=64, seed=7, zipf_s=ZIPF),
        batch_slots=8,
    ),
    ServingScenario(
        name="overload_storm",
        traffic=TrafficConfig(pattern="bursty", mean_gap_cycles=60.0,
                              num_requests=96, seed=23, burst_len=12,
                              zipf_s=ZIPF),
        policy=RobustnessPolicy(admission_watermark=14,
                                deadline_cycles=40_000, max_retries=2,
                                retry_backoff_cycles=3_000.0,
                                degrade_mode="hot_rows_only",
                                degrade_watermark=4, hot_fraction=0.1),
        batch_slots=8,
    ),
)


def _best_of(n: int, fn):
    """Best-of-n wall clock: returns the fastest run's result."""
    return min((fn() for _ in range(n)), key=lambda s: s.wall_seconds)


def run(profile: bool = False) -> List[Dict]:
    wl = dlrm_rmc2_small(num_tables=TABLES, rows_per_table=ROWS, batch_size=BATCH,
                         num_batches=2)
    base_hw = tpuv6e()

    def base_grid(**kw):
        return sweep(wl, base_hw, policies=POLICIES, capacities=CAPACITIES,
                     ways=WAYS, zipf_s=ZIPF, seed=0, **kw)

    # Warm pass compiles every scan shape; the timed passes measure steady
    # state (the regime a DSE study with hundreds of points actually lives
    # in). Best-of-2 like the placement slice — the perf gate compares these
    # numbers across runners.
    base_grid()
    from repro.core.memory import stack as _stack

    dp0 = _stack.distance_pass_count()
    sr = base_grid()
    stack_passes = _stack.distance_pass_count() - dp0
    sr = min(sr, base_grid(), key=lambda s: s.wall_seconds)
    prof = None
    if profile:
        # Separate profiled pass: an active session adds per-stage
        # synchronization (block_until_ready inside the compute stages), so
        # the headline per_config_ms above measures the production path and
        # the breakdown below attributes a dedicated run.
        with profiling.collect() as prof:
            t_prof = time.perf_counter()
            base_grid()
            profiled_wall = time.perf_counter() - t_prof

    # Same grid with per-config scans (no vmapped batching): isolates the
    # batched-classification speedup from trace/matrix sharing.
    base_grid(batch_scans=False)
    sr_nb = _best_of(2, lambda: base_grid(batch_scans=False))

    # NUMA placement-axes slice: the (affinity x placement) grid on a
    # 2-core table_hash cluster, timed separately so the headline
    # per_config_ms (the perf-gate number) keeps its historical grid.
    wl_p = dlrm_rmc2_small(num_tables=PLACEMENT_TABLES, rows_per_table=ROWS,
                           batch_size=BATCH, num_batches=2)
    hw_p = base_hw.with_cluster(2, "private", "table_hash")
    placement_axes = PLACEMENT_AXES
    sweep(wl_p, hw_p, **placement_axes)          # warm
    sr_p = _best_of(2, lambda: sweep(wl_p, hw_p, **placement_axes))

    sample = sr.entries[:: max(1, len(sr.entries) // N_INDEPENDENT_SAMPLE)]
    t0 = time.perf_counter()
    for e in sample:
        c = e.config
        hw = base_hw.with_policy(
            OnChipPolicy(c.policy), capacity_bytes=c.capacity_bytes, ways=c.ways
        )
        ref = simulate(wl, hw, seed=0, zipf_s=c.zipf_s)
        mism = e.result.diff(ref)
        assert not mism, (c.label, mism)
    t_indep = time.perf_counter() - t0
    est_independent_s = t_indep / len(sample) * sr.num_configs

    # Serving slice: steady + overload-with-robustness scenarios swept as
    # first-class axes; timed separately (best-of-2 like the other slices)
    # so the headline per_config_ms keeps its historical fixed-trace grid.
    wl_s = dlrm_rmc2_small(num_tables=SERVING_TABLES,
                           rows_per_table=SERVING_ROWS, batch_size=BATCH,
                           num_batches=2)
    sweep(wl_s, base_hw, scenarios=SERVING_SCENARIOS, **SERVING_AXES)  # warm
    sr_s = _best_of(2, lambda: sweep(wl_s, base_hw,
                                     scenarios=SERVING_SCENARIOS,
                                     **SERVING_AXES))
    best_p99 = sr_s.best("p99_cycles")

    best = sr.best("total_cycles")
    perf_row: Dict = {
        "kind": "perf",
        "configs": sr.num_configs,
        "sweep_s": sr.wall_seconds,
        "per_config_ms": sr.wall_seconds / sr.num_configs * 1e3,
        "est_independent_s": est_independent_s,
        "speedup_vs_independent": est_independent_s / max(sr.wall_seconds, 1e-9),
        "unbatched_sweep_s": sr_nb.wall_seconds,
        "batched_scan_speedup": sr_nb.wall_seconds / max(sr.wall_seconds, 1e-9),
        "cache_backend": base_hw.cache_backend,
        "stack_distance_passes": stack_passes,
        "distinct_memo_keys": sr.distinct_memo_keys,
        # Runner context: the headline grid runs unsharded on one device, and
        # cross-runner trajectory comparisons need to know both.
        "sharded": sr.sharded,
        "device_count": sr.device_count,
        "host_cpus": os.cpu_count() or 1,
        "placement_configs": sr_p.num_configs,
        "placement_per_config_ms": sr_p.wall_seconds / sr_p.num_configs * 1e3,
        "bitexact_sample": len(sample),
        "best_config": best.config.label,
        "best_total_cycles": best.result.total_cycles,
        "serving_configs": sr_s.num_configs,
        "serving_per_config_ms": sr_s.wall_seconds / sr_s.num_configs * 1e3,
        "best_serving_p99_config": best_p99.config.label,
        "best_serving_p99_cycles": best_p99.result.p99_cycles,
        # Failure telemetry (core.faults): all-zero on this fault-free run —
        # nonzero counters in a perf trajectory mean the runner degraded
        # (retries/failovers) and its walls are not comparable.
        "fault_telemetry": sr.telemetry.brief(),
    }
    if profile:
        breakdown = prof.breakdown(total_seconds=profiled_wall)
        perf_row["stage_seconds"] = {k: round(v, 4) for k, v in breakdown.items()}
        perf_row["stage_ms_per_config"] = {
            k: round(v / sr.num_configs * 1e3, 3) for k, v in breakdown.items()
        }
    rows: List[Dict] = [perf_row]
    rows.extend(
        {"kind": "config", **r} for r in sr.speedup_over("spm")
    )
    rows.extend({"kind": "serving", **e.row()} for e in sr_s.entries)
    return rows


def sharded_probe(devices: int) -> Dict:
    """The 96-config grid, unsharded vs sharded over ``devices`` devices of
    this process — raises unless bitwise equal, reports the wall-clock ratio."""
    wl = dlrm_rmc2_small(num_tables=TABLES, rows_per_table=ROWS,
                         batch_size=BATCH, num_batches=2)
    base_hw = tpuv6e()
    sweep(wl, base_hw, **SHARDED_AXES)                       # warm
    ref = _best_of(2, lambda: sweep(wl, base_hw, **SHARDED_AXES))
    sweep(wl, base_hw, devices=devices, **SHARDED_AXES)      # warm
    sh = _best_of(
        2, lambda: sweep(wl, base_hw, devices=devices, **SHARDED_AXES)
    )
    for a, b in zip(ref.entries, sh.entries):
        if a.config != b.config or a.result.diff(b.result):
            raise RuntimeError(f"sharded probe: {b.config.label} differs "
                               f"from unsharded {a.config.label}")
    # The probe runs fault-free: any retry/failover here is a bug in the
    # supervision layer, not runner noise.
    if sh.telemetry.any_faults:
        raise RuntimeError(f"sharded probe: faults {sh.telemetry.to_dict()}")
    return {
        "sharded_fault_telemetry": sh.telemetry.brief(),
        "sharded_configs": sh.num_configs,
        "sharded_distinct_memo_keys": sh.distinct_memo_keys,
        "sharded_device_count": sh.device_count,
        "sharded_bitexact": True,
        "sharded_unsharded_s": ref.wall_seconds,
        "sharded_sweep_s": sh.wall_seconds,
        "sharded_speedup": ref.wall_seconds / max(sh.wall_seconds, 1e-9),
        "sharded_per_config_ms": sh.wall_seconds / sh.num_configs * 1e3,
    }


if __name__ == "__main__":
    import argparse

    import jax

    from benchmarks import common
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--profile", action="store_true",
                    help="add a per-stage wall-time breakdown to the perf row")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the sharded-sweep probe")
    args = ap.parse_args()
    enable_compile_cache()

    bench_rows = run(profile=args.profile)
    perf = next(r for r in bench_rows if r["kind"] == "perf")
    n_devices = jax.device_count()
    if not args.no_sharded and n_devices >= 2:
        perf.update(sharded_probe(n_devices))
    elif not args.no_sharded:
        print(f"sharded probe skipped: {n_devices} device "
              f"({jax.devices()[0].platform}); it needs 2 or more")
    path = common.save_rows("BENCH_sweep", bench_rows, repo_root=True)
    print(f"saved {path}")
    print(f"configs={perf['configs']} sweep_s={perf['sweep_s']:.2f} "
          f"per_config_ms={perf['per_config_ms']:.1f} "
          f"speedup_vs_independent={perf['speedup_vs_independent']:.2f} "
          f"batched_scan_speedup={perf['batched_scan_speedup']:.2f}")
    print(f"serving: {perf['serving_configs']} (hw x scenario) points, "
          f"{perf['serving_per_config_ms']:.1f} ms/config, best p99 "
          f"{perf['best_serving_p99_cycles']:,.0f} cyc "
          f"@ {perf['best_serving_p99_config']}")
    if "sharded_speedup" in perf:
        print(f"sharded: {perf['sharded_configs']} configs on "
              f"{perf['sharded_device_count']} devices "
              f"(host_cpus={perf['host_cpus']}) "
              f"speedup={perf['sharded_speedup']:.2f}x bitexact=True")
    if args.profile:
        for k, v in perf["stage_ms_per_config"].items():
            print(f"  stage {k:<12s} {v:8.2f} ms/config")
