"""Smoke test of the simulator's main path on a TPU, at the paper's Table I widths.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

One process drives every phase, and it needs a TPU: on any other platform it
exits non-zero before simulating anything. The workload is the paper's
Table I DLRM (60 tables x 1M rows x dim 128 fp32, 120 lookups per table) at
batch 32 over 4 batches, about 7.4M line accesses.

Phases on one chip:

  a. simulate:  ``repro.launch.simulate.run``, the CLI's path, for spm, lru,
     srrip and pinning at every (capacity, ways) of the base grid
     (``benchmarks/dse_sweep.py``);
  b. sweep:     ``sweep()`` over the same axes; every entry must be bitwise
     equal to phase a's result for its config (``SimResult.diff``);
  c. pallas:    ``cache_backend="pallas"`` (lru, srrip, fifo) and
     ``"stack_pallas"`` (lru) at one geometry, each bitwise equal to
     ``"stack"``, with both kernels compiled for the chip
     (``tpu_custom_call`` in the lowered program), not interpreted;
  d. reference: every count and cycle total of phases a and c against
     ``chip_smoke_reference.json``, which
     ``scripts/write_chip_smoke_reference.py`` writes on the CPU from the
     same code, seed and sizes.

``--chips 4`` runs ``sweep(devices=4)`` against ``sweep(devices=1)`` over the
sharded grid under strict fault tolerance: bitwise equality, zero fault
telemetry, and each shard's device arrays on its own chip.

Earlier lines report the JAX version, the device, the stack engine, and per
phase the compile and wall seconds, the config count and "match". The last
line of standard output is one JSON object, printed only when every phase
passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.dse_sweep import CAPACITIES, POLICIES, SHARDED_AXES, WAYS  # noqa: E402
from repro.core import FaultTolerance, SimResult, dlrm_rmc2_small, sweep, tpuv6e  # noqa: E402
from repro.core.memory import dram, stack  # noqa: E402
from repro.kernels.cache_scan import cache_scan_groups  # noqa: E402
from repro.kernels.stack_distance import stack_distance_groups  # noqa: E402
from repro.launch import simulate as simulate_cli  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

REFERENCE = ROOT / "chip_smoke_reference.json"
SEED = 0                       # the CLI's simulate() seed; the sweep uses it too
ZIPF = simulate_cli.parser().get_default("zipf")
PALLAS_RUNS = (("pallas", "lru"), ("pallas", "srrip"), ("pallas", "fifo"),
               ("stack_pallas", "lru"))
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Size:
    """DLRM workload size; the defaults are the paper's Table I."""

    tables: int = 60
    rows: int = 1_000_000
    lookups: int = 120
    batch: int = 32
    num_batches: int = 4

    def cli_args(self) -> list:
        return ["--tables", str(self.tables), "--rows", str(self.rows),
                "--lookups", str(self.lookups), "--batch", str(self.batch),
                "--num-batches", str(self.num_batches)]

    def workload(self):
        return dlrm_rmc2_small(
            num_tables=self.tables, rows_per_table=self.rows,
            lookups=self.lookups, batch_size=self.batch,
            num_batches=self.num_batches,
        )


@dataclasses.dataclass(frozen=True)
class Grid:
    """Sweep axes; the defaults are the benchmark's base grid."""

    policies: tuple = POLICIES
    capacities: tuple = CAPACITIES
    ways: tuple = WAYS

    def configs(self):
        return [(p, c, w) for p in self.policies for c in self.capacities
                for w in self.ways]


TABLE_I = Size()
BASE_GRID = Grid()
PALLAS_GEOMETRY = (CAPACITIES[0], 16)      # 1 MB, 16 ways


def config_name(policy: str, capacity: int, ways: int) -> str:
    return f"{policy}/{capacity}/{ways}"


def simulate_config(size: Size, policy: str, capacity: int, ways: int,
                    backend: "str | None" = None) -> SimResult:
    """One ``python -m repro.launch.simulate`` run, as a call."""
    argv = size.cli_args() + ["--policy", policy, "--capacity-bytes",
                              str(capacity), "--ways", str(ways)]
    if backend is not None:
        argv += ["--cache-backend", backend]
    return simulate_cli.run(simulate_cli.parser().parse_args(argv))


def phase_simulate(size: Size, grid: Grid) -> dict:
    """a. ``{config name: SimResult}`` for every config of ``grid``."""
    return {config_name(*c): simulate_config(size, *c) for c in grid.configs()}


def phase_sweep(size: Size, grid: Grid, simulated: dict) -> list:
    """b. Mismatches between one ``sweep()`` and phase a's results."""
    sr = sweep(size.workload(), tpuv6e(), policies=grid.policies,
               capacities=grid.capacities, ways=grid.ways, zipf_s=ZIPF,
               seed=SEED)
    problems = []
    if sr.num_configs != len(simulated):
        problems.append(f"sweep has {sr.num_configs} configs, "
                        f"simulate {len(simulated)}")
    for e in sr.entries:
        c = e.config
        name = config_name(c.policy, c.capacity_bytes, c.ways)
        if name not in simulated:
            problems.append(f"{name}: not simulated")
        elif diff := e.result.diff(simulated[name]):
            problems.append(f"{name}: {diff}")
    return problems


def phase_pallas(size: Size, simulated: dict, geometry=PALLAS_GEOMETRY):
    """c. ``({run name: SimResult}, mismatches)`` of the Pallas backends
    against the stack engine at one geometry: phase a's result where it has
    the config, a ``stack/<policy>`` run otherwise."""
    results, problems = {}, []
    for backend, policy in PALLAS_RUNS:
        stack_name = f"stack/{policy}"
        ref = simulated.get(config_name(policy, *geometry)) or results.get(stack_name)
        if ref is None:
            ref = results[stack_name] = simulate_config(
                size, policy, *geometry, backend="stack")
        got = results[f"{backend}/{policy}"] = simulate_config(
            size, policy, *geometry, backend=backend)
        if diff := got.diff(ref):
            problems.append(f"{backend}/{policy}: {diff}")
    return results, problems


def kernels_compiled(num_sets: int = 16, ways: int = 16) -> dict:
    """``{kernel: lowered for the chip?}``: lowers both kernel entry points
    as the cache engine calls them (interpret mode left to the platform) and
    looks for the Mosaic custom call, which interpret mode never emits."""
    x = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
    fns = {f"pallas/{p}": (lambda s, t, v, p=p: cache_scan_groups(
        s, t, v, num_sets, ways, p)) for p in ("lru", "srrip", "fifo")}
    fns["stack_pallas/lru"] = lambda s, t, v: stack_distance_groups(
        s, t, v, num_sets, ways)
    return {name: "tpu_custom_call" in jax.jit(fn).lower(x, x, x).as_text()
            for name, fn in fns.items()}


def result_record(res: SimResult) -> dict:
    """Every count and cycle total of a result, as JSON would carry it."""
    return json.loads(res.to_json())


def reference_payload(size: Size, results: dict) -> dict:
    return {"size": dataclasses.asdict(size), "seed": SEED, "zipf": ZIPF,
            "results": {k: result_record(v) for k, v in sorted(results.items())}}


def _diff_records(a, b, path=""):
    if isinstance(a, dict) and isinstance(b, dict):
        for k in sorted(set(a) | set(b)):
            yield from _diff_records(a.get(k), b.get(k), f"{path}.{k}")
    elif isinstance(a, list) and isinstance(b, list) and len(a) == len(b):
        for i, (x, y) in enumerate(zip(a, b)):
            yield from _diff_records(x, y, f"{path}[{i}]")
    elif a != b or type(a) is not type(b):
        yield f"{path}: here {a!r}, reference {b!r}"


def compare_reference(size: Size, results: dict, reference: dict,
                      geometry=PALLAS_GEOMETRY) -> list:
    """d. Mismatches between ``results`` (``{config or run name:
    SimResult}``) and the CPU reference. A Pallas run is held to the
    reference of its stack config."""
    want = reference_payload(size, {})
    problems = [f"reference {k} {reference.get(k)!r}, here {want[k]!r}"
                for k in ("size", "seed", "zipf") if reference.get(k) != want[k]]
    ref = reference["results"]
    for name, res in sorted(results.items()):
        backend, policy = name.split("/")[:2]
        key = (config_name(policy, *geometry)
               if backend in ("pallas", "stack_pallas", "stack") else name)
        if key not in ref:
            problems.append(f"{name}: no reference {key}")
            continue
        problems += [f"{name}{d}" for d in _diff_records(result_record(res), ref[key])]
    return problems


class CompileClock:
    """Seconds JAX spent compiling (or loading compiled programs from its
    persistent cache), and the cache's hits and misses."""

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def report(name: str, clock: CompileClock, t0: float, c0, configs: int,
           problems: list) -> bool:
    s, h, m = (a - b for a, b in zip(clock.snapshot(), c0))
    verdict = "match" if not problems else f"MISMATCH ({len(problems)})"
    print(f"phase {name}: configs={configs} compile_s={s!r} cache_hits={h} "
          f"cache_misses={m} wall_s={time.perf_counter() - t0!r} {verdict}",
          flush=True)
    for p in problems[:20]:
        print(f"  {p}", flush=True)
    return not problems


def record_devices(seen: dict):
    """Wrap the engines' device passes so each call notes the devices its
    outputs sit on, keyed by the calling thread (one per sweep shard).
    Returns a function that restores the originals."""
    originals = [(dram, "_scan_channel_chunked"), (stack, "_stack_pass_jnp")]
    saved = [(mod, name, getattr(mod, name)) for mod, name in originals]

    def wrap(fn):
        def recorded(*args, **kwargs):
            out = fn(*args, **kwargs)
            devs = {d for leaf in jax.tree.leaves(out) for d in leaf.devices()}
            seen.setdefault(threading.current_thread().name, set()).update(devs)
            return out
        return recorded

    for mod, name, fn in saved:
        setattr(mod, name, wrap(fn))
    return lambda: [setattr(mod, name, fn) for mod, name, fn in saved]


def phase_sharded(size: Size, axes: dict, devices: int):
    """``sweep(devices=n)`` against ``sweep(devices=1)``: ``(configs,
    problems)``, the problems being mismatches, fault telemetry, and shards
    that did not run on a device of their own."""
    wl, hw = size.workload(), tpuv6e()
    ref = sweep(wl, hw, devices=1, **axes)
    seen: dict = {}
    restore = record_devices(seen)
    try:
        sh = sweep(wl, hw, devices=devices,
                   fault_tolerance=FaultTolerance(strict=True), **axes)
    finally:
        restore()
    problems = []
    if sh.telemetry.any_faults:
        problems.append(f"fault telemetry {sh.telemetry.brief()}")
    if sh.num_configs != ref.num_configs:
        problems.append(f"{sh.num_configs} configs sharded, {ref.num_configs} not")
    for a, b in zip(ref.entries, sh.entries):
        if a.config != b.config:
            problems.append(f"config order {a.config.label} / {b.config.label}")
        elif diff := a.result.diff(b.result):
            problems.append(f"{a.config.label}: {diff}")
    shards = {k: v for k, v in seen.items() if k.startswith("sweep-shard-")}
    for name, devs in sorted(shards.items()):
        print(f"  {name}: arrays on {sorted(str(d) for d in devs)}", flush=True)
    per_shard = [next(iter(d)) for d in shards.values() if len(d) == 1]
    if len(shards) != devices or len(per_shard) != devices \
            or len(set(per_shard)) != devices:
        problems.append(f"{len(shards)} shards ran device passes on "
                        f"{[sorted(map(str, d)) for d in shards.values()]}, "
                        f"want one distinct device each for {devices}")
    return sh.num_configs, problems


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded sweep, across four chips")
    args = ap.parse_args(argv)
    dev = require_tpu()
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"jax {jax.__version__} device_kind={dev.device_kind!r} "
          f"devices={jax.device_count()} stack_engine={stack._default_engine()} "
          f"compile_cache={cache_dir}", flush=True)
    ok = True

    if args.chips == 4:
        if jax.device_count() != 4:
            sys.exit(f"chip_smoke: --chips 4 needs 4 devices, JAX found "
                     f"{jax.device_count()}")
        size = dataclasses.replace(TABLE_I, num_batches=1)
        t0, c0 = time.perf_counter(), clock.snapshot()
        n, problems = phase_sharded(size, SHARDED_AXES, devices=4)
        ok = report("sharded (devices=4 vs 1)", clock, t0, c0, n, problems)
    else:
        t0, c0 = time.perf_counter(), clock.snapshot()
        simulated = phase_simulate(TABLE_I, BASE_GRID)
        ok &= report("a simulate", clock, t0, c0, len(simulated), [])

        t0, c0 = time.perf_counter(), clock.snapshot()
        problems = phase_sweep(TABLE_I, BASE_GRID, simulated)
        ok &= report("b sweep", clock, t0, c0, len(simulated), problems)

        t0, c0 = time.perf_counter(), clock.snapshot()
        pallas, problems = phase_pallas(TABLE_I, simulated)
        compiled = kernels_compiled()
        print(f"  kernels compiled for the chip: {compiled}", flush=True)
        problems += [f"{k}: interpreted" for k, v in compiled.items() if not v]
        ok &= report("c pallas", clock, t0, c0, len(pallas), problems)

        t0, c0 = time.perf_counter(), clock.snapshot()
        reference = json.loads(REFERENCE.read_text())
        results = {**simulated, **pallas}
        problems = compare_reference(TABLE_I, results, reference)
        ok &= report("d reference", clock, t0, c0, len(results), problems)

    if not ok:
        sys.exit("chip_smoke: a phase failed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
