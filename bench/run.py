"""Run one benchmark cell once on the chips it names.

    python bench/run.py --workload dlrm_t1.grid24 --seed 7 --seconds 30 --trace 0

A cell of ``BENCHMARK.json`` pairs a configuration (``bench/configs``) with
a traffic mix (``bench/traffic``). A unit is one ``sweep()`` over the mix's
whole grid on a trace of its own, drawn from ``--seed`` and the unit's
index. Set-up sets glibc to keep freed memory (``keep_freed_memory``),
loads the compile cache and runs one warm-up unit for each of the mix's
``warmup_seeds``, which the window never draws; the window then
runs whole units back to back until ``--seconds`` have passed and finishes
the unit in flight. A trace whose bucketed shapes the warm-up did not meet
compiles inside the window, and the count is logged.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` its
per-layer metrics (``bench/metrics``): stage spans over the first half of
the window and a device trace of the second half. Either way the answers of
the window are checked against the plain reference (``yardstick/check.py``)
after it closes. The last line of standard output is one JSON object; the
numbers compared, with their limits, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell needs, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from yardstick import cells, check, devtrace  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int):
    """The first ``n`` TPU devices; exits non-zero when there are fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


M_TRIM_THRESHOLD, M_TOP_PAD, M_MMAP_THRESHOLD = -1, -2, -3     # glibc mallopt


def keep_freed_memory() -> None:
    """Have glibc serve arrays of up to 32 MB from its heap and keep what
    is freed there for reuse. The simulator's host stages allocate and free
    large numpy temporaries in every call; by default glibc maps and unmaps
    many of them, and each round trip faults its pages in afresh, at a cost
    that swings from process to process where the kernel is a user-space
    sandbox, as on the TPU v5e hosts this benchmark was measured on.
    """
    libc = ctypes.CDLL("libc.so.6")
    for param, value in ((M_MMAP_THRESHOLD, 32 << 20), (M_TRIM_THRESHOLD, 2**31 - 1),
                         (M_TOP_PAD, 256 << 20)):
        if libc.mallopt(param, value) != 1:
            raise RuntimeError(f"mallopt({param}, {value}) refused")


def release_freed_memory() -> None:
    """Hand what the heap holds free back to the system (``malloc_trim``)."""
    ctypes.CDLL("libc.so.6").malloc_trim(0)


def rss_bytes() -> int:
    """Resident memory of this process now."""
    return int(Path("/proc/self/statm").read_text().split()[1]) * os.sysconf("SC_PAGE_SIZE")


class RssPeak:
    """Peak resident memory over a stretch of the run, sampled every
    ``period`` seconds by a thread of its own, so that what set-up held
    (compiling above all) stays out of the reading."""

    def __init__(self, period: float = 0.02):
        self.period, self.peak = period, 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True)

    def _sample(self) -> None:
        while not self._stop.wait(self.period):
            self.peak = max(self.peak, rss_bytes())

    def start(self) -> None:
        self.peak = rss_bytes()
        self._thread.start()

    def stop(self) -> float:
        """The peak in GB."""
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, rss_bytes())
        return self.peak / 1e9


@dataclass
class Window:
    """Units run back to back, with their answers and seeds."""

    answers: list = field(default_factory=list)
    seeds: list = field(default_factory=list)
    unit_seconds: list = field(default_factory=list)
    configs: int = 0
    failed: int = 0
    seconds: float = 0.0


def run_window(unit, cell, run_seed: int, seconds: float, win: Window) -> float:
    """Run units until ``seconds`` have passed, then finish the one in
    flight; returns the wall seconds. Each unit sits in a profiler
    annotation named for its index."""
    import jax

    grid = len(cell.grid())
    t0 = time.perf_counter()
    while True:
        index = len(win.seeds)
        seed = cell.unit_seed(run_seed, index)
        t_unit = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"{devtrace.UNIT_PREFIX}{index}"):
            try:
                win.answers.append(unit(seed))
            except Exception:                      # a failed unit is counted
                log(traceback.format_exc())
                win.answers.append({})
                win.failed += grid
        win.unit_seconds.append(time.perf_counter() - t_unit)
        win.seeds.append(seed)
        win.configs += grid
        if time.perf_counter() - t0 >= seconds:
            return time.perf_counter() - t0


@dataclass
class Observation:
    """What the per-layer readers read."""

    stage_seconds: dict = None
    stage_configs: int = 0
    device: dict = None


def measure(cell, run_seed: int, seconds: float, trace: bool) -> dict:
    """One run of ``cell``: the result object the last line prints, with
    the compared numbers under ``checks``."""
    import jax

    devices = require_chips(cell.chips)
    log(f"devices up: rss_gb={rss_bytes() / 1e9!r}")
    from repro.core import profiling
    from yardstick import program

    clock = program.CompileClock()
    unit = program.Unit(cell, devices=cell.chips)
    warmup = cell.traffic["warmup_seeds"]
    try:
        unit(warmup[0])             # compiles, in a checkout's first run
        release_freed_memory()      # so what compiling held stays out of the RSS peak
        for seed in warmup[1:]:
            unit(seed)
    except Exception:           # the window's units fail too, and are counted
        log(traceback.format_exc())
    setup_s = time.perf_counter() - T0
    log(f"jax {jax.__version__} device_kind={devices[0].device_kind!r} "
        f"devices={len(devices)} setup_s={setup_s!r} "
        f"setup compiles={clock.snapshot()} rss_gb={rss_bytes() / 1e9!r}")

    win, obs = Window(), Observation()
    rss = RssPeak()
    rss.start()
    c0 = clock.snapshot()
    if not trace:
        win.seconds = run_window(unit, cell, run_seed, seconds, win)
    else:
        needs = {m["reader"].NEEDS for m in cell.per_layer}
        split = seconds / len(needs) if needs else seconds
        if "stages" in needs:
            with profiling.collect() as prof:
                win.seconds += run_window(unit, cell, run_seed, split, win)
            obs.stage_seconds, obs.stage_configs = dict(prof.seconds), win.configs
            log(f"stages (s, exclusive) over {win.configs} configs: {prof.breakdown()}")
        if "device_trace" in needs:
            with tempfile.TemporaryDirectory() as tmp:
                jax.profiler.start_trace(tmp)
                try:
                    win.seconds += run_window(unit, cell, run_seed, split, win)
                finally:
                    jax.profiler.stop_trace()
                obs.device = devtrace.reduce(devtrace.read_xplane(devtrace.find_xplane(tmp)))
            log(f"device trace: {json.dumps(obs.device)}")
    rss_gb = rss.stop()
    c1 = clock.snapshot()
    log(f"window: units={len(win.seeds)} configs={win.configs} failed={win.failed} "
        f"seconds={win.seconds!r} compiles={c1[0] - c0[0]} compile_s={c1[1] - c0[1]!r} "
        f"cache_hits={c1[2] - c0[2]} cache_misses={c1[3] - c0[3]} "
        f"unit_seconds={[round(t, 3) for t in win.unit_seconds]}")

    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats)}
    if trace and obs.device:
        device.update(busy_s=obs.device["busy_s"], window_s=obs.device["window_s"])
    del unit
    gc.collect()

    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = m["reader"].read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        done = win.configs - win.failed
        values = {"setup_s": setup_s, "configs_per_s": done / win.seconds,
                  "host_rss_peak_gb": rss_gb}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    t_check = time.perf_counter()
    nums = check.numbers(cell, win.answers, win.seeds, run_seed)
    log(f"reference check took {time.perf_counter() - t_check!r} s")
    out = {"correct": check.passed(nums) and win.failed == 0,
           "attempted": win.configs, "failed": win.failed,
           "metrics": metrics, "device": device}
    if trace and obs.device:
        out["breakdown"] = {"device_ops": obs.device["device_ops"],
                            "idle_gaps": obs.device["idle_gaps"]}
    out["checks"] = nums
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    keep_freed_memory()
    # The compile cache lives in the checkout at a fixed path, whatever the
    # environment names, so two checkouts measured side by side share
    # nothing; JAX reads the variable when it is first imported.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    out = measure(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    for name, n in out["checks"].items():
        log(f"check {name} {n['value']!r} limit {n['limit']!r}")


if __name__ == "__main__":
    main()
