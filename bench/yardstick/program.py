"""The system under test, driven as its users drive it: one ``sweep()`` per
unit on a fresh trace. This module and the workload constructors in
``bench/programs`` are the benchmark's code that drives the simulator
(``src/repro``); ``run.py`` takes only its stage profiler and compile cache
from it."""
from __future__ import annotations

import json
from typing import Dict, List

import jax

from repro.core import sweep

from . import cells, reference

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Unit:
    """Runs one unit: the cell's whole grid through one ``sweep()`` call."""

    def __init__(self, cell, devices: int):
        self.cell = cell
        self.devices = devices
        constructor = cells.load_program(cell.config["program"]["workload"])
        self.workload, self.hardware = constructor.build(cell.config)
        t = cell.traffic
        self.axes = dict(policies=tuple(t["policies"]),
                         capacities=tuple(t["capacities"]),
                         ways=tuple(t["ways"]), zipf_s=tuple(t["zipf_s"]),
                         num_cores=tuple(t["num_cores"]))

    def __call__(self, seed: int) -> Dict[tuple, dict]:
        """``{config key: SimResult record}`` of the unit drawn from ``seed``."""
        res = sweep(self.workload, self.hardware, seed=seed,
                    devices=self.devices if self.devices > 1 else None,
                    **self.axes)
        out = {}
        for e in res.entries:
            c = e.config
            key = reference.config_key(dict(
                policy=c.policy, capacity_bytes=c.capacity_bytes, ways=c.ways,
                zipf_s=c.zipf_s, num_cores=c.num_cores))
            out[key] = json.loads(e.result.to_json())
        return out


class CompileClock:
    """Backend compiles (count and seconds) and persistent-cache hits and
    misses, through ``jax.monitoring``."""

    def __init__(self):
        self.compiles, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> List:
        return [self.compiles, self.seconds, self.hits, self.misses]
