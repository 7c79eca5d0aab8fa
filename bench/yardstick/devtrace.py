"""Reduce a ``jax.profiler`` trace to device busy time, the device programs
that took most time, and the longest idle gaps.

Busy time is the union of the intervals on each device's ``XLA Modules``
line: an interval is one execution of a compiled program, so the device
runs an operation throughout it (the ``XLA Ops`` line agrees to within
0.1% and holds thousands of times as many events, one per loop step). The
window runs from the start of the first unit annotation (``bench_unit_<i>``,
written by the benchmark around each unit) to the end of the last; the
trace only spans those units. The device clock in the trace sits about a
millisecond off the host's, which is why busy time is not clipped to the
window. An idle gap is named by the unit annotation it falls in.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Tuple

UNIT_PREFIX = "bench_unit_"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Modules"
_HASH = re.compile(r"\(\d+\)$")


def read_xplane(path: str) -> dict:
    """``{"ops": {plane: [(start_ns, dur_ns, name)]}, "units": [(start_ns,
    end_ns, name)]}`` from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    units = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [(e.start_ns, e.duration_ns,
                                        _HASH.sub("", e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(UNIT_PREFIX):
                        units.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return {"ops": ops, "units": sorted(units)}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: List[Tuple[float, float]]):
    """Merged ``[start, end)`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(trace: dict, top: int = 10) -> dict:
    """``busy_s`` (mean over devices), ``window_s``, the ``top`` device
    programs by summed time and the ``top`` longest idle gaps within the
    window (first device)."""
    units = trace["units"]
    if not units or not trace["ops"]:
        return {}
    lo, hi = units[0][0], max(u[1] for u in units)
    busy, op_time = [], {}
    gaps = []
    for i, (plane, events) in enumerate(sorted(trace["ops"].items())):
        merged = _union([(s, s + d) for s, d, _ in events])
        busy.append(sum(e - s for s, e in merged))
        for _, d, name in events:
            op_time[name] = op_time.get(name, 0.0) + d
        if i == 0:
            inside = [[max(s, lo), min(e, hi)] for s, e in merged if e > lo and s < hi]
            edges = [lo] + [x for iv in inside for x in iv] + [hi]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    name = next((u[2] for u in units if u[0] <= s < u[1]), "between units")
                    gaps.append((name, (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(busy),
        "device_ops": [[n, t / len(busy) / 1e9] for n, t in ops_top],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
    }
