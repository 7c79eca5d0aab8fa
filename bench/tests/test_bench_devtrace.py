"""The reduction from a device trace to busy time, idle gaps and top ops."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench_tiny import cells  # noqa: F401  (sets the import path)
from yardstick import devtrace

DATA = Path(__file__).resolve().parent / "data"


def test_idle_share_of_recorded_events():
    trace = json.loads((DATA / "idle_events.json").read_text())
    trace["units"] = [tuple(u) for u in trace["units"]]
    out = devtrace.reduce(trace)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(550e-9)
    assert out["devices"] == 2
    assert out["idle_gaps"] == [["bench_unit_1", pytest.approx(300e-9)],
                                ["bench_unit_0", pytest.approx(200e-9)],
                                ["bench_unit_0", pytest.approx(100e-9)]]
    assert [op for op, _ in out["device_ops"]] == ["scan", "copy", "sort"]
    idle = cells.load_metric("device_idle_share")

    class Obs:
        device = out
    assert idle.read(Obs) == pytest.approx(45.0)


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: 3 units, each 4 runs of a 1024 x
    1024 matmul program of 11.86 us and a 20 ms sleep."""
    trace = devtrace.read_xplane(str(DATA / "small.xplane.pb"))
    assert [u[2] for u in trace["units"]] == ["bench_unit_0", "bench_unit_1", "bench_unit_2"]
    out = devtrace.reduce(trace)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(142348e-9)
    assert out["window_s"] == pytest.approx((120617704 - 48998893) * 1e-9)
    assert out["device_ops"] == [["jit__lambda", pytest.approx(142348e-9)]]
    assert [g[0] for g in out["idle_gaps"][:3]] == ["bench_unit_2", "bench_unit_1", "bench_unit_0"]


def test_no_units_or_no_device_reads_nothing():
    assert devtrace.reduce({"ops": {}, "units": [(0, 10, "bench_unit_0")]}) == {}
    assert devtrace.reduce({"ops": {"/device:TPU:0": [(0, 5, "x")]}, "units": []}) == {}
