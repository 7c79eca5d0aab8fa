"""The charge of the device's idle time to the host span open on the unit
thread: recorded events, a trace recorded on the chip, and what the shares
read where there is nothing to charge.

Run as a script on a TPU, this file records the chip trace its test reads:

    python bench/tests/test_bench_spans.py <log dir>
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

from bench_tiny import cells  # noqa: F401  (sets the import path)
from yardstick import devtrace, spans

DATA = Path(__file__).resolve().parent / "data"


class Obs:
    def __init__(self, device=None):
        self.device = device


def _events():
    trace = json.loads((DATA / "span_events.json").read_text())
    trace["lines"] = [[tuple(sp) for sp in line] for line in trace["lines"]]
    return trace


def test_idle_charged_to_innermost_span():
    trace = _events()
    out = spans.reduce(trace)
    assert out["window_s"] == pytest.approx(1000e-9)
    want = dict.fromkeys(spans.LABELS, 0.0)
    want.update(trace_gen=100, classify=150, stack_distance=100, unstaged=110,
                compile=50, dram=230, host_sync=60)
    assert out["idle_s"] == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert out["compiles"] == {"count": 1, "seconds": pytest.approx(100e-9),
                               "by_function": {"_scan_channel_chunked": [1, pytest.approx(100e-9)]},
                               "by_stage": {"dram": [1, pytest.approx(100e-9)]}}


def test_shares_partition_the_device_idle_share():
    trace = _events()
    units, _ = spans.unit_thread(trace["lines"])
    device = devtrace.reduce({"ops": trace["ops"], "units": units})
    shares = spans.shares(spans.reduce(trace))
    assert set(shares) == set(spans.LABELS)
    assert shares["unstaged"] == pytest.approx(11.0)
    idle = cells.load_metric("device_idle_share").read(Obs(device))
    assert idle == pytest.approx(80.0)
    assert sum(shares.values()) == pytest.approx(idle)


def test_compile_outside_any_stage_or_jitted_call():
    trace = {"ops": {"/device:TPU:0": [(0, 10, "x")]},
             "lines": [[(0, 100, "bench_unit_0"), (0, 30, "stage.classify"),
                        (40, 90, "backend_compile")]]}
    out = spans.reduce(trace)
    assert out["compiles"] == {"count": 1, "seconds": pytest.approx(50e-9),
                               "by_function": {"?": [1, pytest.approx(50e-9)]},
                               "by_stage": {"unstaged": [1, pytest.approx(50e-9)]}}
    assert out["idle_s"]["classify"] == pytest.approx(20e-9)
    assert out["idle_s"]["compile"] == pytest.approx(50e-9)
    assert out["idle_s"]["unstaged"] == pytest.approx(20e-9)


@pytest.mark.parametrize("trace", [
    {"ops": {}, "lines": [[(0, 10, "bench_unit_0"), (0, 5, "stage.dram")]]},
    {"ops": {"/device:TPU:0": [(0, 5, "x")]}, "lines": [[(0, 10, "stage.dram")]]},
    {"ops": {"/device:TPU:0": [(0, 5, "x")]},
     "lines": [[(0, 10, "bench_unit_0"), (2, 4, "backend_compile")]]},
], ids=["no_device", "no_unit", "no_stage_span"])
def test_nothing_to_charge_reads_nothing(trace):
    assert spans.reduce(trace) == {}
    assert spans.shares(spans.reduce(trace)) == {}


def test_recorded_chip_trace_charges_each_sleep_to_its_span():
    """A trace recorded on one TPU v5e by ``record`` below: the eight
    programs (0.71-0.80 ms each) were dispatched inside the 7.15 ms
    ``stage.dram`` span, and the device sat idle through the two sleeps.
    The device clock sits about a millisecond off the host's, so the
    charges and ``devtrace``'s idle time agree to within that."""
    trace = spans.read_xplane(str(DATA / "stage_spans.xplane.pb"))
    units, unit_spans = spans.unit_thread(trace["lines"])
    assert [u[2] for u in units] == ["bench_unit_0"]
    assert [n for _, _, n in unit_spans if n.startswith("stage.")] == ["stage.dram",
                                                                       "stage.trace_gen"]
    out = spans.reduce(trace)
    idle = out["idle_s"]
    assert idle["trace_gen"] == pytest.approx(0.020, abs=1e-3)
    assert idle["unstaged"] == pytest.approx(0.030, abs=1e-3)
    assert idle["dram"] < 2e-3
    assert out["compiles"]["count"] == 0
    device = devtrace.reduce({"ops": trace["ops"], "units": units})
    assert sum(idle.values()) == pytest.approx(device["window_s"] - device["busy_s"], abs=1e-3)


def matmul(x):
    return x @ x / x.shape[0]


def record(log_dir: str) -> None:
    """A trace on one chip: one unit holding eight runs of a 4096 x 4096
    float32 matmul program under ``stage("dram")``, a 20 ms sleep under
    ``stage("trace_gen")`` and a 30 ms sleep under no stage. The program
    compiles before the trace opens, and the Python tracer is off, so the
    file stays small."""
    import jax
    import jax.numpy as jnp

    from repro.core.profiling import stage

    program = jax.jit(matmul)
    x = jnp.ones((4096, 4096), jnp.float32)
    program(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(f"{devtrace.UNIT_PREFIX}0"):
        with stage("dram"):
            for _ in range(8):
                x = program(x)
            x.block_until_ready()
        with stage("trace_gen"):
            time.sleep(0.02)
        time.sleep(0.03)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    record(sys.argv[1])
