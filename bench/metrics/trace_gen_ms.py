"""Exclusive host milliseconds per evaluated configuration in the ``trace_gen``
stage: index-trace generation, expansion and line translation (core/trace.py, core/workload.py).

Read from ``repro.core.profiling.collect()`` around the traced run's
stage-profiled units; an open session blocks on device work inside each
stage, so the number is only taken in the traced run.
"""
NEEDS = "stages"


def read(obs):
    seconds = (obs.stage_seconds or {}).get("trace_gen")
    if seconds is None or not obs.stage_configs:
        return None
    return 1000.0 * seconds / obs.stage_configs
