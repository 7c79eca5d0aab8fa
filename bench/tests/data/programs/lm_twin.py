"""The test-only kind ``lm_twin``'s workload constructor: ``lm_workload`` of
the architecture ``program`` names, at a decode shape of the configuration
file's own ``batch_size`` and ``seq_len``, so that a test can cut the batch."""
from __future__ import annotations

from repro.core import hardware
from repro.core.lm_mapper import lm_workload
from repro.models import get_config
from repro.models.config import ShapeConfig


def build(cfg: dict):
    """``(workload, hardware)`` that the configuration file names."""
    prog, w = cfg["program"], cfg["workload"]
    shape = ShapeConfig(f"decode_b{w['batch_size']}", w["seq_len"], w["batch_size"], "decode")
    wl = lm_workload(get_config(prog["arch"]), shape, num_batches=w["num_batches"])
    return wl, getattr(hardware, prog["hardware"])()
