"""``repro.core.lm_mapper.lm_workload`` of the architecture and shape the
configuration file's ``program`` names, with its ``workload.num_batches``,
on the hardware preset ``program`` names."""
from __future__ import annotations

from repro.core import hardware
from repro.core.lm_mapper import lm_workload
from repro.models import SHAPES_BY_NAME, get_config


def build(cfg: dict):
    """``(workload, hardware)`` that the configuration file names."""
    prog = cfg["program"]
    wl = lm_workload(get_config(prog["arch"]), SHAPES_BY_NAME[prog["shape"]],
                     num_batches=cfg["workload"]["num_batches"])
    return wl, getattr(hardware, prog["hardware"])()
