"""``repro.core.workload.dlrm_rmc2_small`` at the sizes of the configuration
file's ``workload``, on the hardware preset its ``program`` names."""
from __future__ import annotations

from repro.core import hardware, workload


def build(cfg: dict):
    """``(workload, hardware)`` that the configuration file names."""
    w = cfg["workload"]
    wl = workload.dlrm_rmc2_small(
        num_tables=w["num_tables"], rows_per_table=w["rows_per_table"],
        dim=w["dim"], lookups=w["lookups"], batch_size=w["batch_size"],
        num_batches=w["num_batches"])
    return wl, getattr(hardware, cfg["program"]["hardware"])()
