"""The reference's DLRM: ``num_tables`` equal tables of ``rows_per_table``
vectors of ``dim`` elements, ``lookups`` rows of each table per sample,
pooled by ``vector_op``, beside a bottom and a top MLP and the feature
interaction. Pure numpy; the configuration file's ``workload`` holds every
number."""
from __future__ import annotations

from typing import List

import numpy as np

from yardstick import reference


def spec(cfg: dict) -> dict:
    """Table count, rows, vector bytes, lookups per sample and batching."""
    w = cfg["workload"]
    return dict(tables=w["num_tables"], rows=w["rows_per_table"],
                dim=w["dim"], dtype_bytes=w["dtype_bytes"],
                lookups=w["lookups"], pooling=w["vector_op"],
                batch=w["batch_size"], num_batches=w["num_batches"])


def line_stream(cfg: dict, zipf_s: float, seed: int) -> dict:
    """Every line access of the unit, in order, with its batch and sample."""
    return reference.table_stream(spec(cfg), zipf_s, seed,
                                  cfg["hardware"]["onchip"]["line_bytes"])


def batch_lines(cfg: dict, seed: int) -> List[int]:
    """Line accesses of each batch: the same for every batch and seed."""
    return reference.table_batch_lines(spec(cfg), cfg["hardware"]["onchip"]["line_bytes"])


def vector_work(cfg: dict, stream: dict, in_batch: np.ndarray, core: np.ndarray,
                cores: int):
    """``(vector ops, vector cycles)`` of one batch: the pooling of each
    sample's lookups, split over the cores by the lookups each serves; the
    slowest core sets the cycles."""
    s, hw = spec(cfg), cfg["hardware"]
    pool_flops = (s["batch"] * s["tables"] * max((s["lookups"] - 1) * s["dim"], 0)
                  if s["pooling"] in ("sum", "mean") else 0)
    vector_cycles = pool_flops / max(hw["vector_lanes"] * hw["vector_sublanes"], 1)
    if cores == 1:
        return pool_flops, vector_cycles
    lookups = np.bincount(core[in_batch], minlength=cores) // stream["lpv"]
    return pool_flops, max(vector_cycles * lookups[k] / max(lookups.sum(), 1)
                           for k in range(cores))


def matrix_ops(cfg: dict) -> List[tuple]:
    """``(m, n, k, dtype_bytes, count)`` of every matrix op, in model order:
    bottom MLP, feature interaction, top MLP."""
    w = cfg["workload"]
    b, db = w["batch_size"], w["mlp_dtype_bytes"]
    ops = []
    d = w["dense_features"]
    for out in w["bottom_mlp"]:
        ops.append((b, out, d, db, 1))
        d = out
    n_vec = w["num_tables"] + 1
    ops.append((b * n_vec, n_vec, w["dim"], db, 1))
    d = n_vec * (n_vec - 1) // 2 + w["dim"]
    for out in w["top_mlp"]:
        ops.append((b, out, d, db, 1))
        d = out
    return ops
