"""A test-only workload kind whose line accesses differ from batch to batch
and from seed to seed, as routed traffic's do. It gives what
``check.identities`` reads (``batch_lines``, ``matrix_ops``) and nothing
else."""
from __future__ import annotations

from typing import List


def batch_lines(cfg: dict, seed: int) -> List[int]:
    """Line accesses of each batch of the unit drawn from ``seed``."""
    return [64 * (1 + (seed + 3 * b) % 5) for b in range(cfg["workload"]["num_batches"])]


def matrix_ops(cfg: dict) -> List[tuple]:
    """``(m, n, k, dtype_bytes, count)``: one router-sized projection."""
    return [(cfg["workload"]["batch_size"], 16, 128, 2, 1)]
