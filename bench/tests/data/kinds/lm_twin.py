"""A test-only workload kind, added as new files alone: the dense decoder of
``bench/kinds/lm.py`` under another name. Pure numpy."""
from __future__ import annotations

from typing import List

import numpy as np

from yardstick import reference


def spec(cfg: dict) -> dict:
    """Table count, rows, vector bytes, lookups per sample and batching."""
    a, w = cfg["architecture"], cfg["workload"]
    return dict(tables=1, rows=a["vocab_size"], dim=a["hidden_size"],
                dtype_bytes=w["dtype_bytes"], lookups=1,
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
    """``(vector ops, vector cycles)`` of one batch: none, rows are
    concatenated."""
    return 0, 0.0


def matrix_ops(cfg: dict) -> List[tuple]:
    """``(m, n, k, dtype_bytes, count)`` of every matrix op, in model order:
    per layer the Q, K, V and O projections, the causal half of the scores
    and of the weighted values, the SwiGLU FFN; then the logits."""
    a, w = cfg["architecture"], cfg["workload"]
    tokens, db = w["batch_size"], w["matrix_dtype_bytes"]
    d, h, kv, dh = (a["hidden_size"], a["num_attention_heads"],
                    a["num_key_value_heads"], a["head_dim"])
    eff = max(int(w["seq_len"] * 0.5), 1)          # causal half of the context
    layer = [
        (tokens, h * dh, d), (tokens, kv * dh, d), (tokens, kv * dh, d),
        (tokens, d, h * dh),
        (tokens * h, eff, dh), (tokens * h, dh, eff),
        (tokens, a["intermediate_size"], d), (tokens, a["intermediate_size"], d),
        (tokens, d, a["intermediate_size"]),
    ]
    ops = [(m, n, k, db, a["num_hidden_layers"]) for m, n, k in layer]
    ops.append((tokens, a["vocab_size"], d, db, 1))
    return ops
