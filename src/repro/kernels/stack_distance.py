"""Pallas LRU stack-distance kernel (the TPU variant of the distance pass).

``memory/stack.py`` computes exact LRU stack distances analytically (argsorts
and prefix sums). This kernel is the VMEM-resident realization of the same
distance pass for ``cache_backend="stack_pallas"``: per set-group sub-trace
it keeps a *recency-ordered* tag list (way 0 = MRU) in VMEM scratch and walks
the padded sub-trace in-kernel. For every access the position of its tag in
the recency list IS the stack distance (capped at ``ways`` — larger distances
are indistinguishable from a miss for every associativity this state covers);
updating is one rotate-insert toward MRU, no timestamps.

This is a deliberately different *shape* of implementation from both the
``(tags, meta)`` cache-scan kernel and the analytic engine — agreement across
the three (and ``GoldenCache``) is therefore meaningful, and is enforced by
the differential fuzz tests in ``tests/test_cache_stack.py``. It shares the
cache-scan kernel's TPU layout (``kernels/cache_scan.py``: SMEM per-access
scalars, a sequential tile axis carrying the state, lane-dense outputs).
Off-TPU the kernel runs in interpret mode so CPU CI exercises the exact
kernel program.

Outputs: per-access capped distance (int32; hit for W ways iff ``dist < W``
with ``W <= ways``) and the eviction flag (miss with a full set).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cache_scan import access_scalars, lanes_any, run_set_groups, walk_tile


def _stack_distance_kernel(
    ways: int,
    x_ref,        # SMEM (3, T) int32 per access: local set, tag, valid
    dist_ref,     # VMEM (T / 128, 128) int32 out: stack distance, capped
    evict_ref,    # VMEM (T / 128, 128) int32 out: eviction performed
    tags_ref,     # VMEM (sets, 1, lanes) int32 scratch: recency list, -1 empty
):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        tags_ref[...] = jnp.full(tags_ref.shape, -1, jnp.int32)

    lanes = tags_ref.shape[2]
    way_idx = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def access(i):
        s, tag, valid = access_scalars(x_ref, i)

        row = tags_ref[s]                                   # (1, lanes)
        hit_vec = (row == tag) & (way_idx < ways)
        # Position of the tag in the recency list = capped stack distance.
        pos = jnp.min(jnp.where(hit_vec, way_idx, lanes), axis=-1,
                      keepdims=True)
        found = pos < lanes
        dist = jnp.where(found, pos, ways)

        # Rotate-insert toward MRU: ways [1, limit] take their left
        # neighbour, way 0 takes the tag; ways beyond the hit position (or
        # everything on a miss, dropping the LRU way) stay put. Padding
        # lanes lie beyond ``ways - 1 >= limit`` and never change.
        limit = jnp.where(found, pos, ways - 1)
        rolled = pltpu.roll(row, 1, 1)
        new_row = jnp.where(
            way_idx == 0, tag, jnp.where(way_idx <= limit, rolled, row)
        )
        full = lanes_any((way_idx == ways - 1) & (row >= 0))
        evict = valid & ~found & full
        tags_ref[s] = jnp.where(valid, new_row, row)
        return jnp.where(valid, dist, ways), evict.astype(jnp.int32)

    walk_tile(access, x_ref, (dist_ref, evict_ref))


def stack_distance_groups(
    sets: jax.Array,      # (B, L) int32 local set index
    tags: jax.Array,      # (B, L) int32 tag
    valid: jax.Array,     # (B, L) bool
    num_sets: int,
    ways: int,
    interpret: "bool | None" = None,
):
    """Run B padded set-group sub-traces through the distance kernel.

    Returns device-resident ``(dist, evict)``: int32 distances capped at
    ``ways`` (hit for W-way LRU iff ``dist < W``) and bool eviction flags.
    ``interpret=None`` auto-selects interpret mode off-TPU.
    """
    dist, evict = run_set_groups(
        _stack_distance_kernel, (int(ways),), 1, sets, tags, valid,
        num_sets, ways, interpret,
    )
    return dist, evict.astype(bool)
