"""Pallas set-associative cache-scan kernel (the simulator's hot loop).

``cache.py`` simulates the paper's on-chip cache by scanning the address
trace with a ``(tags, meta)`` carry. This module is the Pallas realization of
that loop (``HardwareConfig.cache_backend="pallas"``): one kernel instance
per set-group sub-trace keeps the whole ``(group_sets, ways)`` tag + metadata
state in VMEM scratch and walks the padded sub-trace in-kernel, so the state
never round-trips through HBM between accesses and the grid dimension
processes the length-bucketed sub-traces of many configs in one launch.

Replacement semantics are copied access-for-access from ``cache._step``
(ChampSim LRU / SRRIP / FIFO). Way selection takes the minimum way index
under a mask (``_first_true``), which tie-breaks like argmax/argmin (lowest
way). Integer state only, so the kernel is bit-exact against
``golden.GoldenCache`` — enforced by the differential fuzz tests in
``tests/test_cache_pallas.py``.

TPU layout (``tests/test_tpu_compile.py`` compiles it for a v5e):

  * grid ``(B, L / T)``: sub-traces on the first axis, the access axis tiled
    by ``T`` on the second, a sequential (``"arbitrary"``) axis the state
    scratch is carried across;
  * per-access set, tag and valid flag are scalars read from SMEM blocks;
  * the state is ``(sets, 1, lanes)`` VMEM scratch, ways padded to a
    multiple of 128 lanes and masked, so a set is one dynamically indexed
    row on the untiled leading axis;
  * outputs are collected 128 accesses at a time in one lane-dense row and
    stored as ``(T / 128, 128)`` tiles.

Off-TPU the kernel runs in interpret mode (selected automatically), so CPU
CI exercises the exact kernel program end to end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_RRPV = 3  # 2-bit SRRIP (mirrors cache.MAX_RRPV)

_POLICY_IDS = {"lru": 0, "srrip": 1, "fifo": 2}

LANES = 128        # accesses per lane-dense output row
TILE_MAX = 1024    # accesses per grid step: (8, 128) output tiles


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_layout(L: int) -> "tuple[int, int]":
    """``(padded length, tile)`` of the access axis for a length-``L`` trace.

    A tile is the whole padded trace up to ``TILE_MAX``; longer traces pad to
    a multiple of ``TILE_MAX`` so every output block is ``(8, 128)``-aligned.
    """
    Lp = _round_up(max(L, 1), LANES)
    if Lp <= TILE_MAX:
        return Lp, Lp
    return _round_up(Lp, TILE_MAX), TILE_MAX


def _state_lanes(ways: int) -> int:
    """Lane width of one set's state row (ways padded to whole vregs)."""
    return _round_up(ways, LANES)


def _first_true(mask: jax.Array, lane: jax.Array) -> jax.Array:
    """Mask selecting the first True along the last axis (argmax tie-break)."""
    first = jnp.min(jnp.where(mask, lane, lane.shape[-1]), axis=-1, keepdims=True)
    return lane == first


def lanes_any(mask: jax.Array) -> jax.Array:
    """``(1, 1)`` mask: is any lane of the row set?"""
    return jnp.max(mask.astype(jnp.int32), axis=-1, keepdims=True) > 0


def access_scalars(x_ref, i):
    """Set, tag and ``(1, 1)`` valid mask of access ``i`` of the tile."""
    valid = jnp.full((1, 1), x_ref[2, i], jnp.int32) != 0
    return x_ref[0, i], x_ref[1, i], valid


def walk_tile(access, x_ref, out_refs):
    """Run ``access(i)`` for every access ``i`` of one tile, in order.

    ``access`` returns one ``(1, 1)`` int32 value per output; they are placed
    lane by lane into ``(1, 128)`` rows, and each full row is stored into the
    ``(T / 128, 128)`` output blocks ``out_refs``.
    """
    T = x_ref.shape[1]
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def row_body(r, carry):
        def lane_body(c, accs):
            vals = access(r * LANES + c)
            return tuple(
                jnp.where(out_lane == c, v, a) for v, a in zip(vals, accs)
            )

        zero = jnp.zeros((1, LANES), jnp.int32)
        accs = jax.lax.fori_loop(0, LANES, lane_body, (zero,) * len(out_refs))
        for ref, acc in zip(out_refs, accs):
            ref[pl.ds(r, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, T // LANES, row_body, 0)


def _cache_scan_kernel(
    policy_id: int,
    ways: int,
    x_ref,        # SMEM (3, T) int32 per access: local set, tag, valid
    hit_ref,      # VMEM (T / 128, 128) int32 out: on-chip hit
    evict_ref,    # VMEM (T / 128, 128) int32 out: eviction performed
    tags_ref,     # VMEM (sets, 1, lanes) int32 scratch: line tags, -1 invalid
    meta_ref,     # VMEM (sets, 1, lanes) int32 scratch: LRU/FIFO ts or RRPV
):
    T = x_ref.shape[1]
    j = pl.program_id(1)
    srrip = policy_id == _POLICY_IDS["srrip"]

    @pl.when(j == 0)
    def _init():
        tags_ref[...] = jnp.full(tags_ref.shape, -1, jnp.int32)
        meta_ref[...] = jnp.full(
            meta_ref.shape, MAX_RRPV if srrip else -1, jnp.int32
        )

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tags_ref.shape[2]), 1)
    way_ok = lane < ways

    def access(i):
        s, tag, valid = access_scalars(x_ref, i)
        t = j * T + i                     # access index within the sub-trace

        row_tags = tags_ref[s]            # (1, lanes)
        row_meta = meta_ref[s]

        hit_vec = (row_tags == tag) & way_ok
        hit = lanes_any(hit_vec)
        hit_mask = _first_true(hit_vec, lane)
        invalid_vec = row_tags < 0

        if srrip:
            # Age the set until some way reaches MAX_RRPV (persists).
            top = jnp.max(jnp.where(way_ok, row_meta, 0), axis=-1, keepdims=True)
            aged = row_meta + jnp.maximum(0, MAX_RRPV - top)
            victim_mask = _first_true((aged == MAX_RRPV) & way_ok, lane)
            new_meta_hit = jnp.where(hit_mask, 0, row_meta)
            new_meta_miss = jnp.where(victim_mask, MAX_RRPV - 1, aged)
        else:
            # Invalid ways carry -1 < any timestamp, so the first minimum is
            # the first invalid way when one exists (ChampSim behaviour).
            masked = jnp.where(invalid_vec, -1, row_meta)
            masked = jnp.where(way_ok, masked, jnp.iinfo(jnp.int32).max)
            low = jnp.min(masked, axis=-1, keepdims=True)
            victim_mask = _first_true(masked == low, lane)
            if policy_id == _POLICY_IDS["lru"]:
                new_meta_hit = jnp.where(hit_mask, t, row_meta)
            else:  # fifo: hits do not touch metadata
                new_meta_hit = row_meta
            new_meta_miss = jnp.where(victim_mask, t, row_meta)

        evict = valid & ~hit & lanes_any(victim_mask & (row_tags >= 0))
        new_meta = jnp.where(hit, new_meta_hit, new_meta_miss)
        new_tags = jnp.where(hit, row_tags, jnp.where(victim_mask, tag, row_tags))

        # Padding accesses (and padding lanes) leave the state untouched.
        keep = valid & way_ok
        tags_ref[s] = jnp.where(keep, new_tags, row_tags)
        meta_ref[s] = jnp.where(keep, new_meta, row_meta)
        return (hit & valid).astype(jnp.int32), evict.astype(jnp.int32)

    walk_tile(access, x_ref, (hit_ref, evict_ref))


def _pack_accesses(sets, tags, valid, L: int):
    """The kernels' ``(B, tiles, 3, T)`` int32 input: set, tag and valid of
    every access, tiled along the access axis; the padded tail is inert."""
    Lp, T = _tile_layout(L)
    x = jnp.stack([jnp.asarray(a, jnp.int32) for a in (sets, tags, valid)], 1)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, Lp - L)))
    B = x.shape[0]
    return x.reshape(B, 3, Lp // T, T).transpose(0, 2, 1, 3)


@functools.lru_cache(maxsize=None)
def _set_group_call(kernel, kernel_args: tuple, n_state: int, num_sets: int,
                    ways: int, B: int, L: int, interpret: bool):
    """Memoized pallas_call of one set-group kernel per (geometry, batch
    shape): per-access scalars in ``(3, T)`` SMEM tiles, ``n_state``
    ``(sets, 1, lanes)`` VMEM state scratches, two lane-dense outputs.

    The bucketed sweep re-dispatches identical shapes many times; building
    the call once per shape keeps tracing (and on TPU, compilation) out of
    the steady-state path, matching the jitted scan backend's cost profile.
    """
    Lp, T = _tile_layout(L)
    scalars = pl.BlockSpec((None, None, 3, T), lambda b, j: (b, j, 0, 0),
                           memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((None, T // LANES, LANES), lambda b, j: (b, j, 0))
    out = jax.ShapeDtypeStruct((B, Lp // LANES, LANES), jnp.int32)
    state = pltpu.VMEM((num_sets, 1, _state_lanes(ways)), jnp.int32)
    return pl.pallas_call(
        functools.partial(kernel, *kernel_args),
        grid=(B, Lp // T),
        in_specs=[scalars],
        out_specs=[tile, tile],
        out_shape=[out, out],
        scratch_shapes=[state] * n_state,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )


def run_set_groups(kernel, kernel_args: tuple, n_state: int, sets, tags,
                   valid, num_sets: int, ways: int, interpret):
    """Run a set-group kernel over ``(B, L)`` sub-traces; returns its two
    ``(B, L)`` int32 outputs. ``interpret=None`` picks interpret mode
    off-TPU so the kernel runs everywhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, L = sets.shape
    call = _set_group_call(kernel, kernel_args, n_state, int(num_sets),
                           int(ways), int(B), int(L), bool(interpret))
    return tuple(o.reshape(B, -1)[:, :L]
                 for o in call(_pack_accesses(sets, tags, valid, L)))


def cache_scan_groups(
    sets: jax.Array,      # (B, L) int32 local set index
    tags: jax.Array,      # (B, L) int32 tag
    valid: jax.Array,     # (B, L) bool
    num_sets: int,
    ways: int,
    policy: str = "lru",
    interpret: "bool | None" = None,
):
    """Run B padded set-group sub-traces through the Pallas cache kernel.

    Same contract as ``cache._simulate_many`` (per-access hit/evict arrays,
    device-resident); grid dimension = sub-trace batch. ``interpret=None``
    auto-selects interpret mode off-TPU so the kernel runs everywhere.
    """
    if policy not in _POLICY_IDS:
        raise ValueError(f"unknown policy {policy!r}; options: {sorted(_POLICY_IDS)}")
    hits, evicts = run_set_groups(
        _cache_scan_kernel, (_POLICY_IDS[policy], int(ways)), 2,
        sets, tags, valid, num_sets, ways, interpret,
    )
    return hits.astype(bool), evicts.astype(bool)
