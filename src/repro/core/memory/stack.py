"""Analytic LRU stack-distance engine (Mattson classification, no scan).

LRU is a *stack algorithm*: at any point the cache set holds exactly the
``ways`` most recently used distinct lines mapping to it. An access therefore
hits a W-way LRU cache iff its **stack distance** — the number of distinct
same-set lines touched since the previous access to the same line — is
``< W``. One distance computation over a trace classifies the access for
EVERY associativity at once (Mattson's inclusion property), which is exactly
the amortization a DSE grid sweeping the ways axis wants: the distance pass
depends only on ``(stream, num_sets)``, never on ``ways``.

The pass itself is *analytic* — a handful of argsorts and prefix sums, no
sequential ``lax.scan`` over the trace:

  1. ``prev[i]``: previous access to the same line (one stable argsort by
     (line, time); shared across every geometry of a stream).
  2. ``win[i]``: same-set accesses strictly inside ``(prev[i], i)`` from the
     per-set access rank (one stable argsort by (set, time)).
  3. ``T[i] = #{k < i, same set : prev[k] > prev[i]}`` — the accesses inside
     the window whose own previous access is ALSO inside it (duplicates).
     Then ``distance = win - T``. ``T`` is a segmented per-element inversion
     count of the ``prev`` sequence, computed with a two-level radix
     decomposition over the *rank of last access* (the lexicographic
     (set, prev) rank): a cross-bucket histogram + suffix prefix-sum plus two
     small block-local masked compare-reductions — all O(N * block) work in
     fully vectorized form.

Evictions are analytic too: LRU never invalidates, so a miss evicts iff the
set already holds ``ways`` distinct lines, i.e. iff the number of distinct
same-set lines seen before the access is ``>= ways``.

Three executions of the same math, all bit-exact against ``GoldenCache``
(test-enforced):

  * ``stack_distances_np``   — numpy host twin; the CPU hot path (argsort on
    host is ~4x faster than XLA CPU sort) and the reference the others are
    tested against.
  * ``stack_distances_jnp``  — jitted jnp port, device-resident for TPU-side
    pipelines (padded to a bucketed length; num_sets is a traced scalar so
    one compilation serves every geometry of a length bucket). On a TPU v5e
    a gather or scatter of unknown indices costs 5-9 ns an element, a
    payload-carrying sort 1.5-2.5 ns (PERF.md §5), so the port moves the
    trace between orders with sorts alone: (set, time), (line, time), rank,
    back to (set, time) and to time order, five sorts whose payloads carry
    what the next order needs. Each half of the inversion count runs in the
    order that makes it a block-local compare (same bucket in rank order,
    other buckets in position order), and the (chunk, bucket) table is built
    and read through one-hot matmuls, not a scatter-add and a gather.
  * ``kernels/stack_distance.py`` — Pallas kernel variant of the distance
    pass (``cache_backend="stack_pallas"``), VMEM-resident recency state.

``classify_lru_stack_many`` is the entry the cache engine routes
``cache_backend="stack"`` through: it memoizes distance passes by
``(stream, num_sets)`` within the call, so all same-``num_sets`` geometries
in a sweep grid classify from ONE shared distance computation.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..profiling import is_active as _profiling_active, stage

# Cold (first-ever) accesses get this sentinel distance: larger than any real
# associativity, so they miss for every ways value.
DIST_COLD = np.int32(2**30)

_BS = 128          # minimum radix block size for the inversion count (pow2)
_BIG_I32 = np.int32(np.iinfo(np.int32).max)


def _block_size(n: int) -> int:
    """Radix block size for an n-element inversion count.

    Grows as a power of two >= sqrt(n)/2 (floor ``_BS``) so the cross-bucket
    (chunk, bucket) histogram stays O(n) elements — with a FIXED block the
    table is O((n/bs)^2), which would make million-access traces allocate
    hundreds of MB. Block-local compare work is O(n * bs); at the default
    sweep scales (n ~ 5e4) this resolves to the measured-fastest bs=128.
    """
    b = _BS
    while b * b * 4 < n:
        b *= 2
    return b

# Distance passes actually computed (not served from a memo) — benchmarks and
# tests read this to verify cross-geometry sharing.
_distance_passes = 0


def distance_pass_count() -> int:
    return _distance_passes


# --------------------------------------------------------------------------
# numpy twin (CPU hot path + golden reference for the jnp/Pallas variants)
# --------------------------------------------------------------------------

def _inv_prev_larger_np(rk: np.ndarray, bs: Optional[int] = None) -> np.ndarray:
    """cnt[i] = #{k < i : rk[k] > rk[i]} for a permutation ``rk`` of [0, N).

    Two-level radix decomposition: bucket ranks into blocks of ``bs``; count
    cross-bucket pairs with a chunked histogram + suffix prefix sums, and
    same-bucket / same-chunk pairs with block-local masked compare-reductions
    (each O(N * bs) fully vectorized work; the histogram is O(N) elements by
    the ``_block_size`` scaling).
    """
    N = rk.size
    if N == 0:
        return np.zeros(0, dtype=np.int32)
    if bs is None:
        bs = _block_size(N)
    G = -(-N // bs)
    N_pad = G * bs
    # Padding ranks N..N_pad-1 sit at the END of the time axis: never
    # "previous" to a real element, so they contribute to no count.
    rk_p = np.concatenate([rk, np.arange(N, N_pad, dtype=np.int32)])
    g = rk_p >> int(np.log2(bs))

    # Same value-bucket, earlier time, larger rank.
    ordg = np.argsort(g, kind="stable")            # (bucket, time) order
    V = rk_p[ordg].reshape(G, bs)
    tri = np.arange(bs)[:, None] < np.arange(bs)[None, :]
    cnt = np.zeros(N_pad, dtype=np.int32)
    cnt[ordg] = _prev_larger_in_blocks_np(V, tri).reshape(-1)

    # Strictly higher bucket, earlier time: full earlier chunks via a
    # (chunk, bucket) histogram, the residual chunk via a local compare.
    NC = N_pad // bs
    rowflat = np.repeat(np.arange(NC, dtype=np.int64), bs) * G + g
    hist = np.bincount(rowflat, minlength=NC * G).reshape(NC, G)
    before = np.cumsum(hist, axis=0) - hist
    suf = before[:, ::-1].cumsum(axis=1)[:, ::-1] - before
    cnt += suf.reshape(-1)[rowflat].astype(np.int32)
    Gt = g.reshape(NC, bs)
    cnt += _prev_larger_in_blocks_np(Gt, tri).reshape(-1)
    return cnt[:N]


# Peak transient elements of one block-compare slab (16M bools = 16 MB):
# caps the (slab, bs, bs) boolean tensors regardless of trace length.
_SLAB_ELEMS = 1 << 24


def _prev_larger_in_blocks_np(V: np.ndarray, tri: np.ndarray) -> np.ndarray:
    """Per row of ``V``: count, for each position b, earlier positions a < b
    with V[a] > V[b] — processed in row slabs so the (slab, bs, bs) boolean
    intermediates stay bounded (identical results to one full broadcast)."""
    G, bs = V.shape
    out = np.empty((G, bs), dtype=np.int32)
    slab = max(1, _SLAB_ELEMS // (bs * bs))
    for lo in range(0, G, slab):
        W = V[lo:lo + slab]
        out[lo:lo + slab] = ((W[:, :, None] > W[:, None, :]) & tri).sum(
            axis=1, dtype=np.int32
        )
    return out


def stack_distances_np(
    lines: np.ndarray, num_sets: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact per-access LRU stack distance + distinct-lines-seen-before count.

    Returns ``(dist, distinct_before)``; cold accesses report ``DIST_COLD``.
    ``dist[i] < ways``  <=>  the access hits a (num_sets, ways) LRU cache.
    """
    lines = np.ascontiguousarray(lines).reshape(-1)
    N = lines.size
    if N == 0:
        z = np.zeros(0, dtype=np.int32)
        return z, z.copy()
    idx = np.arange(N, dtype=np.int32)
    set_idx = (lines % num_sets).astype(np.int32)

    order = np.argsort(lines, kind="stable")       # (line, time) order
    ls = lines[order]
    same = np.zeros(N, dtype=bool)
    same[1:] = ls[1:] == ls[:-1]
    tmp = np.full(N, -1, dtype=np.int32)
    tmp[1:][same[1:]] = order[:-1][same[1:]].astype(np.int32)
    prev = np.empty(N, dtype=np.int32)
    prev[order] = tmp

    order2 = np.argsort(set_idx, kind="stable")    # (set, time) order
    ss = set_idx[order2]
    start = np.ones(N, dtype=bool)
    start[1:] = ss[1:] != ss[:-1]
    grp = np.maximum.accumulate(np.where(start, idx, 0))
    r = np.empty(N, dtype=np.int32)
    r[order2] = idx - grp

    valid = prev >= 0
    win = np.where(valid, r - r[np.maximum(prev, 0)] - 1, 0)

    # Lexicographic (set, prev) rank — the "rank of last access" — via two
    # stable argsorts; counting inversions in the (set, time) layout keeps
    # smaller-set elements below the composite order (never counted) and
    # compares same-set elements on prev: one pass segments by set for free.
    o1 = np.argsort(prev, kind="stable")
    p = o1[np.argsort(set_idx[o1], kind="stable")]
    rk = np.empty(N, dtype=np.int32)
    rk[p] = idx
    T = np.empty(N, dtype=np.int32)
    T[order2] = _inv_prev_larger_np(rk[order2])
    dist = np.where(valid, (win - T).astype(np.int32), DIST_COLD)

    firsts = (~valid)[order2].astype(np.int32)
    cs = np.cumsum(firsts, dtype=np.int64)
    seg_base = np.maximum.accumulate(np.where(start, cs - firsts, 0))
    distinct_before = np.empty(N, dtype=np.int32)
    distinct_before[order2] = cs - firsts - seg_base
    return dist, distinct_before


# --------------------------------------------------------------------------
# jnp port (device-resident; numpy twin is the test-enforced golden)
# --------------------------------------------------------------------------

def _prev_larger_in_blocks_jnp(V: jax.Array, tri: jax.Array) -> jax.Array:
    """jnp twin of ``_prev_larger_in_blocks_np`` (same slab bound, so the
    (slab, bs, bs) boolean intermediates stay bounded under jit too)."""
    G, bs = V.shape
    slab = max(1, _SLAB_ELEMS // (bs * bs))
    parts = [
        jnp.sum((W[:, :, None] > W[:, None, :]) & tri, axis=1, dtype=jnp.int32)
        for W in (V[lo:lo + slab] for lo in range(0, G, slab))
    ]
    return jnp.concatenate(parts, axis=0)


def _same_bucket_jnp(p: jax.Array, bs: int) -> jax.Array:
    """Same-bucket half of the inversion count, in rank order.

    ``p[j]`` is the position of rank ``j``. Returns, for each rank ``j``,
    ``#{j' > j in j's bucket of bs ranks : p[j'] < p[j]}`` — the pairs the
    numpy twin counts after sorting by (bucket, time), read here straight off
    the rank order's rows: a row reversed and negated turns "later and
    smaller" into "earlier and larger"."""
    N = p.shape[0]
    tri = jnp.arange(bs)[:, None] < jnp.arange(bs)[None, :]
    rows = (N - 1 - p.reshape(N // bs, bs))[:, ::-1]
    return _prev_larger_in_blocks_jnp(rows, tri)[:, ::-1].reshape(-1)


# float32 holds every integer below 2**24 exactly: the largest (chunk,
# bucket) table entry a one-hot matmul reads in one piece.
_F32_EXACT = 1 << 24


def _cross_bucket_jnp(rk: jax.Array, bs: int) -> jax.Array:
    """Cross-bucket half of the inversion count, in position order.

    Returns ``#{s' < s : rk[s'] // bs > rk[s] // bs}`` for a permutation
    ``rk``: earlier chunks of bs positions through a (chunk, bucket) table,
    the element's own chunk through a block-local compare. The table is
    built and read on the MXU, with no scatter or gather: a bucket splits
    into (hi, lo); a chunk's histogram is the product of its one-hot hi and
    lo matrices, and each position reads its entry through a one-hot hi
    product and a select over lo. Each output sums one nonzero product, an
    integer below 2**24, so float32 at HIGHEST precision holds it exactly."""
    N = rk.shape[0]
    G = N // bs
    g = (rk // bs).reshape(G, bs)                  # bucket per position
    L = 1 << ((G.bit_length() - 1) // 2)
    H = G // L
    hi = (g // L)[:, :, None] == jnp.arange(H, dtype=jnp.int32)
    lo = (g % L)[:, :, None] == jnp.arange(L, dtype=jnp.int32)
    hist = jnp.einsum(
        "cbh,cbl->chl", hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32).reshape(G, G)
    before = jnp.cumsum(hist, axis=0) - hist       # chunks before c
    higher = jnp.cumsum(before[:, ::-1], axis=1)[:, ::-1] - before
    # Entries reach N - bs: past float32's exact range, read 16 bits a time.
    pieces = ([(higher, 1)] if N <= _F32_EXACT
              else [(higher >> 16, 1 << 16), (higher & 0xFFFF, 1)])
    earlier = 0
    for piece, scale in pieces:
        row = jnp.einsum(
            "cbh,chl->cbl", hi.astype(jnp.float32),
            piece.reshape(G, H, L).astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        earlier = earlier + scale * jnp.sum(
            jnp.where(lo, row, 0.0), axis=-1
        ).astype(jnp.int32)
    tri = jnp.arange(bs)[:, None] < jnp.arange(bs)[None, :]
    return (earlier + _prev_larger_in_blocks_jnp(g, tri)).reshape(-1)


@functools.partial(jax.jit, static_argnames=("bs",))
def _stack_pass_jnp(lines: jax.Array, num_sets: jax.Array, n_real: jax.Array,
                    bs: int):
    """Padded device pass; ``num_sets``/``n_real`` are traced scalars so one
    compilation serves every geometry of a length bucket.

    The numpy twin's math, with every reordering a payload-carrying sort:
    (set, time) order, then (line, time) order, then rank order, then back
    to (set, time) and time order. No gather or scatter is left: on a TPU
    one of unknown indices costs several times a sort that moves the same
    array with its payloads."""
    N = lines.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    # Padding gets the set past the last, so it also fills the last
    # positions of the (set, time) order: ``real`` holds in both orders.
    real = idx < n_real
    set_idx = jnp.where(real, lines % num_sets, num_sets)

    # (set, time) order: position s holds a set, a line and their time.
    ss, ls, ts = jax.lax.sort((set_idx, lines, idx), num_keys=1,
                              is_stable=True)
    start = jnp.concatenate([jnp.ones(1, bool), ss[1:] != ss[:-1]])
    grp = jax.lax.cummax(jnp.where(start, idx, 0))

    # (line, time) order: each access follows its line's previous access,
    # so the window ``win`` is a difference of (set, time) positions.
    ll, sl, gl = jax.lax.sort((jnp.where(real, ls, _BIG_I32), idx, grp),
                              num_keys=1, is_stable=True)
    same = jnp.concatenate(
        [jnp.zeros(1, bool), (ll[1:] == ll[:-1]) & (sl[1:] < n_real)]
    )
    sprev = jnp.concatenate([jnp.zeros(1, jnp.int32), sl[:-1]])
    win = jnp.where(same, sl - sprev - 1, -1)      # -1: cold

    # Rank order, the lexicographic (set, prev) rank: positions are
    # set-major, so a warm access keys on its previous position and a cold
    # one on its set's first, between the warm keys of its set and those of
    # the set before. Cold accesses tie, and their order among themselves
    # moves no warm access's count: a distance is only read for warm ones.
    key = jnp.where(same, 2 * sprev + 2, 2 * gl + 1)
    _, p, win = jax.lax.sort((key, sl, win), num_keys=1)
    part = jnp.where(win >= 0, win - _same_bucket_jnp(p, bs), -1)

    # Back to (set, time) order: rk[s] is the rank at position s.
    _, rk, part = jax.lax.sort((p, idx, part), num_keys=1)
    valid = part >= 0
    dist = jnp.where(valid, part - _cross_bucket_jnp(rk, bs),
                     jnp.int32(DIST_COLD))

    firsts = (~valid & real).astype(jnp.int32)
    cs = jnp.cumsum(firsts)
    seg_base = jax.lax.cummax(jnp.where(start, cs - firsts, 0))
    distinct_before = cs - firsts - seg_base

    _, dist, distinct_before = jax.lax.sort((ts, dist, distinct_before),
                                            num_keys=1)
    return dist, distinct_before


def _pad_len(n: int) -> int:
    """Power-of-two length bucketing (compiled-shape reuse, as in cache.py)."""
    b = _BS
    while b < n:
        b *= 2
    return b


def stack_distances_jnp(
    lines: np.ndarray, num_sets: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Device-resident ``stack_distances_np`` (equality test-enforced)."""
    lines = np.ascontiguousarray(lines).reshape(-1)
    n = lines.size
    if n == 0:
        z = np.zeros(0, dtype=np.int32)
        return z, z.copy()
    if lines.dtype != np.int32 and int(lines.max()) >= int(_BIG_I32):
        # The device pass is int32 (no x64); silently wrapping here would
        # diverge from the int64-capable numpy twin.
        raise ValueError("line numbers exceed int32 range; rebase the trace")
    N = _pad_len(n)
    lp = np.zeros(N, dtype=np.int32)
    lp[:n] = lines
    d, db = _stack_pass_jnp(
        jnp.asarray(lp), jnp.int32(num_sets), jnp.int32(n), _block_size(N)
    )
    if _profiling_active():
        jax.block_until_ready((d, db))
    with stage("host_sync"):
        return np.asarray(d)[:n], np.asarray(db)[:n]


# --------------------------------------------------------------------------
# Classification entry point (what cache_backend="stack" routes through)
# --------------------------------------------------------------------------

def _default_engine() -> str:
    # Host argsort beats XLA CPU sort ~4x; on TPU the jnp pass stays device-
    # resident. Same results either way (equality test-enforced).
    return "jnp" if jax.default_backend() == "tpu" else "np"


def stack_distances(
    lines: np.ndarray, num_sets: int, engine: Optional[str] = None
) -> Tuple[np.ndarray, np.ndarray]:
    global _distance_passes
    _distance_passes += 1
    engine = engine or _default_engine()
    if engine == "np":
        return stack_distances_np(lines, num_sets)
    if engine == "jnp":
        return stack_distances_jnp(lines, num_sets)
    raise ValueError(f"unknown stack engine {engine!r}; options: np, jnp")


def classify_lru_stack_many(
    streams: Sequence[np.ndarray],
    geometries: Sequence,                      # Sequence[CacheGeometry]
    engine: Optional[str] = None,
) -> List[Tuple[np.ndarray, int]]:
    """Per-access LRU hits + eviction count for several (trace, geometry)
    pairs from shared stack-distance passes.

    The distance pass depends only on ``(stream, num_sets)`` — every ways
    value (and every geometry that degenerates to the same num_sets) of a
    sweep grid classifies from one memoized computation. Bit-exact with the
    scan engine / ``GoldenCache`` (test-enforced).
    """
    # Memoize by the stream's underlying buffer (the sweep hands views of the
    # SAME array to every geometry of a memo group) + num_sets; ``streams``
    # keeps the keyed arrays alive for the whole call, so pointers are stable.
    as_i32: Dict[tuple, np.ndarray] = {}
    memo: Dict[Tuple[tuple, int], Tuple[np.ndarray, np.ndarray]] = {}
    out: List[Tuple[np.ndarray, int]] = []
    for stream, geom in zip(streams, geometries):
        arr = np.asarray(stream)
        # Strides are part of the key: two views can share (pointer, size,
        # dtype) yet read different elements (e.g. a[:500] vs a[::2]).
        sid = (arr.__array_interface__["data"][0], arr.shape, arr.dtype.str,
               arr.strides)
        lines32 = as_i32.get(sid)
        if lines32 is None:
            lines64 = np.asarray(arr, dtype=np.int64).reshape(-1)
            if lines64.size and int(lines64.max()) >= int(_BIG_I32):
                raise ValueError(
                    "line numbers exceed int32 range; rebase the trace"
                )
            lines32 = lines64.astype(np.int32)
            as_i32[sid] = lines32
        key = (sid, geom.num_sets)
        dist_pass = memo.get(key)
        if dist_pass is None:
            with stage("stack_distance"):
                dist_pass = stack_distances(lines32, geom.num_sets, engine)
            memo[key] = dist_pass
        dist, distinct_before = dist_pass
        hits = dist < np.int32(min(geom.ways, int(DIST_COLD) - 1))
        evictions = int(((~hits) & (distinct_before >= geom.ways)).sum())
        out.append((hits, evictions))
    return out
