"""Stack-distance cache backend + cross-config DRAM batcher: differential
fuzz vs the ChampSim-semantics golden model and bit-exactness guarantees.

The ``stack``/``stack_pallas`` backends are advertised as pure execution-
strategy knobs: every hit/miss, eviction, DRAM row-hit, and finish-cycle
count must be bitwise identical to the scan backend and ``GoldenCache`` —
including adversarial geometries (1 set, 1 way, non-power-of-two ways) and
the Mattson sharing property (every ways value of a grid classified from ONE
distance pass). Likewise ``dram_timing_many`` must equal per-request
dispatch, including the multi-core contended path.
"""
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from differential import assert_bitwise_equal_results, golden_pair
from repro.core import dlrm_rmc2_small, simulate, sweep, tpuv6e
from repro.core.hardware import OnChipPolicy
from repro.core.memory import stack as stack_mod
from repro.core.memory.cache import (
    CacheGeometry,
    simulate_cache,
    simulate_cache_many,
)
from repro.core.memory.dram import (
    DramModel,
    DramRequest,
    dram_timing_many,
    dram_timing_single,
)
from repro.core.memory.golden import GoldenCache
from repro.core.memory.stack import (
    _block_size,
    _cross_bucket_jnp,
    _inv_prev_larger_np,
    _same_bucket_jnp,
    _stack_pass_jnp,
    classify_lru_stack_many,
    distance_pass_count,
    stack_distances_jnp,
    stack_distances_np,
)

GEOMETRIES = [
    (1, 1, 6), (1, 4, 30), (3, 2, 50), (7, 5, 200), (32, 16, 4000),
    (8, 3, 120), (33, 7, 500),          # non-pow2 ways / sets
]


@pytest.mark.parametrize("backend", ["stack", "stack_pallas"])
@pytest.mark.parametrize("sets,ways,space", GEOMETRIES)
def test_stack_bit_exact_vs_golden(backend, sets, ways, space, rng):
    lines = rng.integers(0, space, size=300)
    geom = CacheGeometry(num_sets=sets, ways=ways, line_bytes=64)
    ours = simulate_cache(lines, geom, "lru", backend=backend)
    gold = GoldenCache(geom, "lru")
    gold_hits = gold.run(lines)
    assert np.array_equal(ours.hits, gold_hits)
    assert ours.num_hits == gold.num_hits
    assert ours.num_misses == gold.num_misses
    assert ours.num_evictions == gold.num_evictions


@settings(max_examples=20, deadline=None)
@given(
    sets=st.sampled_from([1, 2, 3, 5, 8, 33, 128]),
    ways=st.sampled_from([1, 2, 3, 4, 7, 16]),
    n=st.integers(1, 400),
    space=st.integers(1, 900),
    seed=st.integers(0, 2**31 - 1),
)
def test_stack_bit_exact_property(sets, ways, n, space, seed):
    lines = np.random.default_rng(seed).integers(0, space, size=n)
    geom = CacheGeometry(num_sets=sets, ways=ways, line_bytes=64)
    ours = simulate_cache(lines, geom, "lru", backend="stack")
    gold = GoldenCache(geom, "lru")
    gold_hits = gold.run(lines)
    assert np.array_equal(ours.hits, gold_hits)
    assert ours.num_evictions == gold.num_evictions


def _uniform(n, space):
    return lambda rng: rng.integers(0, space, size=n)


# (lines from a seeded rng, num_sets): padded buckets (n just over 128 and
# 4096 leaves n_real < N), degenerate and wide set counts, streams with no
# reuse or nothing but reuse, and a skewed stream of the benchmark's kind.
JNP_CASES = {
    "uniform_sets1": (_uniform(777, 5000), 1),
    "uniform_sets3": (_uniform(777, 5000), 3),
    "uniform_sets64": (_uniform(777, 5000), 64),
    "bucket_edge_129": (_uniform(129, 40), 4),
    "bucket_edge_4097": (_uniform(4097, 3000), 16),
    "sets1_long": (_uniform(5000, 700), 1),
    "sets3_long": (_uniform(5000, 700), 3),
    "sets1024": (_uniform(5000, 20000), 1024),
    "sets4096": (_uniform(9000, 50000), 4096),
    "all_cold": (lambda rng: rng.permutation(3000) * 7, 8),
    "one_line": (lambda rng: np.full(2000, 12345), 16),
    "zipf_65536": (lambda rng: rng.zipf(1.1, size=1 << 16) % 200_000, 2048),
}


@pytest.mark.parametrize("case", list(JNP_CASES))
def test_stack_jnp_engine_matches_numpy(case, rng):
    """The device-resident jnp pass equals the numpy host twin bitwise."""
    make, sets = JNP_CASES[case]
    lines = make(rng).astype(np.int32)
    d_np, b_np = stack_distances_np(lines, sets)
    d_j, b_j = stack_distances_jnp(lines, sets)
    assert np.array_equal(d_np, d_j)
    assert np.array_equal(b_np, b_j)


def test_stack_jnp_engine_end_to_end(rng):
    """classify_lru_stack_many(engine="jnp") equals the numpy engine."""
    stream = rng.integers(0, 3000, size=2000).astype(np.int64)
    geoms = [CacheGeometry(num_sets=s, ways=w, line_bytes=64)
             for s, w in ((16, 4), (16, 8), (64, 3))]
    a = classify_lru_stack_many([stream] * len(geoms), geoms, engine="np")
    b = classify_lru_stack_many([stream] * len(geoms), geoms, engine="jnp")
    for (ha, ea), (hb, eb) in zip(a, b):
        assert np.array_equal(ha, hb)
        assert ea == eb


def test_one_distance_pass_classifies_every_ways(rng):
    """Mattson sharing: all ways values of one (stream, num_sets) classify
    from ONE distance pass, each bit-exact vs an independent golden run."""
    stream = rng.integers(0, 4000, size=3000).astype(np.int64)
    ways_axis = (1, 2, 3, 4, 7, 8, 16)
    geoms = [CacheGeometry(num_sets=32, ways=w, line_bytes=64)
             for w in ways_axis]
    before = distance_pass_count()
    results = simulate_cache_many([stream] * len(geoms), geoms, "lru",
                                  backend="stack")
    assert distance_pass_count() - before == 1       # shared pass
    for geom, res in zip(geoms, results):
        gold = GoldenCache(geom, "lru")
        gold_hits = gold.run(stream)
        assert np.array_equal(res.hits, gold_hits)
        assert res.num_evictions == gold.num_evictions
    # Mattson inclusion: hits grow monotonically with associativity.
    for a, b in zip(results, results[1:]):
        assert not np.any(a.hits & ~b.hits)


def test_stack_backend_analytic_for_non_stack_policies(rng):
    """srrip/fifo under the stack variants run the analytic per-set engines
    (no sequential full-trace scan) and stay bit-exact vs scan."""
    lines = rng.integers(0, 600, size=400)
    geom = CacheGeometry(num_sets=8, ways=4, line_bytes=64)
    for policy in ("srrip", "fifo"):
        for backend in ("stack", "stack_pallas"):
            got = simulate_cache(lines, geom, policy, backend=backend)
            ref = simulate_cache(lines, geom, policy, backend="scan")
            assert np.array_equal(got.hits, ref.hits), (policy, backend)
            assert got.num_evictions == ref.num_evictions


def test_stack_backend_selection_and_no_fallback_warning(caplog):
    """Every policy resolves to an analytic engine under "stack" (the
    srrip/fifo stack->scan fallback — and its warning — is retired);
    "stack_pallas" differs from "stack" only for LRU's distance pass."""
    from repro.core.memory.cache import _effective_backend

    assert _effective_backend("lru", "stack") == "stack"
    assert _effective_backend("lru", "stack_pallas") == "stack_pallas"
    assert _effective_backend("srrip", "stack") == "stack"
    assert _effective_backend("fifo", "stack") == "stack"
    assert _effective_backend("srrip", "stack_pallas") == "stack"
    assert _effective_backend("fifo", "stack_pallas") == "stack"
    assert _effective_backend("fifo", "scan") == "scan"
    assert _effective_backend("srrip", "pallas") == "pallas"

    logger = "repro.core.memory.cache"
    rng = np.random.default_rng(5)
    lines = rng.integers(0, 300, size=256)
    geom = CacheGeometry(num_sets=8, ways=4, line_bytes=64)
    with caplog.at_level(logging.WARNING, logger=logger):
        for policy in ("srrip", "fifo", "lru"):
            simulate_cache(lines, geom, policy, backend="stack")
    assert not [r for r in caplog.records if r.name == logger]


def test_analytic_engines_share_presort_across_ways(rng):
    """rrip sharing: all ways values of one (stream, num_sets) classify from
    ONE compression presort, each bit-exact vs an independent golden run."""
    from repro.core.memory.rrip import analytic_pass_count

    stream = rng.integers(0, 4000, size=3000).astype(np.int64)
    ways_axis = (1, 2, 3, 4, 7, 8, 16)
    geoms = [CacheGeometry(num_sets=32, ways=w, line_bytes=64)
             for w in ways_axis]
    for policy in ("srrip", "fifo"):
        before = analytic_pass_count()
        results = simulate_cache_many([stream] * len(geoms), geoms, policy,
                                      backend="stack")
        assert analytic_pass_count() - before == 1       # shared presort
        for geom, res in zip(geoms, results):
            gold = GoldenCache(geom, policy)
            gold_hits = gold.run(stream)
            assert np.array_equal(res.hits, gold_hits), (policy, geom.ways)
            assert res.num_evictions == gold.num_evictions


@pytest.mark.parametrize("policy", ["srrip", "fifo"])
def test_analytic_engine_corpus_differential(policy):
    """tests/differential.py lock: the analytic srrip/fifo engines are
    bitwise identical to the scan engine across the seeded trace corpus."""
    geoms = [CacheGeometry(num_sets=64, ways=4, line_bytes=64),
             CacheGeometry(num_sets=128, ways=8, line_bytes=64)]

    def classify(backend):
        def run(et):
            stream = et.address_trace(64).lines
            return simulate_cache_many([stream] * len(geoms), geoms,
                                       policy, backend=backend)
        return run

    golden_pair(classify("stack"), classify("scan"),
                label=f"analytic-{policy}")()


def test_sweep_grid_stack_vs_scan_and_independent_simulate():
    """Every grid point under the stack backend equals both the scan-backend
    sweep and an independent simulate() run, bit for bit."""
    wl = dlrm_rmc2_small(num_tables=2, rows_per_table=2000, dim=128,
                         lookups=4, batch_size=8, num_batches=2)
    grid = dict(policies=("spm", "lru", "srrip", "fifo"),
                capacities=(1 << 16, 1 << 17), ways=(2, 4),
                zipf_s=0.9, seed=0)
    hw_stack = tpuv6e().with_cache_backend("stack")
    got = sweep(wl, hw_stack, **grid)
    ref = sweep(wl, tpuv6e().with_cache_backend("scan"), **grid)
    assert got.num_configs == ref.num_configs
    for a, b in zip(got.entries, ref.entries):
        assert_bitwise_equal_results(a.result, b.result, label=a.config.label)
    for e in got.entries[:: max(1, got.num_configs // 5)]:
        c = e.config
        hw = hw_stack.with_policy(
            OnChipPolicy(c.policy), capacity_bytes=c.capacity_bytes, ways=c.ways
        )
        ind = simulate(wl, hw, seed=0, zipf_s=c.zipf_s)
        assert_bitwise_equal_results(e.result, ind, label=c.label)


def _mk_request(rng, model, nv, num_segments, num_sources, lpv=8):
    base = rng.integers(0, 100_000, size=nv).astype(np.int64) * lpv
    lines = (base[:, None] + np.arange(lpv)[None, :]).reshape(-1)
    seg = np.sort(rng.integers(0, num_segments, size=nv))
    seg = np.repeat(seg, lpv)
    src = np.repeat(rng.integers(0, num_sources, size=nv), lpv)
    return DramRequest(lines, seg, src, num_segments, num_sources, model)


def test_dram_batcher_bit_exact_vs_unbatched(rng):
    """Cross-memo-key batching: every request's DramResults and per-source
    finish matrix equal its unbatched dispatch — including multi-core
    contended requests and empty traces."""
    model = DramModel.from_hardware(tpuv6e())
    reqs = [
        _mk_request(rng, model, 700, 2, 1),
        _mk_request(rng, model, 45, 3, 1),
        _mk_request(rng, model, 400, 2, 4),     # multi-core contended
        DramRequest(np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.int64), 2, 1, model),
        _mk_request(rng, model, 300, 2, 2),
    ]
    batched = dram_timing_many(reqs, batch=True)
    for req, (res_b, fin_b) in zip(reqs, batched):
        res_u, fin_u = dram_timing_single(req)
        assert fin_b.shape == fin_u.shape == (req.num_segments, req.num_sources)
        assert_bitwise_equal_results((res_b, fin_b), (res_u, fin_u))


def test_sweep_batch_dram_flag_bit_exact():
    """batch_dram=False is the unbatched reference path; results identical —
    across single-core AND multi-core cluster grid points."""
    wl = dlrm_rmc2_small(num_tables=2, rows_per_table=1500, dim=128,
                         lookups=4, batch_size=8, num_batches=2)
    grid = dict(policies=("spm", "lru"), capacities=(1 << 16,), ways=(2,),
                zipf_s=0.9, seed=0, num_cores=(1, 2),
                topologies=("private", "shared"))
    a = sweep(wl, tpuv6e(), batch_dram=True, **grid)
    b = sweep(wl, tpuv6e(), batch_dram=False, **grid)
    assert a.num_configs == b.num_configs
    assert_bitwise_equal_results(a, b)


def test_stack_memo_distinguishes_aliasing_views(rng):
    """Two views sharing (pointer, size, dtype) but different strides must
    not share a distance pass."""
    a = rng.integers(0, 50, size=1000).astype(np.int64)
    geom = CacheGeometry(num_sets=4, ways=2, line_bytes=64)
    views = [a[:500], a[::2]]
    got = classify_lru_stack_many(views, [geom, geom])
    for v, (h, ev) in zip(views, got):
        gold = GoldenCache(geom, "lru")
        gold_hits = gold.run(np.ascontiguousarray(v))
        assert np.array_equal(h, gold_hits)
        assert ev == gold.num_evictions


def test_inversion_block_size_keeps_histogram_linear():
    """The radix block grows with n so the (chunk, bucket) histogram stays
    O(n) elements — large traces must not allocate quadratic tables."""
    for n in (1, 100, 46080, 1 << 20, 1 << 24):
        bs = _block_size(n)
        assert bs >= 128 and bs & (bs - 1) == 0
        blocks = -(-n // bs)
        assert blocks * blocks <= max(16 * n, 128 * 128)
    # and the count stays exact at a non-default block size
    rng = np.random.default_rng(3)
    v = rng.permutation(3000).astype(np.int32)
    ref = _inv_prev_larger_np(v, bs=128)
    for bs in (256, 512):
        assert np.array_equal(_inv_prev_larger_np(v, bs=bs), ref)


@pytest.mark.parametrize("split", [False, True], ids=["whole", "16bit"])
@pytest.mark.parametrize("N,bs", [(4096, 128), (4096, 256), (1 << 15, 128)])
def test_jnp_inversion_halves_sum_to_numpy_count(N, bs, split, monkeypatch):
    """The device pass splits the inversion count across two orders: the
    same-bucket half in rank order, the cross-bucket half in position
    order. Together they equal the numpy twin's count at any block size,
    reading the (chunk, bucket) table whole or, as past 2**24 accesses, in
    16-bit halves."""
    if split:
        monkeypatch.setattr(stack_mod, "_F32_EXACT", N // 2)
    rk = np.random.default_rng(N + bs).permutation(N).astype(np.int32)
    p = np.argsort(rk).astype(np.int32)
    same = np.asarray(_same_bucket_jnp(jnp.asarray(p), bs))[rk]
    cross = np.asarray(_cross_bucket_jnp(jnp.asarray(rk), bs))
    assert np.array_equal(same + cross, _inv_prev_larger_np(rk, bs=bs))


@pytest.mark.parametrize("log2_n", [12, 21])
def test_stack_pass_jnp_moves_data_by_sorts(log2_n):
    """The pass's design, read from its lowered program: five sorts carry
    the trace between its orders, two one-hot matmuls build and read the
    inversion count's (chunk, bucket) table, and there is no gather or
    scatter. Those are the slow primitives on a TPU; this fails on the CPU
    if one comes back."""
    N = 1 << log2_n
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    text = jax.jit(
        lambda lines, sets, n: _stack_pass_jnp(lines, sets, n, _block_size(N))
    ).lower(jax.ShapeDtypeStruct((N,), jnp.int32), i32, i32).as_text()
    ops = re.findall(r'"?stablehlo\.(sort|scatter|gather|dot_general)\b', text)
    assert {op: ops.count(op) for op in set(ops)} == {
        "sort": 5, "dot_general": 2,
    }
    # The table read multiplies float32 counts: exact only at HIGHEST.
    dots = re.findall(r"stablehlo\.dot_general .*", text)
    assert sum("precision = [HIGHEST, HIGHEST]" in d for d in dots) == 1


def test_stack_rejects_out_of_range_lines():
    geom = CacheGeometry(num_sets=4, ways=2, line_bytes=64)
    with pytest.raises(ValueError, match="int32"):
        simulate_cache(np.array([2**40]), geom, "lru", backend="stack")


def test_stack_empty_and_single_access():
    geom = CacheGeometry(num_sets=4, ways=2, line_bytes=64)
    res = simulate_cache(np.zeros(0, dtype=np.int64), geom, "lru",
                         backend="stack")
    assert res.accesses == 0 and res.num_evictions == 0
    res1 = simulate_cache(np.array([5]), geom, "lru", backend="stack")
    assert res1.num_misses == 1 and not res1.hits[0]


def test_multicore_cluster_stack_backend_bit_exact():
    """Cluster topologies under the stack backend equal the scan backend
    (shared-LLC classification + contended DRAM downstream of it)."""
    wl = dlrm_rmc2_small(num_tables=2, rows_per_table=1500, dim=128,
                         lookups=4, batch_size=8, num_batches=2)
    base = tpuv6e().with_policy("lru", capacity_bytes=1 << 16, ways=2)
    for cores, topo in ((2, "shared"), (2, "private")):
        hw = base.with_cluster(cores, topo)
        got = simulate(wl, hw.with_cache_backend("stack"), seed=0, zipf_s=0.9)
        ref = simulate(wl, hw.with_cache_backend("scan"), seed=0, zipf_s=0.9)
        assert_bitwise_equal_results(got, ref, label=f"{cores}c-{topo}")
