"""Device-resident hot path: jnp ports vs numpy goldens, the chunked DRAM
engine, compiled-shape guarantees, the cache_backend knob end to end, and
the stage profiler.

The perf overhaul's contract is "same results, different execution": every
jnp port keeps its numpy original as the golden reference, the chunked DRAM
scan must agree with the explicit per-access reference ordering, and the
backend knob must be invisible in simulation outputs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from differential import assert_bitwise_equal_results
from repro.core import dlrm_rmc2_small, simulate, tpuv6e
from repro.core import profiling
from repro.core.hardware import CACHE_BACKENDS
from repro.core.memory.cache import _MIN_BUCKET, _bucket_len
from repro.core.memory.dram import (
    DramModel,
    _frfcfs_order,
    _frfcfs_order_ref,
    simulate_dram,
    simulate_dram_contended,
)
from repro.core.memory.policies import PolicyContext, get_policy
from repro.core.trace import (
    ConcatTrace,
    FullTrace,
    expand_trace,
    generate_zipf_trace,
    shard_lookup_cores,
    shard_lookup_cores_jnp,
    translate,
    translate_jnp,
)
from repro.core.workload import EmbeddingOpSpec


@pytest.fixture
def spec():
    return EmbeddingOpSpec(num_tables=5, rows_per_table=700, dim=64,
                           lookups_per_sample=3, dtype_bytes=4)


def _concat(spec, rng, batches=(4, 7)):
    traces = []
    for i, b in enumerate(batches):
        it = generate_zipf_trace(b * spec.num_tables * spec.lookups_per_sample,
                                 spec.rows_per_table, 0.9, seed=i)
        traces.append(expand_trace(it, spec, b, seed=i))
    return ConcatTrace.from_traces(traces)


# --------------------------------------------------------------------------
# jnp ports vs numpy goldens
# --------------------------------------------------------------------------

def test_translate_jnp_matches_numpy(spec, rng):
    concat = _concat(spec, rng)
    for line_bytes in (64, 128, 96):
        at = translate(concat, spec, line_bytes)
        got = np.asarray(translate_jnp(
            jnp.asarray(concat.table_ids), jnp.asarray(concat.row_ids),
            spec, line_bytes,
        ))
        assert np.array_equal(got, at.lines)


@pytest.mark.parametrize("mode", ["batch", "table_hash"])
@pytest.mark.parametrize("cores", [1, 2, 3, 8])
def test_shard_lookup_cores_jnp_matches_numpy(spec, rng, mode, cores):
    concat = _concat(spec, rng)
    ref = shard_lookup_cores(concat, cores, mode)
    got = np.asarray(shard_lookup_cores_jnp(concat, cores, mode))
    assert np.array_equal(got, ref)


def test_policy_classify_jnp_matches_numpy(rng):
    lines = rng.integers(0, 5000, size=2000).astype(np.int64)
    hw = tpuv6e().with_onchip(capacity_bytes=1 << 16)
    for name in ("spm", "pinning"):
        pol = get_policy(name)
        ctx = pol.prepare(lines, PolicyContext.from_hardware(hw))
        ref = pol.classify(lines, ctx)
        got = np.asarray(pol.classify_jnp(jnp.asarray(lines), ctx))
        assert np.array_equal(got, ref), name


# --------------------------------------------------------------------------
# DRAM: FR-FCFS fast ordering + chunked engine
# --------------------------------------------------------------------------

def test_frfcfs_fast_order_matches_reference(rng):
    dm = DramModel.from_hardware(tpuv6e())
    for trial in range(4):
        n = int(rng.integers(100, 5000))
        lines = rng.integers(0, 1_000_000, size=n)
        seg = np.sort(rng.integers(0, 3, size=n)) if trial % 2 else None
        ch, bk, _row = dm.decompose(lines)
        blk = lines // dm.lines_per_block
        fast = _frfcfs_order(ch, bk, blk, dm.banks_per_channel, dm.channels, seg=seg)
        ref = _frfcfs_order_ref(ch, bk, blk, dm.banks_per_channel, dm.channels, seg=seg)
        assert np.array_equal(fast, ref)


def test_chunked_dram_segment_independence(rng):
    """A segment timed inside a larger contended dispatch must match the
    same segment timed alone — including total latency, which is reduced on
    the host in original access order precisely to be layout-independent."""
    dm = DramModel.from_hardware(tpuv6e())
    v = rng.integers(0, 100_000, size=1500)
    lines = (v[:, None] * 8 + np.arange(8)[None, :]).reshape(-1)
    seg = np.sort(rng.integers(0, 3, size=lines.size))
    src = rng.integers(0, 2, size=lines.size)
    got, fin = simulate_dram_contended(lines, seg, src, 3, 2, dm)
    for s in range(3):
        ref = simulate_dram(lines[seg == s], dm)
        assert_bitwise_equal_results(got[s], ref, label=f"segment {s}")
        assert fin[s].max() + 0.0 == pytest.approx(got[s].finish_cycle)


# --------------------------------------------------------------------------
# Length bucketing: padding bound + compiled-shape count
# --------------------------------------------------------------------------

def test_bucket_len_padding_bound():
    """A sub-trace is never padded by more than 2x (above the floor)."""
    for n in list(range(1, 300)) + [1000, 4097, 100_000]:
        b = _bucket_len(n)
        assert b >= n
        assert b <= max(_MIN_BUCKET, 2 * n)


def test_bucket_len_compile_count_logarithmic():
    """O(log N) distinct padded shapes across every trace length up to N —
    the compiled-scan reuse guarantee the smaller floor must preserve."""
    N = 1 << 20
    distinct = {_bucket_len(n) for n in range(1, N + 1, 97)}
    import math
    assert len(distinct) <= math.ceil(math.log2(N / _MIN_BUCKET)) + 2


# --------------------------------------------------------------------------
# cache_backend knob end to end
# --------------------------------------------------------------------------

def test_cache_backend_bit_exact_end_to_end():
    """simulate() under every cache backend (Pallas variants in interpret
    mode on CPU) equals the scan backend for a cache-mode policy, bit for
    bit — the knob can never change results."""
    wl = dlrm_rmc2_small(num_tables=2, rows_per_table=300, batch_size=2,
                         num_batches=2)
    base = tpuv6e().with_policy("lru", capacity_bytes=1 << 14)
    assert set(CACHE_BACKENDS) == {"scan", "pallas", "stack", "stack_pallas"}
    ref = simulate(wl, base.with_cache_backend("scan"), seed=0, zipf_s=0.9)
    for backend in ("pallas", "stack", "stack_pallas"):
        got = simulate(wl, base.with_cache_backend(backend), seed=0, zipf_s=0.9)
        assert_bitwise_equal_results(got, ref, label=backend)


def test_cache_backend_validation():
    with pytest.raises(ValueError, match="cache backend"):
        tpuv6e().with_cache_backend("nope")


# --------------------------------------------------------------------------
# Stage profiler
# --------------------------------------------------------------------------

def test_profiling_stages_cover_hot_path():
    wl = dlrm_rmc2_small(num_tables=2, rows_per_table=400, batch_size=4,
                         num_batches=2)
    hw = tpuv6e().with_policy("lru", capacity_bytes=1 << 15)
    # Default (stack) backend: LRU classification shows up as the
    # stack_distance stage; the scan backend reports cache_scan instead.
    with profiling.collect() as prof:
        simulate(wl, hw, seed=0, zipf_s=0.9)
    got = prof.breakdown()
    for name in ("trace_gen", "classify", "stack_distance", "dram"):
        assert name in got, got
        assert got[name] >= 0.0
    with profiling.collect() as prof_scan:
        simulate(wl, hw.with_cache_backend("scan"), seed=0, zipf_s=0.9)
    got_scan = prof_scan.breakdown()
    for name in ("trace_gen", "classify", "cache_scan", "dram", "host_sync"):
        assert name in got_scan, got_scan
        assert got_scan[name] >= 0.0
    # exclusive accounting: stages don't double-count nested children
    assert sum(got_scan.values()) < 60.0


def test_profiling_disabled_reports_nothing():
    wl = dlrm_rmc2_small(num_tables=2, rows_per_table=400, batch_size=2,
                         num_batches=1)
    simulate(wl, tpuv6e(), seed=0)     # no collect() active: must not record
    with profiling.collect() as prof:
        pass
    assert prof.breakdown() == {}


def test_profiler_trace_shows_stage_spans_without_a_session(tmp_path, monkeypatch):
    """An open ``jax.profiler`` trace holds a ``stage.<name>`` host span for
    every stage a session would time, opens no session and so makes no
    stage block on the device; a session inside the trace times the same
    stages as one outside it."""
    import glob

    import jax
    from repro.core.memory import cache, dram, rrip, stack

    wl = dlrm_rmc2_small(num_tables=2, rows_per_table=400, batch_size=4,
                         num_batches=2)
    hw = tpuv6e().with_policy("lru", capacity_bytes=1 << 15)
    gates = []
    for mod in (cache, dram, rrip, stack):
        monkeypatch.setattr(mod, "_profiling_active",
                            lambda: gates.append(profiling.is_active()) or gates[-1])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        simulate(wl, hw, seed=0, zipf_s=0.9)
        traced_gates = list(gates)
        with profiling.collect() as traced:
            simulate(wl, hw, seed=1, zipf_s=0.9)
    with profiling.collect() as untraced:
        simulate(wl, hw, seed=1, zipf_s=0.9)
    assert traced_gates and not any(traced_gates)
    assert all(gates[len(traced_gates):])

    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines for e in line.events}
    spans = {n[len(profiling.SPAN_PREFIX):] for n in names
             if n.startswith(profiling.SPAN_PREFIX)}
    assert {"trace_gen", "classify", "stack_distance", "dram"} <= spans
    assert spans == set(traced.seconds) == set(untraced.seconds)
