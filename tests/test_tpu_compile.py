"""The main path's device programs compile for a TPU v5e, without a chip.

JAX describes a ``v5e:2x2`` topology on the CPU and the TPU compiler
compiles for one of its chips, so what Mosaic or XLA would refuse on the
chip fails here: block shapes off the (8, 128) tiling, primitives without a
Mosaic lowering, unaligned dynamic vector loads, programs that do not fit in
HBM. Nothing runs, so these say nothing about results or speed.

Shapes are the cache engine's real buckets on the paper's Table I trace with
16-set groups: ~8192 groups of length 256 at 128 MB, 64 groups of length
32768 at 1 MB; and the jnp stack-distance pass at 2^18 and 2^21 accesses.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.memory.stack import _block_size, _stack_pass_jnp
from repro.kernels.cache_scan import cache_scan_groups
from repro.kernels.stack_distance import stack_distance_groups

BUCKETS = [(8192, 256), (64, 32768)]
# The stack pass's padded lengths in the benchmark's cells: Command R+'s
# 1.57M line accesses, and Table I's lane-transformed stream.
STACK_PASS_LENGTHS = [1 << 18, 1 << 21]
GROUP_SETS, WAYS = 16, 16
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described chip's programs can be written to the persistent cache
    but not read back; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("B,L", BUCKETS)
@pytest.mark.parametrize("policy", ["lru", "srrip", "fifo"])
def test_cache_scan_compiles_for_v5e(policy, B, L, one_chip, no_persistent_cache):
    compiled = _compile(
        lambda s, t, v: cache_scan_groups(s, t, v, GROUP_SETS, WAYS, policy,
                                          interpret=False),
        one_chip, ((B, L), jnp.int32), ((B, L), jnp.int32), ((B, L), jnp.bool_),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,L", BUCKETS)
def test_stack_distance_compiles_for_v5e(B, L, one_chip, no_persistent_cache):
    compiled = _compile(
        lambda s, t, v: stack_distance_groups(s, t, v, GROUP_SETS, WAYS,
                                              interpret=False),
        one_chip, ((B, L), jnp.int32), ((B, L), jnp.int32), ((B, L), jnp.bool_),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("N", STACK_PASS_LENGTHS)
def test_stack_pass_jnp_compiles_for_v5e(N, one_chip, no_persistent_cache):
    compiled = _compile(
        lambda lines, sets, n: _stack_pass_jnp(lines, sets, n, _block_size(N)),
        one_chip, ((N,), jnp.int32), ((), jnp.int32), ((), jnp.int32),
    )
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < V5E_HBM_BYTES
