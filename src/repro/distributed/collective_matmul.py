"""Overlapped collective matmul (shard_map ring, reduce-scatter style).

The beyond-paper §Perf lever for collective-bound cells. Row-parallel TP
(``y = psum(x_loc @ w_loc)``) exposes one big all-reduce after the dot. The
ring version splits the output into ``n`` chunks and interleaves
collective-permutes with per-chunk dots, so each hop's ICI transfer hides
behind the next chunk's MXU work:

  at step t, device d sends its partial sum for chunk (d - t) mod n and
  folds in its own partial for the incoming chunk; after n-1 hops device d
  holds the fully-reduced chunk (d+1) mod n (reduce-scatter), which a final
  all-gather (or the next layer's sharding) reassembles.

In the lowered HLO the all-reduce disappears in favor of n-1
collective-permutes interleaved with dots (asserted by tests and inspected in
the dry-run HLO).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _own_chunk(x_loc, w_loc, c, n_chunks):
    nc = w_loc.shape[-1] // n_chunks
    w_c = jax.lax.dynamic_slice_in_dim(w_loc, c * nc, nc, axis=-1)
    return x_loc @ w_c


def ring_matmul(
    x: jax.Array,        # (..., M, K) sharded on K over `axis`
    w: jax.Array,        # (K, N) sharded on K over `axis`; N % axis_size == 0
    mesh: Mesh,
    axis: str = "model",
) -> jax.Array:          # (..., M, N) fully reduced, replicated on `axis`
    n = mesh.shape[axis]

    def body(x_loc, w_loc):
        idx = jax.lax.axis_index(axis)
        perm = [(j, (j + 1) % n) for j in range(n)]

        def step(t, msg):
            msg = jax.lax.ppermute(msg, axis, perm)
            c = (idx - t - 1) % n
            return msg + _own_chunk(x_loc, w_loc, c, n)

        msg = _own_chunk(x_loc, w_loc, idx % n, n)
        msg = jax.lax.fori_loop(0, n - 1, step, msg)
        # device d now holds fully-reduced chunk (d+1) % n
        gathered = jax.lax.all_gather(msg, axis)          # (n, ..., M, Nc)
        order = (jnp.arange(n) - 1) % n                   # chunk j lives at (j-1)%n
        gathered = jnp.take(gathered, order, axis=0)
        return jnp.concatenate(jnp.split(gathered, n, axis=0), axis=-1)[0]

    # replication is established by the final gather (check disabled)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(*(None,) * (x.ndim - 1), axis), P(axis, None)),
        out_specs=P(*(None,) * (x.ndim - 1), None),
        check_vma=False,
    )(x, w)


def psum_matmul(x, w, mesh, axis="model"):
    """Baseline: local partial matmul + one all-reduce (no overlap)."""

    def body(x_loc, w_loc):
        return jax.lax.psum(x_loc @ w_loc, axis)

    # psum output is replicated by construction (check disabled)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(*(None,) * (x.ndim - 1), axis), P(axis, None)),
        out_specs=P(*(None,) * (x.ndim - 1), None),
        check_vma=False,
    )(x, w)
