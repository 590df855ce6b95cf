"""Compute/communication overlap: ring all-gather matmul via ppermute.

The FSDP pattern ``y = x @ all_gather(w, axis)`` serializes a full weight
all-gather before the matmul.  The ring version decomposes the matmul over
the weight shards: at ring step s each device multiplies with the shard it
currently holds while ppermute-ing it onward, so ICI transfer of shard s+1
hides under the MXU time of shard s.  Exposed collective time drops from
``(n-1)/n · |W| / bw`` to ~one shard, provided per-shard matmul time ≥
per-shard transfer time (napkin check in EXPERIMENTS.md §Perf).

``ring_allgather_matmul`` is written for ``jax.shard_map`` over the FSDP
axis; ``reference_allgather_matmul`` is the oracle.  Both are exercised in
tests (1-device ring degenerates to a plain matmul; the ring arithmetic is
additionally validated by a manual multi-shard simulation in
tests/test_collectives.py).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def reference_allgather_matmul(x: jax.Array, w_shard: jax.Array,
                               axis_name: str) -> jax.Array:
    """Oracle: gather the full weight, then one big matmul."""
    w = jax.lax.all_gather(w_shard, axis_name, axis=0, tiled=True)
    return x @ w


def ring_allgather_matmul(x: jax.Array, w_shard: jax.Array,
                          axis_name: str) -> jax.Array:
    """x: (..., d) replicated over the ring axis; w_shard: (d/n, f) — this
    device's shard of the d-sharded weight.  Returns x @ W (full)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    shard_rows = w_shard.shape[0]

    def step(s, carry):
        acc, w_cur = carry
        # shard currently held started at device (idx - s) mod n
        src = (idx - s) % n
        x_slice = jax.lax.dynamic_slice_in_dim(
            x, src * shard_rows, shard_rows, axis=x.ndim - 1)
        acc = acc + x_slice @ w_cur
        w_nxt = jax.lax.ppermute(
            w_cur, axis_name, [(i, (i + 1) % n) for i in range(n)])
        return acc, w_nxt

    acc0 = jnp.zeros(x.shape[:-1] + (w_shard.shape[1],),
                     jnp.promote_types(x.dtype, w_shard.dtype))
    acc, _ = jax.lax.fori_loop(0, n, step, (acc0, w_shard))
    return acc.astype(x.dtype)


def make_overlapped_matmul(mesh: Mesh, axis: str = "data"):
    """shard_map-wrapped ring matmul: weights d-sharded over ``axis``."""
    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(), P(axis, None)), out_specs=P(),
             check_vma=False)
    def f(x, w):
        return ring_allgather_matmul(x, w, axis)
    return f
