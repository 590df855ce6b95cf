"""Pallas kernel for the lockstep scan's step-commit — the inner hot loop.

Every step of the candidate-axis scan (:mod:`repro.core.jaxsim`) ends in
the same commit over the ``[P, S, B]`` lane-last state: pick the first
free slot of each lane's dispatch pool (min over the pool's slot clocks,
first-minimum tie-break like the reference heap), push the clock to the
task's end time, and fold the busy/seen per-pool accumulators.  That
commit is the densest part of the step body — a pool-select, a slot
argmin and three masked scatters over the full state — and on a TPU it is
exactly the shape the VPU wants: lane axis last (the 128-lane axis),
pool × slot as sublanes.

This kernel fuses the whole commit into one ``pl.pallas_call`` with the
grid over lane blocks, following the BlockSpec idiom of
:mod:`repro.kernels.block_matmul`.  Scatters become masked selects
(``broadcasted_iota`` comparisons) because pallas has no scatter — which
is also why the fusion wins: the lax path materialises gather/scatter
index ops per step, the kernel is pure elementwise/reduce traffic.

Where it compiles: with ``interpret=False`` the kernel compiles for a TPU
(Mosaic) with float32 state and x64 *off* — ``tests/test_tpu_compile.py``
compiles it for a described v5e.  It does not compile under x64 in any
dtype: XLA passes no float64 operand into a Mosaic kernel, and with
float32 state the int64 index arithmetic that x64 brings fails to
legalize.  The jax scan runs under x64 with 64-bit state, so it always
uses the lax commit and does not call this kernel; the kernel returns to
the scan once time fits 32 bits (integer ticks).  ``interpret=True``
evaluates the same kernel body in Python on any backend and dtype, which
is how CPU CI checks it against a numpy oracle.  Booleans cross the
kernel boundary as int32 masks (TPU VMEM has no bool tiles).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _commit_kernel(p_ref, rt_ref, base_ref, live_ref, clocks_ref, busy_ref,
                   seen_ref, oclk_ref, obusy_ref, oseen_ref, oend_ref):
    clocks = clocks_ref[...]                              # [P, S, b]
    P, S, b = clocks.shape
    p = p_ref[...].reshape(1, 1, b)                       # lane -> pool id
    rt = rt_ref[...]                                      # [1, b]
    base = base_ref[...]                                  # [1, b]
    live = live_ref[...] != 0                             # [1, b] bool

    pool_ids = jax.lax.broadcasted_iota(jnp.int32, (P, S, b), 0)
    sel = pool_ids == p                                   # lane's pool rows
    big = jnp.asarray(jnp.inf, clocks.dtype)
    cl = jnp.min(jnp.where(sel, clocks, big), axis=0)     # [S, b]
    tmin = jnp.min(cl, axis=0, keepdims=True)             # [1, b]
    # first-minimum slot, exactly the reference argmin tie-break
    slot_ids = jax.lax.broadcasted_iota(jnp.int32, (S, b), 0)
    s = jnp.min(jnp.where(cl == tmin, slot_ids, S), axis=0, keepdims=True)

    start = jnp.maximum(rt, tmin)
    end = start + base                                    # [1, b]

    slot3 = jax.lax.broadcasted_iota(jnp.int32, (P, S, b), 1)
    upd = sel & (slot3 == s.reshape(1, 1, b)) & live.reshape(1, 1, b)
    oclk_ref[...] = jnp.where(upd, end.reshape(1, 1, b), clocks)

    lane_pool = (jax.lax.broadcasted_iota(jnp.int32, (P, b), 0)
                 == p.reshape(1, b)) & live               # [P, b]
    obusy_ref[...] = busy_ref[...] + jnp.where(lane_pool, end - start, 0.0)
    oseen_ref[...] = seen_ref[...] | lane_pool.astype(jnp.int32)
    oend_ref[...] = end


def step_commit(clocks: jax.Array, busy: jax.Array, seen: jax.Array,
                p: jax.Array, rt: jax.Array, base: jax.Array,
                live: jax.Array, *, interpret: bool = True):
    """Fused slot-argmin + clock/busy/seen commit for one scan step.

    ``clocks [P, S, B]``, ``busy/seen [P, B]``, per-lane ``p`` (dispatch
    pool id), ``rt`` (ready time), ``base`` (cost) and ``live`` mask, all
    ``[B]``.  Returns ``(clocks', busy', seen', end)`` with ``end [B]``
    the per-lane finish time (``start + base`` whether or not the lane is
    live — callers mask with ``live`` exactly like the lax path).
    """
    P, S, B = clocks.shape
    bB = min(B, 128)                       # B is a power of two (bucketed)
    grid = (B // bB,)
    dtype = clocks.dtype
    lane2 = lambda i: (0, i)               # noqa: E731 — BlockSpec index map
    row2 = pl.BlockSpec((1, bB), lane2)
    pool2 = pl.BlockSpec((P, bB), lane2)
    state3 = pl.BlockSpec((P, S, bB), lambda i: (0, 0, i))
    oclk, obusy, oseen, oend = pl.pallas_call(
        _commit_kernel,
        grid=grid,
        in_specs=[row2, row2, row2, row2, state3, pool2, pool2],
        out_specs=[state3, pool2, pool2, row2],
        out_shape=[
            jax.ShapeDtypeStruct((P, S, B), dtype),
            jax.ShapeDtypeStruct((P, B), dtype),
            jax.ShapeDtypeStruct((P, B), jnp.int32),
            jax.ShapeDtypeStruct((1, B), dtype),
        ],
        interpret=interpret,
    )(p.reshape(1, B).astype(jnp.int32), rt.reshape(1, B),
      base.reshape(1, B), live.reshape(1, B).astype(jnp.int32),
      clocks, busy, seen.astype(jnp.int32))
    return oclk, obusy, oseen.astype(bool), oend.reshape(B)
