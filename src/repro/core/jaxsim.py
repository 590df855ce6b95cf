"""jax ``lax.scan`` candidate-axis engine — the sweep loop, compiled.

:mod:`repro.core.batchsim` proved the lockstep formulation: every candidate
sharing one :class:`~repro.core.fastsim.FrozenGraph` advances through one
replayed reference event order with per-candidate state stacked on a
candidate ("lane") axis.  Its per-step cost is numpy-*dispatch*-bound
(~20 µs/step of Python/C boundary crossings per task row).  This module
compiles the identical per-step semantics into a single jit-compiled
:func:`jax.lax.scan` over the replayed order, so a whole sweep runs as one
XLA computation with the full per-candidate state carried as scan state on
a device-resident candidate axis.

Invariants (shared with the numpy backend unless stated):

* **Lane-last axis convention.**  Per-candidate state is stacked with the
  lane axis *last* — pool free-slot clocks ``[P, S, B]``, busy ``[P, B]``,
  and the step-indexed ends ``[T+1, B]`` and placements ``[2T+1, B]``
  (:func:`_runner`), which the host turns back into the batchsim layout's
  placement ids ``[n, B]``, so the shared assembly helper
  (:func:`repro.core.replay.lane_results`) serves both backends.
* **rtol tier, not bit-identity.**  The exact engines replicate the
  reference engine's float ops in the reference order; XLA owns its own op
  scheduling, so this engine is pinned at the relaxed tier instead:
  makespans and busy sums within :data:`repro.core.replay.JAX_RTOL`
  (relative) of the reference, placements/pool layouts discrete-identical,
  and rankings stable under the documented tie-break (sub-tolerance
  makespan ties break by candidate submission order).  The scan runs
  under ``jax.enable_x64``, and its ``lax`` step does exact IEEE float64
  time arithmetic on int64 bit patterns (:func:`_bits_add`), so on every
  backend — the TPU, whose own float64 is inexact, included — its
  makespans match the reference bit for bit.
* **Divergence falls back to the exact path.**  The same per-step heap-key
  monotonicity check as batchsim runs *inside* the scan (carried
  ``prev_key`` per lane); lanes whose popped ``(ready_t, tie_break)`` keys
  ever violate it are flagged, their scan state is discarded, and they are
  re-simulated through :func:`~repro.core.fastsim.simulate_fast` — the
  identical contract to the numpy backend.  The megabatch replays no
  order at all: it runs the **heap scan** (:func:`_heap_runner`), a
  second lockstep scan in which each lane pops its own heap.
* **Fixed-bucket lane chunking.**  Lanes are evaluated in chunks padded to
  power-of-two widths (``chunk`` caps the bucket — non-power-of-two caps
  round *down* to a power of two, so the compiled width never exceeds the
  cap and the jit cache stays keyed on a handful of shapes); padding lanes
  replicate a real lane and are dropped before assembly.

Beyond the per-graph protocol, two mechanisms flip the engine's cold-start
economics:

* **Multi-graph megabatch** (:func:`simulate_jax_many`).  One heap-scan
  launch serves *every* graph family of a sweep at once: each family's
  row tables are padded to a shared ``[R, G, ...]`` stack, each lane
  carries its cohort index ``g``, and the step body reads its rows per
  lane.  No order is discovered, replayed or validated; the routing
  around it is :func:`repro.core.replay.simulate_many`.
* **Persistent compile cache** (:class:`repro.core.xlacache.CompileCache`).
  The scan runner is compiled ahead-of-time per shape signature and the
  serialized executable persists in the sweep's DiskCache (``xla``
  namespace), so a warm store turns the multi-second cold compile into a
  millisecond deserialize — across processes and runs, exactly like the
  order library.

The step body's commit (pool select + slot argmin + clock/busy/seen
update) is the pure-``lax`` form on every backend, the TPU included
(``step_impl="auto"`` resolves to ``"lax"``).  The fused pallas kernel
:func:`repro.kernels.lockstep_step.step_commit` stays out of the scan: the
scan carries 64-bit state, and XLA passes no 64-bit operand into a Mosaic
kernel, so the kernel cannot compile inside it until time fits 32 bits
(integer ticks).  ``step_impl="pallas"`` raises with that reason.
"""
from __future__ import annotations

import collections
import functools
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .devices import SystemConfig
from .fastsim import FrozenGraph, simulate_fast
# JAX_RTOL is re-exported here on purpose: it is this engine's tier constant.
from .replay import (BatchStats, JAX_RTOL, Layout,  # noqa: F401
                     MAX_RESCUE_ROUNDS, MIN_LOCKSTEP, PruneContext,
                     RESCUE_MIN, ReplayLibrary, graph_aux, lane_results,
                     simulate_grouped, simulate_many)
from .simulator import SimResult
from .spans import span
from .xlacache import CompileCache
from ..testing import faults

# The jax import is deferred until the engine is actually used: importing
# repro.core (which re-exports simulate_jax) must stay cheap and must not
# load a multithreaded runtime before the exploration engine's process
# pools pick a start method.  _jax() performs the import.

#: Lanes per compiled scan chunk (the bucket cap) on the per-graph path.
#: Chunks are padded up to power-of-two widths so the jit cache is keyed
#: on a handful of shapes; non-power-of-two caps round down to a power of
#: two (the effective cap), so the compiled width never exceeds the cap.
DEFAULT_CHUNK = 64

#: Lane-bucket cap for the multi-graph megabatch: wider than the per-graph
#: default because one heap launch carries every cohort of the sweep, so
#: the fixed per-launch overhead amortises over more lanes.
MEGABATCH_CHUNK = 256

#: Heap-scan launch shapes (:func:`_heap_cohorts`): rows pad to a multiple
#: of this, and lanes and cohorts bucket to powers of two no smaller than
#: these floors, so a sweep whose count of heap-bound lanes or cohorts
#: drifts with its costs meets the widths its warm-up compiled.
HEAP_ROW_ALIGN = 128
HEAP_MIN_LANES = 32
HEAP_MIN_COHORTS = 4

#: Valid ``step_impl`` names: ``auto`` resolves to ``lax`` on every
#: backend (the scan's 64-bit state cannot enter the Mosaic kernel — see
#: :func:`resolve_step_impl`).
STEP_IMPLS = ("auto", "lax")

#: Fallback in-memory compile cache for bare ``simulate_jax`` calls with no
#: Explorer-owned cache: still deduplicates compiles within the process.
_MEM_COMPILE_CACHE = CompileCache()


def _jax():
    """``(jax, jnp)``, imported on first use."""
    import jax
    import jax.numpy as jnp
    return jax, jnp


def _bucket(n: int, cap: int) -> int:
    """Smallest power of two >= ``n``, clamped to ``[min(8, cap'), cap']``
    where ``cap'`` is ``cap`` rounded *down* to a power of two.

    The result is always a power of two and never exceeds ``cap`` — a
    non-power-of-two cap (say ``jax_chunk=48``) must not leak odd compiled
    widths (48-lane buckets) into the jit cache, and must never compile
    *wider* than the user asked."""
    cap_p = 1
    while cap_p * 2 <= cap:
        cap_p *= 2
    b = min(8, cap_p)
    while b < n and b * 2 <= cap_p:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Exact float64 time, carried as int64 bit patterns
# ---------------------------------------------------------------------------
#
# A TPU emulates float64 at reduced precision: on a v5e, 9,146 of 10,000
# random sums of scan-sized times differed from IEEE float64 (max relative
# error 6.1e-15), and even a round trip through the device rounds the
# value.  The scan's in-flight heap-key check compares ready times for
# exact ties, so with float64 state every lane of a tie-rich graph read
# as diverged on the chip and went to the host fallback: rankings stayed
# right, but the chip did no useful work.  The lax step therefore carries
# each time as the int64 bit pattern of its float64 value, on every
# backend.  Times are never negative, and for non-negative IEEE doubles
# (+inf included) integer order *is* numeric order, so min / max / argmin
# / compares work unchanged on the patterns; only ``+`` and
# ``-`` need the round-to-nearest-even integer arithmetic below — exact,
# hence bit-identical to float64 wherever it runs.

_FRAC = 52                         # float64 fraction bits
_GUARD = 8                         # extra low bits through add / subtract
_LEAD = 1 << (_FRAC + _GUARD)      # a normalised significand's top bit
_INF_BITS = 0x7FF0000000000000     # +inf; larger than every finite time


def _unpack(x):
    """``(exponent, significand << _GUARD)`` of non-negative float64 bit
    patterns, with subnormals at the effective exponent 1."""
    _, jnp = _jax()
    e = x >> _FRAC
    frac = x & ((1 << _FRAC) - 1)
    m = jnp.where(e > 0, frac | (1 << _FRAC), frac)
    return jnp.maximum(e, 1), m << _GUARD


def _shift_jam(m, d):
    """``m >> d`` with every bit shifted out OR-ed into bit 0."""
    _, jnp = _jax()
    d = jnp.minimum(d, 62)
    kept = m >> d
    return kept | ((kept << d) != m).astype(m.dtype)


def _pack(e, v):
    """Round ``v`` (top bit at ``_LEAD``, or below it only at exponent 1)
    to nearest-even and encode; a carry out of the significand bumps the
    exponent by itself, and overflow clamps to +inf."""
    _, jnp = _jax()
    m = v >> _GUARD
    rem = v & ((1 << _GUARD) - 1)
    half = 1 << (_GUARD - 1)
    up = (rem > half) | ((rem == half) & ((m & 1) == 1))
    return jnp.minimum(((e - 1) << _FRAC) + m + up.astype(v.dtype),
                       _INF_BITS)


def _bits_add(a, b):
    """IEEE float64 ``a + b`` on non-negative bit patterns."""
    _, jnp = _jax()
    x, y = jnp.maximum(a, b), jnp.minimum(a, b)
    ex, mx = _unpack(x)
    ey, my = _unpack(y)
    v = mx + _shift_jam(my, ex - ey)
    carry = v >= (_LEAD << 1)
    v = jnp.where(carry, (v >> 1) | (v & 1), v)
    out = _pack(ex + carry.astype(ex.dtype), v)
    return jnp.where(x >= _INF_BITS, _INF_BITS, out)


def _bits_sub(x, y):
    """IEEE float64 ``x - y`` on non-negative bit patterns, ``x >= y``."""
    jax, jnp = _jax()
    ex, mx = _unpack(x)
    ey, my = _unpack(y)
    v = mx - _shift_jam(my, ex - ey)
    # normalise left until the top bit reaches _LEAD, stopping at the
    # subnormal exponent; only cancellation (exponents within one, so no
    # bit was jammed) shifts by more than one place
    top = 63 - jax.lax.clz(v)
    shift = jnp.clip(_FRAC + _GUARD - top, 0, ex - 1)
    out = _pack(ex - shift, v << shift)
    out = jnp.where(v == 0, 0, out)
    return jnp.where(x >= _INF_BITS, _INF_BITS, out)


def resolve_step_impl(step_impl: str) -> str:
    """The step implementation the scan compiles with.

    The scan state is 64-bit (the scan runs under ``jax.enable_x64``).
    XLA refuses to pass 64-bit operands into a Mosaic (TPU pallas) kernel,
    so the compiled kernel cannot run inside the scan on any backend:
    ``auto`` resolves to ``lax``, and ``pallas`` raises — never a silent
    fall back to the interpreter or to another engine."""
    if step_impl == "pallas":
        jax, _ = _jax()
        backend = jax.default_backend()
        why = (f"the backend is {backend!r}, and the compiled kernel runs "
               f"only on a TPU" if backend != "tpu" else
               "the scan carries 64-bit state, and a Mosaic kernel takes "
               "no 64-bit operands")
        raise ValueError(f"step_impl='pallas' cannot compile here: {why}; "
                         f"use 'lax' (what 'auto' resolves to)")
    if step_impl not in STEP_IMPLS:
        raise ValueError(f"unknown step_impl {step_impl!r}: valid names are "
                         + ", ".join(repr(s) for s in STEP_IMPLS))
    return "lax"


# ---------------------------------------------------------------------------
# The compiled scan runners: the replay scan (the per-graph path) and the
# heap scan (the megabatch)
# ---------------------------------------------------------------------------


def _choose(opts, cost, rt, minc, kind_pool, smp_kid, eft, aB):
    """Vectorised reference `_choose_kind` over all lanes: options visited
    in annotation order, strict < on (key, pref) — the lowest-index winner,
    identical tie-breaks to the exact engines.  ``opts [B, K]`` / ``cost
    [B, NK]`` are each lane's own row's tables; ``minc [P, B]`` is the
    step's hoisted earliest-free-slot reduction, so each option costs a
    [B] gather instead of its own [B, S] min.  Shared by both scans."""
    _, jnp = _jax()
    B = aB.shape[0]
    zero = jnp.zeros((), dtype=minc.dtype)
    best_k = jnp.full((B,), -1, dtype=jnp.int32)
    bv = jnp.zeros((B,), dtype=minc.dtype)
    bp = jnp.zeros((B,), dtype=jnp.int32)
    for j in range(opts.shape[1]):              # K is static and tiny
        k = opts[:, j]
        kk = jnp.maximum(k, 0)
        pi = kind_pool[aB, kk]                                  # [B]
        valid = (k >= 0) & (pi >= 0)
        base = cost[aB, kk]
        t = minc[jnp.maximum(pi, 0), aB]
        start = jnp.maximum(rt, t)
        keyv = _bits_add(start, jnp.where(eft, base, zero))
        pref = (k == smp_kid).astype(jnp.int32)
        better = valid & ((best_k < 0) | (keyv < bv)
                          | ((keyv == bv) & (pref < bp)))
        bv = jnp.where(better, keyv, bv)
        bp = jnp.where(better, pref, bp)
        best_k = jnp.where(better, k, best_k)
    return best_k


def _commit(clocks, busy, seen, p, rt, base, live, aB):
    """Slot argmin + clock/busy/seen update — the step's dense tail, shared
    by both scans."""
    _, jnp = _jax()
    cl = clocks[p, :, aB]                                       # [B, S]
    s = jnp.argmin(cl, axis=1)                  # first-minimum, like ref
    tmin = cl[aB, s]
    start = jnp.maximum(rt, tmin)
    end = _bits_add(start, base)
    clocks = clocks.at[p, s, aB].set(
        jnp.where(live, end, clocks[p, s, aB]))
    b0 = busy[p, aB]
    busy = busy.at[p, aB].set(
        jnp.where(live, _bits_add(b0, _bits_sub(end, start)), b0))
    seen = seen.at[p, aB].set(seen[p, aB] | live)
    return clocks, busy, seen, end


@functools.lru_cache(maxsize=None)
def _runner():
    """The pure scan function.

    The step carries every time (clocks, ends, costs, busy, makespan) as
    its float64 bit pattern in int64, with exact ``+``/``-``
    (:func:`_bits_add`, :func:`_bits_sub`).

    **Step-indexed state.**  Nothing the step writes is indexed by task
    row.  ``ends [T+1, B]`` holds, at row ``t``, the end time step ``t``
    left (a skipped conditional row's ready time passes through); a row's
    ready time is *pulled* as the max over its predecessors' step
    positions (``xs["pred"]``, padded to the slot ``T`` that stays 0).
    ``placed [2T+1, B]`` holds, at ``2t``, the placement of step ``t``'s
    row after the step and, at ``2t + 1``, that of its conditional parent
    (whose device a child may decide before the parent's own step); a
    placement is read at its latest earlier touch (``xs["at_own"]``,
    ``xs["at_par"]``, padded to the slot ``2T`` that stays -1).  Each step
    writes both arrays once, in place, at the shared step counter: on a
    TPU a per-step scatter into row-indexed ``[n+1, B]`` state moves the
    whole array through vector memory every step.  :func:`_scan_cohorts`
    turns ``placed`` back into row placements on the host.

    All shapes are multi-cohort form and **lane-aligned**: step inputs ``xs``
    carry the lane axis directly (``[T, B, ...]`` — each lane's cohort
    rows pre-gathered on the host by :func:`_scan_cohorts`), and per-step
    ``valid`` masks make the task-axis padding inert.  Keeping the cohort
    gathers out of the compiled body matters on CPU, where the scan is
    dispatch-bound: a dozen per-step gather ops cost more than the dense
    math they feed.  It also keeps the cohort count out of the shape
    signature, so warm-run routing drift (cohorts splitting as orders are
    discovered) cannot invalidate the compile cache.  Compiled
    ahead-of-time per shape signature via
    :class:`~repro.core.xlacache.CompileCache` (see :func:`_load_runner`).
    """
    jax, jnp = _jax()

    def run(xs, clocks, ends, placed, busy, seen, kind_pool, smp_kid, eft):
        # the scan's operations carry a stable name in the HLO metadata
        with jax.named_scope("lockstep_scan"):
            B = clocks.shape[2]
            aB = jnp.arange(B)
            zero = jnp.zeros((), dtype=clocks.dtype)

            def step(carry, x):
                (t, clocks, ends, placed, busy, seen, makespan, prev_rt,
                 prev_tb, div) = carry
                valid = x["valid"]                                  # [B]
                # pull, not push: the row's ready time is the latest end of
                # its predecessors, read at the steps that ran them (the
                # pad slot T_max holds 0, the roots' ready time)
                rt = jnp.max(ends[x["pred"], aB[:, None]], axis=1)  # [B]
                tbv = x["tb"]
                # heap-key monotonicity: a lane whose popped (ready_t, tb)
                # key ever fails to strictly increase is not executing its
                # own heap order — flag it for the exact fallback.  Invalid
                # (padding) steps read only pad slots, so every check and
                # write below is gated on `valid`.
                div = div | (valid & ((rt < prev_rt)
                                      | ((rt == prev_rt) & (tbv <= prev_tb))))
                # (div also absorbs bad dispatches below: any lane that
                # *live*-executes a row the reference would raise on takes
                # the exact fallback, which re-raises — or completes when
                # the lane never actually reaches the row under its own
                # order)

                # earliest-free slot per (pool, lane), shared by both choose
                # passes (clocks are only committed after them)
                minc = jnp.min(clocks, axis=1)                      # [P, B]

                # ---- conditional pass-through (per-lane mask) ---------------
                # the parent's placement as its latest earlier touch left it
                # (pad slot 2 T_max holds -1: not placed yet)
                has_cond = (x["c"] >= 0) & valid
                pk = placed[x["at_par"], aB]                        # [B]
                chosen_p = _choose(x["par_opts"], x["par_cost"], rt, minc,
                                   kind_pool, smp_kid, eft, aB)
                pk = jnp.where(pk < 0, chosen_p, pk)
                live = jnp.where(has_cond, x["act"][aB, jnp.maximum(pk, 0)],
                                 True) & valid

                # ---- dispatch + commit for the lanes executing the row ------
                k_own = placed[x["at_own"], aB]
                und = k_own < 0
                chosen_o = _choose(x["own_opts"], x["own_cost"], rt, minc,
                                   kind_pool, smp_kid, eft, aB)
                is_comp = x["is_comp"]
                k = jnp.where(is_comp, jnp.where(und, chosen_o, k_own),
                              x["k_first"])
                k_row = jnp.where(is_comp & live & und, k, k_own)
                div = div | (live & (x["bad_row"] | (k < 0)))
                kk = jnp.maximum(k, 0)
                p = jnp.maximum(kind_pool[aB, kk], 0)               # [B]
                base = x["own_cost"][aB, kk]                        # [B]
                clocks, busy, seen, end = _commit(clocks, busy, seen, p, rt,
                                                  base, live, aB)
                end_eff = jnp.where(live, end, jnp.where(valid, rt, zero))
                makespan = jnp.maximum(makespan, end_eff)
                # one in-place row write each at the shared step counter:
                # the row's end, its placement (slot 2t) and its conditional
                # parent's (slot 2t + 1)
                ends = jax.lax.dynamic_update_index_in_dim(ends, end_eff, t, 0)
                placed = jax.lax.dynamic_update_slice_in_dim(
                    placed, jnp.stack([k_row, pk]), 2 * t, 0)
                prev_rt = jnp.where(valid, rt, prev_rt)
                prev_tb = jnp.where(valid, tbv, prev_tb)
                return (t + 1, clocks, ends, placed, busy, seen, makespan,
                        prev_rt, prev_tb, div), None

            makespan = jnp.zeros((B,), dtype=clocks.dtype)
            # a negative bit pattern sorts below every (non-negative) time
            prev_rt = jnp.full((B,), -1, dtype=clocks.dtype)
            prev_tb = jnp.full((B,), -1, dtype=xs["tb"].dtype)
            div = jnp.zeros((B,), dtype=bool)
            init = (jnp.zeros((), dtype=jnp.int32), clocks, ends, placed,
                    busy, seen, makespan, prev_rt, prev_tb, div)
            (_t, clocks, ends, placed, busy, seen, makespan, _rt, _tb,
             div), _ = jax.lax.scan(step, init, xs)
            return makespan, busy, seen, placed, div

    return run


#: ``cnt`` of a row a lane has popped: never 0 again, since a popped row
#: is no later row's successor.
_POPPED = -1
#: ``cnt`` of a padding row: never released, so never ready.
_PAD_PREDS = 1 << 30


@functools.lru_cache(maxsize=None)
def _heap_runner():
    """The pure heap-scan function: every lane pops its *own* heap.

    The reference pops its ready set by the key ``(ready_t,
    creation_index, uid)``, a strict total order, and a lane's ready set
    is the rows whose predecessors it has all popped.  So the per-lane
    lexicographic argmin of ``(ready bits, tie-break rank)`` over the rows
    that are ready and not popped is exactly the reference's next pop —
    at zero-cost ties too — and no lane can diverge: this scan needs no
    replayed order and no validation.  The host sorts each cohort's rows
    by the tie-break (:func:`_heap_tables`), so the rank is the row
    index and the first minimum of the ready bits is the pop.

    Per-lane state over the cohort's ``R`` (padded) rows: ``ready [R, B]``
    (int64 float64 bit patterns, the max of the popped predecessors'
    ends), ``cnt [R, B]`` (predecessors still unpopped; ``_POPPED`` once
    popped, ``_PAD_PREDS`` on padding rows) and ``placement [R, B]``,
    beside the pool clocks, busy and seen of the replay scan.  Each step
    pops, runs the replay step's conditional pass-through, :func:`_choose`
    and :func:`_commit` on the popped row, then releases the row's
    successors with its end time.  Every row-indexed write is a dense
    ``where`` over ``[R, B]`` (the popped row's one-hot, and its successor
    bitset row expanded to a mask): on a TPU a per-step scatter into
    row-indexed ``[R, B]`` state moves the whole array through vector
    memory every step.

    Graph tables are per cohort, stacked row-major ``[R, G, ...]`` (so the
    loop's operands share the leading dimension ``R``, its trip count);
    each lane carries its cohort index ``g`` and its own row count
    (``valid`` steps are ``t < nrows``).  A lane that pops a row the
    reference would raise on, or finds nothing ready, comes back flagged
    for the exact path.  Compiled ahead of time per shape signature
    (:func:`_load_heap_runner`); the XLA module is ``jit_run_heap``.
    """
    jax, jnp = _jax()
    lax = jax.lax

    def run_heap(tabs, clocks, kind_pool, smp_kid, g, nrows, eft):
        with jax.named_scope("heap_scan"):
            R = tabs["npred"].shape[0]
            P, _, B = clocks.shape
            aB = jnp.arange(B)
            zero = jnp.zeros((), dtype=clocks.dtype)
            none = jnp.array(np.iinfo(np.int64).max, dtype=clocks.dtype)
            rows = lax.broadcasted_iota(jnp.int32, (R, B), 0)
            shifts = jnp.arange(32, dtype=jnp.uint32)

            def first_min(a, b):
                (ka, ia), (kb, ib) = a, b
                pick = (ka < kb) | ((ka == kb) & (ia < ib))
                return jnp.where(pick, ka, kb), jnp.where(pick, ia, ib)

            def row(name, r):
                """The lanes' own cohorts' table rows at ``r``."""
                return tabs[name][r, g]

            def step(carry, valid):
                (clocks, ready, cnt, placement, busy, seen, makespan,
                 bad) = carry
                # ---- pop: each lane's (ready bits, rank) minimum ------------
                kmin, r = lax.reduce(
                    (jnp.where(cnt == 0, ready, none), rows),
                    (none, jnp.int32(R)), first_min, (0,))
                empty = kmin == none
                bad = bad | (valid & empty)             # nothing ready
                r = jnp.where(empty, 0, r)
                rt = jnp.where(empty, zero, kmin)
                minc = jnp.min(clocks, axis=1)                      # [P, B]

                # ---- conditional pass-through (per-lane mask) ---------------
                c = row("c", r)
                has_cond = (c >= 0) & valid
                cc = jnp.maximum(c, 0)
                pk = placement[cc, aB]
                chosen_p = _choose(row("opts", cc), row("cost", cc), rt, minc,
                                   kind_pool, smp_kid, eft, aB)
                pk = jnp.where(pk < 0, chosen_p, pk)
                live = jnp.where(has_cond,
                                 tabs["act"][r, g, jnp.maximum(pk, 0)],
                                 True) & valid

                # ---- dispatch + commit for the lanes executing the row ------
                k_own = placement[r, aB]
                und = k_own < 0
                cost = row("cost", r)
                chosen_o = _choose(row("opts", r), cost, rt, minc,
                                   kind_pool, smp_kid, eft, aB)
                is_comp = row("is_comp", r)
                k = jnp.where(is_comp, jnp.where(und, chosen_o, k_own),
                              row("k_first", r))
                k_row = jnp.where(is_comp & live & und, k, k_own)
                bad = bad | (live & (row("bad_row", r) | (k < 0)))
                kk = jnp.maximum(k, 0)
                p = jnp.maximum(kind_pool[aB, kk], 0)
                clocks, busy, seen, end = _commit(clocks, busy, seen, p, rt,
                                                  cost[aB, kk], live, aB)
                end_eff = jnp.where(live, end, jnp.where(valid, rt, zero))
                makespan = jnp.maximum(makespan, end_eff)

                # ---- dense row writes: placements, pop, release -------------
                at_r = (rows == r) & valid
                placement = jnp.where(
                    at_r, k_row, jnp.where((rows == cc) & has_cond, pk,
                                           placement))
                words = row("succ", r).T                            # [W, B]
                succ = (((words[:, None, :] >> shifts[None, :, None]) & 1)
                        .reshape(R, B) != 0) & valid
                ready = jnp.where(succ, jnp.maximum(ready, end_eff), ready)
                cnt = jnp.where(at_r, _POPPED, cnt - succ.astype(cnt.dtype))
                return (clocks, ready, cnt, placement, busy, seen, makespan,
                        bad), None

            init = (clocks, jnp.zeros((R, B), dtype=clocks.dtype),
                    tabs["npred"][:, g], jnp.full((R, B), -1, jnp.int32),
                    jnp.zeros((P, B), dtype=clocks.dtype),
                    jnp.zeros((P, B), dtype=bool),
                    jnp.zeros((B,), dtype=clocks.dtype),
                    jnp.zeros((B,), dtype=bool))
            # the scan's step input: [R, B], one step per padded row
            (_c, _rd, _cnt, placement, busy, seen, makespan, bad), _ = \
                lax.scan(step, init, rows < nrows)
            return makespan, busy, seen, placement, bad

    return run_heap


@functools.lru_cache(maxsize=None)
def _code_fingerprint() -> str:
    """Hash of the scan's source file, part of every compile-cache key: a
    persisted executable compiled from an older version of the step
    semantics must miss, never silently serve stale results."""
    try:
        with open(__file__, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:                     # zipped/frozen install: source
        return "unhashable"             # unreadable, env key still applies


def _signature(args: Tuple) -> Tuple:
    """Shape/dtype signature of one runner invocation — the compile-cache
    key body (the environment half lives in CompileCache)."""
    def one(a):
        return (tuple(a.shape), str(a.dtype))
    xs = args[0]
    return (_code_fingerprint(),
            tuple((k, one(v)) for k, v in sorted(xs.items())),
            tuple(one(a) for a in args[1:]))


def _load_runner(cc: CompileCache, args: Tuple):
    """The AOT-compiled executable for this signature: in-memory hit, disk
    deserialize, or fresh ``lower().compile()`` (then persisted)."""
    jax, _ = _jax()
    return cc.load_or_compile(
        _signature(args), lambda: jax.jit(_runner()).lower(*args))


def _load_heap_runner(cc: CompileCache, args: Tuple):
    """:func:`_load_runner` for the heap scan, under its own signatures."""
    jax, _ = _jax()
    return cc.load_or_compile(
        ("heap",) + _signature(args),
        lambda: jax.jit(_heap_runner()).lower(*args))


# ---------------------------------------------------------------------------
# Per-cohort step inputs
# ---------------------------------------------------------------------------


def _bad_rows(fg: FrozenGraph, kind_pool: Sequence[int]) -> np.ndarray:
    """``bool[n]``: rows whose *execution* would make the reference engine
    raise under this pool template — a compute row with an eligible option
    (pool present) carrying a NaN cost or with no compatible pool at all,
    or a non-compute row whose device has no pool / no cost.

    Whether such a row ever executes in a given lane is runtime state
    (conditional rows are skipped when the parent lands on the SMP), so
    the scan cannot raise eagerly like :mod:`repro.core.batchsim` does
    mid-sweep: instead a lane that *live*-dispatches a bad row is flagged
    and re-routed through the exact fallback, where ``simulate_fast``
    raises the reference error — or completes, when the lane's own event
    order never reaches the row."""
    (_uids, _ci, _cond, dev_first, dev_opts, _asets, costs, _succs,
     _npred, is_comp, *_rest) = fg._runtime()
    bad = np.zeros(fg.n, dtype=bool)
    for r in range(fg.n):
        if is_comp[r]:
            any_pool = False
            for k in dev_opts[r]:
                if kind_pool[k] < 0:
                    continue
                any_pool = True
                if costs[r][k] != costs[r][k]:      # NaN on eligible option
                    bad[r] = True
            bad[r] |= not any_pool
        else:
            k0 = dev_first[r]
            bad[r] = kind_pool[k0] < 0 or costs[r][k0] != costs[r][k0]
    return bad


def _pool_caps(fg: FrozenGraph, kind_pool: Sequence[int],
               P: int) -> np.ndarray:
    """``int[P]``: how many rows could *ever* dispatch to each pool —
    computes count toward every eligible pool, non-computes toward their
    device's pool.

    This bounds the slot axis exactly: slots are claimed in prefix order
    (the commit's first-minimum argmin always prefers the lowest-index
    free slot, and every slot starts free), so a pool that receives at
    most ``m`` dispatches can never touch slot ``m`` or beyond — clamping
    a lane's slot count to the cap changes nothing about its schedule.  A
    1000-slot candidate over a 64-task graph then costs a 64-wide slot
    axis, not 1024 (the canonical over-provisioned end of a co-design
    ramp).  Every replayed order holds every row, so the cap is the
    graph's, whatever the order.
    """
    lens = np.diff(fg.dev_indptr)
    first = np.zeros(len(fg.dev_kids), dtype=bool)
    first[fg.dev_indptr[:-1][lens > 0]] = True
    take = fg.is_compute[np.repeat(np.arange(fg.n), lens)] | first
    per_kind = np.bincount(fg.dev_kids[take], minlength=len(fg.kinds))
    kp = np.asarray(kind_pool)
    cap = np.zeros(P, dtype=np.int64)
    np.add.at(cap, kp[kp >= 0], per_kind[kp >= 0])
    return cap


# Per-FrozenGraph cap on memoised (order, kind_pool) -> xs entries.  With
# the multi-order replay library a warm sweep replays one order per
# signature-routed cohort, so the cap matches the library's per-key order
# cap instead of the old one-reference-order assumption.
_XS_CACHE_CAP = 32

# Lane-aligned device blocks, memoised across _scan_cohorts calls: keyed by
# content (per-cohort graph hash × order × pool template), launch dims
# and the slice's cohort-index vector.  Entries are a few MB of device
# arrays each; the cap bounds residency, LRU evicts.
_DEV_XS_CACHE: "collections.OrderedDict[Tuple, Tuple]" = \
    collections.OrderedDict()
_DEV_XS_CACHE_CAP = 16


def _group_xs(fg: FrozenGraph, order: Sequence[int],
              kind_pool: Sequence[int]) -> Dict[str, np.ndarray]:
    """Per-step scan inputs shared by every lane of the cohort, in replay
    order: row ids, tie-break scalars, conditional parents, device options
    and cost rows for the row *and* its conditional parent (the parent's
    placement may be decided at this step), activation-mask rows,
    bad-dispatch flags (:func:`_bad_rows`), and the step-indexed state's
    read positions (see :func:`_runner`): ``pred``, the steps of each
    row's predecessors (pad ``T``); ``at_own`` / ``at_par``, the
    ``placed`` slot of the latest earlier touch of the row / of its
    conditional parent (pad ``2T``); ``fin [n]``, each row's last touch
    over the whole order, which the host reads the final placements at.
    The pads are remapped to the launch's by :func:`_scan_cohorts`.

    Memoised on the FrozenGraph like :func:`~repro.core.replay.graph_aux`
    (repeat sweeps — re-ranks, hillclimbs — replay the same order over the
    same payload many times); dropped on pickling like ``_rt``.
    """
    cache = getattr(fg, "_jax_xs", None)
    if cache is None:
        cache = fg._jax_xs = {}
    ckey = (tuple(order), tuple(kind_pool))
    cached = cache.get(ckey)
    if cached is not None:
        return cached
    (uids, ci, cond, dev_first, dev_opts, asets, costs, _succs,
     _npred, is_comp, rankmaps, *_rest) = fg._runtime()
    n = fg.n
    tb, act_mask = graph_aux(fg, ci, rankmaps[0], asets)
    cost_np = fg.cost
    T = len(order)
    K = max(1, max(len(dev_opts[i]) for i in range(n)) if n else 1)
    n_kinds = len(fg.kinds)
    # predecessor steps: every edge (src -> dst) as pos[src] in the row of
    # pos[dst], in ascending column order (a replayed order is
    # topological, so each predecessor's step comes first)
    pos = np.empty(n, dtype=np.int64)
    pos[np.asarray(order, dtype=np.int64)] = np.arange(T)
    src = np.repeat(np.arange(n), np.diff(fg.succ_indptr))
    e_dst, e_src = pos[fg.succ_rows], pos[src]
    by = np.lexsort((e_src, e_dst))
    e_dst, e_src = e_dst[by], e_src[by]
    indeg = np.bincount(e_dst, minlength=T)
    col = np.arange(len(e_dst)) - np.repeat(np.cumsum(indeg) - indeg, indeg)
    PC = max(1, int(indeg.max(initial=0)))

    xs = {
        "r": np.empty(T, dtype=np.int32),
        "tb": np.empty(T, dtype=np.int64),
        "c": np.empty(T, dtype=np.int32),
        "is_comp": np.empty(T, dtype=bool),
        "k_first": np.empty(T, dtype=np.int32),
        "own_opts": np.full((T, K), -1, dtype=np.int32),
        "own_cost": np.zeros((T, n_kinds), dtype=np.float64),
        "par_opts": np.full((T, K), -1, dtype=np.int32),
        "par_cost": np.zeros((T, n_kinds), dtype=np.float64),
        "act": np.zeros((T, n_kinds), dtype=bool),
        "bad_row": _bad_rows(fg, kind_pool)[list(order)],
        "pred": np.full((T, PC), T, dtype=np.int32),
        "at_own": np.empty(T, dtype=np.int32),
        "at_par": np.full(T, 2 * T, dtype=np.int32),
    }
    xs["pred"][e_dst, col] = e_src
    last = [2 * T] * n                  # each row's latest `placed` slot
    for t, r in enumerate(order):
        xs["r"][t] = r
        xs["tb"][t] = tb[r]
        c = cond[r]
        xs["c"][t] = c
        xs["is_comp"][t] = is_comp[r]
        xs["k_first"][t] = dev_first[r]
        xs["own_opts"][t, :len(dev_opts[r])] = dev_opts[r]
        xs["own_cost"][t] = cost_np[r]
        xs["at_own"][t] = last[r]
        last[r] = 2 * t
        if c >= 0:
            xs["par_opts"][t, :len(dev_opts[c])] = dev_opts[c]
            xs["par_cost"][t] = cost_np[c]
            xs["act"][t] = act_mask[r]
            xs["at_par"][t] = last[c]
            last[c] = 2 * t + 1
    xs["fin"] = np.asarray(last, dtype=np.int32)
    # bad-row flags capture every NaN a live dispatch could reach; scrub
    # the rest so no masked-out lane arithmetic can produce a NaN
    np.nan_to_num(xs["own_cost"], copy=False)
    np.nan_to_num(xs["par_cost"], copy=False)
    if len(cache) >= _XS_CACHE_CAP:
        cache.pop(next(iter(cache)))
    cache[ckey] = xs
    return xs


def _heap_tables(fg: FrozenGraph, kind_pool: Sequence[int]
                 ) -> Dict[str, np.ndarray]:
    """The heap scan's per-cohort graph tables (see :func:`_heap_runner`),
    rows sorted by the heap's tie-break ``(creation_index, uid)`` (the
    replay's ``tb``): ``npred``, ``c`` (the conditional parent's sorted
    position, or -1), ``is_comp``, ``k_first``, ``opts [n, K]``, ``cost [n,
    NK]``, ``act [n, NK]``, ``bad_row`` (:func:`_bad_rows`), ``succ [n,
    W]`` (each row's successors as a bitset over sorted positions, ``W =
    ceil(n / 32)`` words), and ``pos [n]``, each row's sorted position,
    which the host reads the final placements at.  Memoised on the
    FrozenGraph beside :func:`_group_xs`."""
    cache = getattr(fg, "_jax_xs", None)
    if cache is None:
        cache = fg._jax_xs = {}
    ckey = ("heap", tuple(kind_pool))
    cached = cache.get(ckey)
    if cached is not None:
        return cached
    (_uids, ci, cond, dev_first, dev_opts, asets, _costs, _succs,
     _npred, _is_comp, rankmaps, *_rest) = fg._runtime()
    n = fg.n
    tb, act_mask = graph_aux(fg, ci, rankmaps[0], asets)
    order = np.argsort(np.asarray(tb, dtype=np.int64), kind="stable")
    pos = np.empty(n, dtype=np.int64)
    pos[order] = np.arange(n)
    K = max(1, max(len(dev_opts[i]) for i in range(n)) if n else 1)
    opts = np.full((n, K), -1, dtype=np.int32)
    for r in range(n):
        opts[pos[r], :len(dev_opts[r])] = dev_opts[r]
    cond_np = np.asarray(cond, dtype=np.int64)
    src = np.repeat(np.arange(n), np.diff(fg.succ_indptr))
    ps, pd = pos[src], pos[fg.succ_rows]
    succ = np.zeros((n, (n + 31) // 32), dtype=np.uint32)
    np.bitwise_or.at(succ, (ps, pd >> 5),
                     np.left_shift(np.uint32(1), (pd & 31).astype(np.uint32)))
    tabs = {
        "npred": fg.n_pred[order].astype(np.int32),
        "c": np.where(cond_np >= 0, pos[np.maximum(cond_np, 0)],
                      -1)[order].astype(np.int32),
        "is_comp": fg.is_compute[order],
        "k_first": np.asarray(dev_first, dtype=np.int32)[order],
        "opts": opts,
        # NaN costs are flagged as bad rows; scrub them like _group_xs
        "cost": np.nan_to_num(fg.cost[order]),
        "act": act_mask[order],
        "bad_row": _bad_rows(fg, kind_pool)[order],
        "succ": succ,
        "pos": pos,
    }
    if len(cache) >= _XS_CACHE_CAP:
        cache.pop(next(iter(cache)))
    cache[ckey] = tabs
    return tabs


# ---------------------------------------------------------------------------
# Cohort driver: task-axis padding, chunked lanes, shared compiled scan
# ---------------------------------------------------------------------------


def _times(a: np.ndarray) -> np.ndarray:
    """Host float64 times as the scans' int64 bit patterns (see
    :func:`_bits_add`); other arrays unchanged."""
    return a.view(np.int64) if a.dtype == np.float64 else a


def _repad(slots: np.ndarray, pad: int, to: int) -> np.ndarray:
    """A cohort's state positions with its own pad slot moved to the
    launch's."""
    return np.where(slots == pad, to, slots)


def _scan_cohorts(cohorts: Sequence[Tuple[FrozenGraph, Sequence[int],
                                          Sequence[Layout],
                                          Optional[np.ndarray]]],
                  policy: str, *, chunk: int,
                  compile_cache: Optional[CompileCache] = None,
                  step_impl: str = "auto",
                  stats: Optional[BatchStats] = None
                  ) -> List[Tuple[Dict[int, SimResult], List[int],
                                  Dict[int, float]]]:
    """Drive every lane of every ``(fg, order, layouts, cutoffs)`` cohort
    through one shared compiled scan.

    Task-axis padding layout: per-cohort step inputs (:func:`_group_xs`)
    are stacked into ``[T_max, G, ...]`` blocks — steps beyond a cohort's
    own length carry ``valid=False`` and read only the pad slots of the
    step-indexed state (predecessor step ``T_max``, placement slot
    ``2 T_max``), so they update nothing a lane reads; rows/pools/options
    pad to the launch's maxima with inert values (``-1`` options, pad
    predecessors, ``inf`` clocks beyond a lane's slot count).  Lanes from
    *all* cohorts share the bucketed lane axis (``chunk`` caps the bucket;
    padding lanes replicate the last real lane) and one slot axis sized
    to the widest lane.

    Retirement on this engine is **post-scan classification**: the
    ``lax.scan`` trip count is fixed at trace time, so lanes cannot be
    dropped mid-flight without recompiling — instead a cohort with a
    finite ``cutoffs`` entry has its non-diverged lanes whose *final*
    makespan exceeds the cutoff reported as retired (the makespan itself
    is the bound — exact, not an estimate).  Compiled-shape reuse and the
    ``valid`` machinery are untouched; the win is
    protocol-level (retired lanes skip schedule materialisation and rank
    assembly), not scan-time.

    Returns one ``(done, diverged, retired)`` triple per cohort in the
    :data:`repro.core.replay.LockstepFn` contract, positions indexing the
    cohort's own ``layouts``; ``retired`` maps position to its bound.
    ``stats`` receives the scan counters (``scan_lane_steps``,
    ``scan_kept_lane_steps``, ``h2d_bytes``).
    """
    jax, jnp = _jax()
    resolve_step_impl(step_impl)

    cc = compile_cache if compile_cache is not None else _MEM_COMPILE_CACHE
    eft = policy == "eft"

    with span("jaxsim.prep"):
        per = []
        for fg, order, layouts, cuts in cohorts:
            pool_names, _, kind_pool = layouts[0]           # template-shared
            kinds = fg.kinds
            caps = _pool_caps(fg, kind_pool, len(pool_names))
            lane_counts = [lay[1] for lay in layouts]
            per.append({
                "fg": fg, "xs": _group_xs(fg, order, kind_pool), "cuts": cuts,
                "pool_names": pool_names, "kind_pool": list(kind_pool),
                "smp_kid": kinds.index("smp") if "smp" in kinds else -1,
                "lane_counts": lane_counts,
                # slot-axis need per lane: pool slot counts clamped to the
                # dispatch caps (exact — see _pool_caps)
                "needs": [max(1, max((min(int(c), int(caps[p]))
                                      for p, c in enumerate(cnt)), default=1))
                          for cnt in lane_counts],
                "P": len(pool_names),
            })
        G = len(per)
        P_max = max(c["P"] for c in per)
        T_max = max(len(c["xs"]["r"]) for c in per)
        K = max(c["xs"]["own_opts"].shape[1] for c in per)
        NK = max(len(c["kind_pool"]) for c in per)
        PC = max(c["xs"]["pred"].shape[1] for c in per)
        for c in per:
            c["fin"] = _repad(c["xs"]["fin"], 2 * len(c["xs"]["r"]),
                              2 * T_max)
        S = _bucket(max(nd for c in per for nd in c["needs"]), cap=1 << 30)

        kind_pool_m = np.full((G, NK), -1, dtype=np.int32)
        smp_kid_m = np.full((G,), -1, dtype=np.int32)
        for gi, c in enumerate(per):
            nk = len(c["kind_pool"])
            kind_pool_m[gi, :nk] = c["kind_pool"]
            smp_kid_m[gi] = c["smp_kid"]

        _mega_memo: List[Optional[Dict[str, np.ndarray]]] = [None]

        def _mega() -> Dict[str, np.ndarray]:
            """The ``[T_max, G, ...]`` task-axis-padded step-input stack —
            built lazily: a warm repeat sweep whose slices all hit the device
            cache never stacks it at all."""
            if _mega_memo[0] is not None:
                return _mega_memo[0]
            mega = {
                "valid": np.zeros((T_max, G), dtype=bool),
                "tb": np.zeros((T_max, G), dtype=np.int64),
                "c": np.full((T_max, G), -1, dtype=np.int32),
                "is_comp": np.zeros((T_max, G), dtype=bool),
                "k_first": np.zeros((T_max, G), dtype=np.int32),
                "own_opts": np.full((T_max, G, K), -1, dtype=np.int32),
                "own_cost": np.zeros((T_max, G, NK), dtype=np.float64),
                "par_opts": np.full((T_max, G, K), -1, dtype=np.int32),
                "par_cost": np.zeros((T_max, G, NK), dtype=np.float64),
                "act": np.zeros((T_max, G, NK), dtype=bool),
                "bad_row": np.zeros((T_max, G), dtype=bool),
                "pred": np.full((T_max, G, PC), T_max, dtype=np.int32),
                "at_own": np.full((T_max, G), 2 * T_max, dtype=np.int32),
                "at_par": np.full((T_max, G), 2 * T_max, dtype=np.int32),
            }
            for gi, c in enumerate(per):
                xs = c["xs"]
                T = len(xs["r"])
                kg, nk, pc = (xs["own_opts"].shape[1], xs["own_cost"].shape[1],
                              xs["pred"].shape[1])
                mega["valid"][:T, gi] = True
                for f in ("tb", "c", "is_comp", "k_first", "bad_row"):
                    mega[f][:T, gi] = xs[f]
                mega["own_opts"][:T, gi, :kg] = xs["own_opts"]
                mega["par_opts"][:T, gi, :kg] = xs["par_opts"]
                mega["own_cost"][:T, gi, :nk] = xs["own_cost"]
                mega["par_cost"][:T, gi, :nk] = xs["par_cost"]
                mega["act"][:T, gi, :nk] = xs["act"]
                # each cohort's own pad slots (T, 2T) -> the launch's
                mega["pred"][:T, gi, :pc] = _repad(xs["pred"], T, T_max)
                for f in ("at_own", "at_par"):
                    mega[f][:T, gi] = _repad(xs[f], 2 * T, 2 * T_max)
            _mega_memo[0] = mega
            return mega

        # cache key prefix for the lane-aligned device blocks: content-based
        # (graph hash × order × pool template per cohort), so repeat sweeps
        # hit it across fresh Explorers — a warm re-rank re-launches resident
        # device blocks without re-stacking or re-transferring anything
        base_key = (tuple((c["fg"].content_hash(), tuple(c["xs"]["r"]),
                           tuple(c["kind_pool"])) for c in per),
                    (T_max, P_max, K, NK, PC),
                    kind_pool_m.tobytes(), smp_kid_m.tobytes())

        lanes_flat = [(gi, pos) for gi, c in enumerate(per)
                      for pos in range(len(c["lane_counts"]))]
        accs = [{"kept": [], "mk": [], "busy": [], "seen": [], "place": []}
                for _ in per]
        diverged: List[List[int]] = [[] for _ in per]
        retired: List[Dict[int, float]] = [{} for _ in per]
        # the effective power-of-two slice width
        step = _bucket(chunk, cap=chunk)
        slices = [lanes_flat[lo:lo + step]
                  for lo in range(0, len(lanes_flat), step)]

    with jax.enable_x64(True):
        def _put(a: np.ndarray):
            """One host-to-device upload, counted in ``h2d_bytes``."""
            if stats is not None:
                stats.h2d_bytes += a.nbytes
            return jnp.asarray(a)

        # lane-aligned step inputs per distinct cohort-index vector: the
        # host gathers [T, G, ...] -> [T, B, ...] once per slice shape so
        # the compiled body carries no gather ops (and no G in its shape
        # signature).  The device blocks are memoised across calls
        # (module-level LRU): per-graph chunking reuses one upload across
        # its equal-width slices, and warm repeat sweeps re-launch the
        # resident blocks without re-stacking or re-transferring anything.
        def _lane_aligned(g_np: np.ndarray) -> Tuple:
            key = (base_key, g_np.tobytes())
            hit = _DEV_XS_CACHE.get(key)
            if hit is None:
                mega = _mega()
                host = {k: _times(np.ascontiguousarray(v[:, g_np]))
                        for k, v in mega.items()}
                with span("jaxsim.transfer"):
                    hit = ({k: _put(v) for k, v in host.items()},
                           _put(kind_pool_m[g_np]), _put(smp_kid_m[g_np]))
                if len(_DEV_XS_CACHE) >= _DEV_XS_CACHE_CAP:
                    _DEV_XS_CACHE.popitem(last=False)
                _DEV_XS_CACHE[key] = hit
            else:
                _DEV_XS_CACHE.move_to_end(key)
            return hit

        launched = []
        for sl in slices:
            with span("jaxsim.prep"):
                B = _bucket(len(sl), cap=chunk)
                # pad lanes replicate the last real lane: finite,
                # well-defined state whose results are simply dropped
                # before assembly
                padded = sl + [sl[-1]] * (B - len(sl))
                g_np = np.fromiter((gi for gi, _ in padded), dtype=np.int32,
                                   count=B)
                clocks = np.full((P_max, S, B), np.inf)
                for li, (gi, pos) in enumerate(padded):
                    for p, cnt in enumerate(per[gi]["lane_counts"][pos]):
                        clocks[p, :cnt, li] = 0.0
                xs_j, kp_j, sk_j = _lane_aligned(g_np)
                with span("jaxsim.transfer"):
                    args = (xs_j, _put(_times(clocks)),
                            # ends, placed, busy, seen: made on device
                            jnp.zeros((T_max + 1, B), dtype=jnp.int64),
                            jnp.full((2 * T_max + 1, B), -1, dtype=jnp.int32),
                            jnp.zeros((P_max, B), dtype=jnp.int64),
                            jnp.zeros((P_max, B), dtype=bool),
                            kp_j, sk_j, _put(np.asarray(eft)))
                exe = _load_runner(cc, args)
            if stats is not None:
                stats.scan_lane_steps += B * T_max
            with span("jaxsim.launch"):
                makespan, busy, seen, placed, div = exe(*args)
                launched.append((sl, np.asarray(div),
                                 np.asarray(makespan).view(np.float64),
                                 np.asarray(busy).view(np.float64),
                                 np.asarray(seen), np.asarray(placed)))

    with span("replay.assemble"):
        for sl, div_np, mk_np, busy_np, seen_np, place_np in launched:
            for li, (gi, pos) in enumerate(sl):
                if div_np[li]:
                    diverged[gi].append(pos)
                    continue
                acc, c = accs[gi], per[gi]
                cuts = c["cuts"]
                if cuts is not None and mk_np[li] > cuts[pos]:
                    # post-scan retirement: the final makespan is its own
                    # (exact) bound, and it exceeds the incumbent cutoff
                    retired[gi][pos] = float(mk_np[li])
                    continue
                acc["kept"].append(pos)
                acc["mk"].append(mk_np[li:li + 1])
                acc["busy"].append(busy_np[:c["P"], li:li + 1])
                acc["seen"].append(seen_np[:c["P"], li:li + 1])
                # each row's placement at its last touch (see _group_xs)
                acc["place"].append(place_np[c["fin"], li:li + 1])
        results: List[Tuple[Dict[int, SimResult], List[int],
                            Dict[int, float]]] = []
        for gi, c in enumerate(per):
            acc = accs[gi]
            if stats is not None:
                stats.scan_kept_lane_steps += \
                    len(acc["kept"]) * len(c["xs"]["r"])
            done: Dict[int, SimResult] = {}
            if acc["kept"]:
                done = lane_results(
                    c["fg"], c["pool_names"], c["lane_counts"], acc["kept"],
                    policy, np.concatenate(acc["mk"]),
                    np.concatenate(acc["busy"], axis=1),
                    np.concatenate(acc["seen"], axis=1),
                    np.concatenate(acc["place"], axis=1).astype(np.int64))
            results.append((done, diverged[gi], retired[gi]))
    return results


def _scan_group(fg: FrozenGraph, order: Sequence[int],
                layouts: Sequence[Layout], policy: str,
                cutoffs: Optional[np.ndarray] = None, *,
                chunk: int = DEFAULT_CHUNK,
                compile_cache: Optional[CompileCache] = None,
                step_impl: str = "auto",
                stats: Optional[BatchStats] = None
                ) -> Tuple[Dict[int, SimResult], List[int],
                           Dict[int, float]]:
    """One-cohort form of :func:`_scan_cohorts` — the per-graph
    :data:`repro.core.replay.LockstepFn`."""
    (triple,) = _scan_cohorts([(fg, order, layouts, cutoffs)], policy,
                              chunk=chunk, compile_cache=compile_cache,
                              step_impl=step_impl, stats=stats)
    return triple


def _heap_cohorts(cohorts: Sequence[Tuple[FrozenGraph, Sequence[Layout],
                                          Optional[np.ndarray]]],
                  policy: str, *, chunk: int,
                  universe: Optional[Sequence[Tuple[FrozenGraph,
                                                    Sequence[Layout]]]]
                  = None,
                  compile_cache: Optional[CompileCache] = None,
                  stats: Optional[BatchStats] = None
                  ) -> List[Tuple[Dict[int, SimResult], List[int],
                                  Dict[int, float]]]:
    """Complete every lane of every ``(fg, layouts, cutoffs)`` cohort in
    the heap scan (:func:`_heap_runner`), which needs no order.

    The launch's shapes come from ``universe``, every ``(fg, layouts)``
    group of the sweep that sends lanes here (default: the cohorts
    themselves; prune seeds leave a cohort narrower than its group): rows
    pad to the largest graph (a multiple of :data:`HEAP_ROW_ALIGN`),
    pools, kinds and options to the largest, and the slot axis to the
    widest lane's need (pool slot counts clamped to the rows that could
    ever dispatch there, as :func:`_pool_caps`); lanes and cohorts bucket
    with floors (:data:`HEAP_MIN_LANES`, :data:`HEAP_MIN_COHORTS`).
    Lanes of all cohorts share slices of at most ``chunk`` lanes.

    Returns one ``(done, flagged, retired)`` triple per cohort, positions
    indexing its ``layouts``: ``flagged`` lanes met a row the reference
    raises on (or a deadlock) and take the exact path; retirement is
    post-scan classification against ``cutoffs``, as in
    :func:`_scan_cohorts`.  ``stats`` receives ``heap_lane_steps`` and
    ``heap_h2d_bytes``.
    """
    if not cohorts:
        return []
    if faults.fire("fail_lockstep"):
        raise RuntimeError("injected fault: fail_lockstep")
    jax, jnp = _jax()
    cc = compile_cache if compile_cache is not None else _MEM_COMPILE_CACHE
    if universe is None:
        universe = [(fg, layouts) for fg, layouts, _ in cohorts]

    with span("jaxsim.heap_prep"):
        R = -(-max(fg.n for fg, _ in universe) // HEAP_ROW_ALIGN) \
            * HEAP_ROW_ALIGN
        W = R // 32
        P = max(len(lay[0]) for _, lays in universe for lay in lays)
        NK = max(len(fg.kinds) for fg, _ in universe)
        K = max(max(1, int(np.diff(fg.dev_indptr).max(initial=0)))
                for fg, _ in universe)
        need = 1
        for fg, lays in universe:
            names, _, kind_pool = lays[0]               # template-shared
            caps = _pool_caps(fg, kind_pool, len(names)).tolist()
            for _, counts, _ in lays:
                need = max([need] + [min(c, cap)
                                     for c, cap in zip(counts, caps)])
        S = _bucket(need, cap=1 << 30)
        G = 1 << (max(len(cohorts), HEAP_MIN_COHORTS) - 1).bit_length()

        per = []
        kind_pool_m = np.full((G, NK), -1, dtype=np.int32)
        smp_kid_m = np.full((G,), -1, dtype=np.int32)
        for gi, (fg, layouts, cuts) in enumerate(cohorts):
            names, _, kind_pool = layouts[0]            # template-shared
            per.append({"fg": fg, "tabs": _heap_tables(fg, kind_pool),
                        "layouts": layouts, "cuts": cuts,
                        "names": names, "P": len(names)})
            kind_pool_m[gi, :len(kind_pool)] = kind_pool
            smp_kid_m[gi] = fg.kinds.index("smp") if "smp" in fg.kinds \
                else -1
        key = (tuple((c["fg"].content_hash(), tuple(kind_pool_m[gi]))
                     for gi, c in enumerate(per)), (R, G, K, NK))

    with jax.enable_x64(True):
        def _put(a: np.ndarray):
            """One host-to-device upload, counted in ``heap_h2d_bytes``."""
            if stats is not None:
                stats.heap_h2d_bytes += a.nbytes
            return jnp.asarray(a)

        with span("jaxsim.heap_prep"):
            tabs_j = _DEV_XS_CACHE.get(key)
            if tabs_j is None:
                stack = {
                    "npred": np.full((R, G), _PAD_PREDS, dtype=np.int32),
                    "c": np.full((R, G), -1, dtype=np.int32),
                    "is_comp": np.zeros((R, G), dtype=bool),
                    "k_first": np.zeros((R, G), dtype=np.int32),
                    "opts": np.full((R, G, K), -1, dtype=np.int32),
                    "cost": np.zeros((R, G, NK), dtype=np.float64),
                    "act": np.zeros((R, G, NK), dtype=bool),
                    "bad_row": np.zeros((R, G), dtype=bool),
                    "succ": np.zeros((R, G, W), dtype=np.uint32),
                }
                for gi, c in enumerate(per):
                    t = c["tabs"]
                    n = len(t["npred"])
                    for f, a in stack.items():
                        if a.ndim == 2:
                            a[:n, gi] = t[f]
                        else:
                            a[:n, gi, :t[f].shape[1]] = t[f]
                with span("jaxsim.heap_transfer"):
                    tabs_j = {f: _put(_times(a)) for f, a in stack.items()}
                if len(_DEV_XS_CACHE) >= _DEV_XS_CACHE_CAP:
                    _DEV_XS_CACHE.popitem(last=False)
                _DEV_XS_CACHE[key] = tabs_j
            else:
                _DEV_XS_CACHE.move_to_end(key)

        lanes = [(gi, pos) for gi, c in enumerate(per)
                 for pos in range(len(c["layouts"]))]
        width = _bucket(chunk, cap=chunk)
        launched = []
        for lo in range(0, len(lanes), width):
            sl = lanes[lo:lo + width]
            with span("jaxsim.heap_prep"):
                B = _bucket(max(len(sl), HEAP_MIN_LANES), cap=chunk)
                padded = sl + [sl[-1]] * (B - len(sl))
                g_np = np.fromiter((gi for gi, _ in padded), dtype=np.int32,
                                   count=B)
                clocks = np.full((P, S, B), np.inf)
                for li, (gi, pos) in enumerate(padded):
                    for p, cnt in enumerate(per[gi]["layouts"][pos][1]):
                        clocks[p, :cnt, li] = 0.0
                nrows = np.fromiter((per[gi]["fg"].n for gi, _ in padded),
                                    dtype=np.int32, count=B)
                with span("jaxsim.heap_transfer"):
                    args = (tabs_j, _put(_times(clocks)),
                            _put(kind_pool_m[g_np]), _put(smp_kid_m[g_np]),
                            _put(g_np), _put(nrows), _put(np.asarray(policy
                                                                     == "eft")))
                exe = _load_heap_runner(cc, args)
            if stats is not None:
                stats.heap_lane_steps += B * R
            with span("jaxsim.heap_launch"):
                makespan, busy, seen, placement, bad = exe(*args)
                launched.append((sl, np.asarray(bad),
                                 np.asarray(makespan).view(np.float64),
                                 np.asarray(busy).view(np.float64),
                                 np.asarray(seen), np.asarray(placement)))

    with span("replay.assemble"):
        accs = [{"kept": [], "mk": [], "busy": [], "seen": [], "place": []}
                for _ in per]
        flagged: List[List[int]] = [[] for _ in per]
        retired: List[Dict[int, float]] = [{} for _ in per]
        for sl, bad_np, mk_np, busy_np, seen_np, place_np in launched:
            for li, (gi, pos) in enumerate(sl):
                c, acc = per[gi], accs[gi]
                if bad_np[li]:
                    flagged[gi].append(pos)
                elif c["cuts"] is not None and mk_np[li] > c["cuts"][pos]:
                    retired[gi][pos] = float(mk_np[li])
                else:
                    acc["kept"].append(pos)
                    acc["mk"].append(mk_np[li:li + 1])
                    acc["busy"].append(busy_np[:c["P"], li:li + 1])
                    acc["seen"].append(seen_np[:c["P"], li:li + 1])
                    # each row's placement at its sorted position
                    acc["place"].append(place_np[c["tabs"]["pos"], li:li + 1])
        results = []
        for gi, c in enumerate(per):
            acc = accs[gi]
            done: Dict[int, SimResult] = {}
            if acc["kept"]:
                done = lane_results(
                    c["fg"], c["names"], [lay[1] for lay in c["layouts"]],
                    acc["kept"], policy, np.concatenate(acc["mk"]),
                    np.concatenate(acc["busy"], axis=1),
                    np.concatenate(acc["seen"], axis=1),
                    np.concatenate(acc["place"], axis=1).astype(np.int64))
            results.append((done, flagged[gi], retired[gi]))
    return results


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def simulate_jax(fg: FrozenGraph, systems: Sequence[SystemConfig],
                 policy: str = "availability", *,
                 min_lockstep: int = MIN_LOCKSTEP,
                 chunk: int = DEFAULT_CHUNK,
                 stats: Optional[BatchStats] = None,
                 library: Optional[ReplayLibrary] = None,
                 max_rounds: int = MAX_RESCUE_ROUNDS,
                 rescue_min: int = RESCUE_MIN,
                 compile_cache: Optional[CompileCache] = None,
                 step_impl: str = "auto",
                 prune: Optional[PruneContext] = None):
    """Schedule-free :class:`SimResult` per system, in input order.

    The jax tier of :func:`repro.core.batchsim.simulate_batch`: equivalent
    to ``[simulate_fast(fg, s, policy) for s in systems]`` at
    :data:`~repro.core.replay.JAX_RTOL` relative makespan/busy error with
    identical placements, and ranking-stable under the documented
    tie-break.  Grouping, multi-order library replay (``library`` —
    orders are engine-agnostic: they are recorded by the exact serial
    path and each lane re-validates in-scan, so a batch-warmed library
    serves this engine unchanged) and the per-lane exact fallback are the
    shared :mod:`repro.core.replay` protocol; ``chunk`` caps the compiled
    lane-bucket width (non-power-of-two caps round down to a power of
    two).  ``compile_cache`` persists compiled executables (default: a
    process-local in-memory cache); ``step_impl`` picks the step-commit
    implementation (see :data:`STEP_IMPLS`).

    ``prune`` enables in-flight lane retirement
    (:class:`~repro.core.replay.PruneContext`): lanes whose makespan
    exceeds the inflated incumbent cutoff come back as
    :class:`~repro.core.replay.Retired` markers instead of results.
    Cutoffs on this engine are pre-inflated by the
    :data:`~repro.core.replay.JAX_RTOL` tolerance so sub-rtol ties never
    retire.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk!r}")
    resolve_step_impl(step_impl)               # fail fast on bad names

    def lockstep(fg, order, layouts, policy, cutoffs=None):
        return _scan_group(fg, order, layouts, policy, cutoffs, chunk=chunk,
                           compile_cache=compile_cache, step_impl=step_impl,
                           stats=stats)

    return simulate_grouped(fg, systems, policy, min_lockstep=min_lockstep,
                            stats=stats, library=library,
                            max_rounds=max_rounds, rescue_min=rescue_min,
                            lockstep_fn=lockstep, prune=prune)


def simulate_jax_many(items: Sequence[Tuple[FrozenGraph,
                                            Sequence[SystemConfig]]],
                      policy: str = "availability", *,
                      min_lockstep: int = MIN_LOCKSTEP,
                      chunk: Optional[int] = None,
                      stats: Optional[BatchStats] = None,
                      compile_cache: Optional[CompileCache] = None,
                      prunes: Optional[Sequence[Optional[PruneContext]]]
                      = None) -> List[List[SimResult]]:
    """Multi-graph megabatch: every ``(graph, systems)`` family of a sweep
    through **one** heap-scan launch.

    Every lane of a pool-template group with at least ``min_lockstep``
    lanes completes in the heap scan (:func:`_heap_cohorts`), bit-identical
    to ``simulate_fast``; smaller groups and flagged lanes take the exact
    serial path (:func:`repro.core.replay.simulate_many`).  No order is
    discovered or replayed, so no ``ReplayLibrary`` is involved.
    ``chunk`` caps the launch's lane bucket and defaults to the wider
    :data:`MEGABATCH_CHUNK`.
    """
    chunk = MEGABATCH_CHUNK if chunk is None else chunk
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk!r}")

    def heap_many(cohorts, universe):
        return _heap_cohorts(cohorts, policy, chunk=chunk, universe=universe,
                             compile_cache=compile_cache, stats=stats)

    return simulate_many(items, policy, heap_many_fn=heap_many,
                         min_lockstep=min_lockstep, stats=stats,
                         prunes=prunes)
