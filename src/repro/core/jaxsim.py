"""jax ``lax.scan`` candidate-axis engine — the sweep loop, compiled.

:mod:`repro.core.batchsim` proved the lockstep formulation: every candidate
sharing one :class:`~repro.core.fastsim.FrozenGraph` advances step by step
with per-candidate state stacked on a candidate ("lane") axis.  Its
per-step cost is numpy-*dispatch*-bound.  This module compiles one lockstep
step into a single jit-compiled :func:`jax.lax.scan`, the **heap scan**
(:func:`_heap_runner`), in which each lane pops its *own* heap, so a whole
sweep runs as one XLA computation with the per-candidate state carried as
scan state on a device-resident lane axis.

Invariants:

* **Lane-last axis convention.**  Per-candidate state is stacked with the
  lane axis *last* — pool free-slot clocks ``[P, S, B]``, busy ``[P, B]``,
  and the row-indexed ready times, predecessor counts and placements
  ``[R, B]`` — so the shared assembly helper
  (:func:`repro.core.replay.lane_results`) turns a launch's outputs into
  results as it does the batchsim layout's.
* **Bit-identical times.**  The scan runs under ``jax.enable_x64`` and does
  exact IEEE float64 time arithmetic on int64 bit patterns
  (:func:`_bits_add`), so on every backend — the TPU, whose own float64 is
  inexact, included — its makespans match the reference bit for bit.  Its
  pop is the reference heap's, ties included, so no lane can diverge.  The
  engine is still declared at the relaxed tier
  (:data:`repro.core.replay.JAX_RTOL`); its results meet the exact one.
* **Bad rows fall back to the exact path.**  A lane that *live*-dispatches
  a row the reference engine would raise on comes back flagged and is
  re-simulated through :func:`~repro.core.fastsim.simulate_fast`, which
  raises the reference error or completes.
* **Fixed-bucket lane chunking.**  Lanes are evaluated in chunks padded to
  power-of-two widths (``chunk`` caps the bucket — non-power-of-two caps
  round *down* to a power of two, so the compiled width never exceeds the
  cap and the jit cache stays keyed on a handful of shapes); padding lanes
  replicate a real lane and are dropped before assembly.

Two mechanisms set the engine's cold-start economics:

* **Multi-graph megabatch** (:func:`simulate_jax_many`).  One heap-scan
  launch serves *every* graph family of a sweep at once: each family's
  row tables are padded to a shared ``[R, G, ...]`` stack, each lane
  carries its cohort index ``g``, and the step body reads its rows per
  lane.  No order is discovered, replayed or validated; the routing
  around it is :func:`repro.core.replay.simulate_many`.
* **Persistent compile cache** (:class:`repro.core.xlacache.CompileCache`).
  The heap scan is compiled ahead-of-time per shape signature and the
  serialized executable persists in the sweep's DiskCache (``xla``
  namespace), so a warm store turns the multi-second cold compile into a
  millisecond deserialize — across processes and runs.
"""
from __future__ import annotations

import collections
import functools
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .devices import SystemConfig
from .fastsim import FrozenGraph
from .replay import (BatchStats, Layout, MIN_LOCKSTEP, PruneContext,
                     graph_aux, lane_results, simulate_many)
from .simulator import SimResult
from .spans import span
from .xlacache import CompileCache
from ..testing import faults

# The jax import is deferred until the engine is actually used: importing
# repro.core (which re-exports the engines) must stay cheap and must not
# load a multithreaded runtime before the exploration engine's process
# pools pick a start method.  _jax() performs the import.

#: Lane-bucket cap for the multi-graph megabatch: one heap launch carries
#: every cohort of the sweep, so the fixed per-launch overhead amortises
#: over many lanes.
MEGABATCH_CHUNK = 256

#: Heap-scan launch shapes (:func:`_heap_cohorts`): rows pad to a multiple
#: of this, and lanes and cohorts bucket to powers of two no smaller than
#: these floors, so a sweep whose count of heap-bound lanes or cohorts
#: drifts with its costs meets the widths its warm-up compiled.
HEAP_ROW_ALIGN = 128
HEAP_MIN_LANES = 32
HEAP_MIN_COHORTS = 4

#: Fallback in-memory compile cache for bare ``simulate_jax_many`` calls
#: with no Explorer-owned cache: still deduplicates compiles within the
#: process.
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


# ---------------------------------------------------------------------------
# The compiled scan: each lane pops its own heap
# ---------------------------------------------------------------------------


def _choose(opts, cost, rt, minc, kind_pool, smp_kid, eft, aB):
    """Vectorised reference `_choose_kind` over all lanes: options visited
    in annotation order, strict < on (key, pref) — the lowest-index winner,
    identical tie-breaks to the exact engines.  ``opts [B, K]`` / ``cost
    [B, NK]`` are each lane's own row's tables; ``minc [P, B]`` is the
    step's hoisted earliest-free-slot reduction, so each option costs a
    [B] gather instead of its own [B, S] min."""
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
    """Slot argmin + clock/busy/seen update — the step's dense tail."""
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
    beside the pool clocks ``[P, S, B]``, busy and seen ``[P, B]``.  Each
    step pops, runs the conditional pass-through (a conditional row whose
    parent is not placed yet places it), :func:`_choose` and
    :func:`_commit` on the popped row, then releases the row's
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
    """Shape/dtype signature of one heap-scan invocation — the
    compile-cache key body (the environment half lives in CompileCache)."""
    def one(a):
        return (tuple(a.shape), str(a.dtype))
    tabs = args[0]
    return (_code_fingerprint(),
            tuple((k, one(v)) for k, v in sorted(tabs.items())),
            tuple(one(a) for a in args[1:]))


def _load_heap_runner(cc: CompileCache, args: Tuple):
    """The AOT-compiled heap-scan executable for this signature: in-memory
    hit, disk deserialize, or fresh ``lower().compile()`` (then persisted)."""
    jax, _ = _jax()
    return cc.load_or_compile(
        ("heap",) + _signature(args),
        lambda: jax.jit(_heap_runner()).lower(*args))


# ---------------------------------------------------------------------------
# Per-cohort graph tables
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
    ramp).
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


# Per-FrozenGraph cap on memoised pool template -> heap tables entries.
_XS_CACHE_CAP = 32

# Stacked device tables, memoised across _heap_cohorts calls: keyed by
# content (per-cohort graph hash × pool template) and launch dims.
# Entries are a few MB of device arrays each; the cap bounds residency,
# LRU evicts.
_DEV_XS_CACHE: "collections.OrderedDict[Tuple, Tuple]" = \
    collections.OrderedDict()
_DEV_XS_CACHE_CAP = 16


def _heap_tables(fg: FrozenGraph, kind_pool: Sequence[int]
                 ) -> Dict[str, np.ndarray]:
    """The heap scan's per-cohort graph tables (see :func:`_heap_runner`),
    rows sorted by the heap's tie-break ``(creation_index, uid)``
    (:func:`~repro.core.replay.graph_aux`'s ``tb``): ``npred``, ``c``
    (the conditional parent's sorted position, or -1), ``is_comp``,
    ``k_first``, ``opts [n, K]``, ``cost [n, NK]``, ``act [n, NK]``,
    ``bad_row`` (:func:`_bad_rows`), ``succ [n,
    W]`` (each row's successors as a bitset over sorted positions, ``W =
    ceil(n / 32)`` words), and ``pos [n]``, each row's sorted position,
    which the host reads the final placements at.  Memoised on the
    FrozenGraph (repeat sweeps — re-ranks, hillclimbs — launch the same
    payload many times); dropped on pickling like ``_rt``."""
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
        # bad-row flags capture every NaN a live dispatch could reach;
        # scrub the rest so no masked-out lane arithmetic makes a NaN
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
# Cohort launches: row padding, chunked lanes, one shared compiled scan
# ---------------------------------------------------------------------------


def _times(a: np.ndarray) -> np.ndarray:
    """Host float64 times as the scan's int64 bit patterns (see
    :func:`_bits_add`); other arrays unchanged."""
    return a.view(np.int64) if a.dtype == np.float64 else a


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
    raises on (or a deadlock) and take the exact path.  Retirement is
    **post-scan classification**: the ``lax.scan`` trip count is fixed at
    trace time, so a lane whose *final* makespan exceeds its cutoff is
    reported as retired, with the makespan as its (exact) bound.
    ``stats`` receives ``heap_lane_steps`` and ``heap_h2d_bytes``.
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
    ``chunk`` caps the launch's lane bucket and defaults to
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
