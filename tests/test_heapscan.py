"""The heap scan: the jax engine's lanes, completed on the device with
each lane popping its own heap.

``jaxsim._heap_cohorts`` runs every lane's own list schedule in one
lockstep scan: each step pops the lane's minimum of ``(ready time,
creation index, uid)`` over its ready rows, exactly the reference's heap,
so it needs no order and no validation.  This file pins it bit for bit to
``simulate_fast`` on seeded random graphs, checks that
``replay.simulate_many`` routes every lane of a jax megabatch sweep there
(every group with at least ``min_lockstep`` lanes; smaller groups take the
exact path) with no discovery, no replayed order and no library, and that a
sweep through ``Explorer`` ranks the paper's applications identically on
every engine.
"""
import collections
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import cholesky as chol
from repro.apps import matmul as mm
from repro.core import Explorer, a9_smp_seconds, jaxsim, zynq_system
from repro.core.augment import Eligibility
from repro.core.codesign import Candidate
from repro.core.devices import DevicePool, SharedResource, SystemConfig
from repro.core.fastsim import FrozenGraph, pool_layout, simulate_fast
from repro.core.hlsreport import HLSSynthesisModel
from repro.core.jaxsim import (HEAP_MIN_LANES, HEAP_ROW_ALIGN, _heap_cohorts,
                               _heap_runner, simulate_jax_many)
from repro.core.batchsim import simulate_batch
from repro.core.replay import (JAX_RTOL, BatchStats, Incumbent, PruneContext,
                               ReplayLibrary, Retired, sims_equivalent)
from repro.core.simulator import Simulator
from repro.core.taskgraph import Task, TaskGraph
from repro.testing import faults
from repro.testing.synth import frozen_for, synth_trace


def _random_dag(seed, n, zero_share=0.4):
    """A bare DAG over two device kinds with many zero-cost tasks and
    shared creation indices, so ready times tie and the heap's
    ``(creation_index, uid)`` tie-break decides."""
    rng = random.Random(seed)
    g = TaskGraph()
    uids = []
    for i in range(n):
        def cost():
            return 0.0 if rng.random() < zero_share else rng.choice(
                (0.25, 0.5, 1.0, 1.5))
        kinds = ("a", "b") if rng.random() < 0.5 else ("b", "a")
        t = Task(uid=g.new_uid(), name=f"t{i}", devices=kinds,
                 costs={"a": cost(), "b": cost()},
                 creation_index=i // 3, meta={"role": "compute"})
        g.add_task(t, infer_deps=False)
        for j in rng.sample(range(i), min(i, rng.randint(0, 3))):
            g.add_edge(uids[j], t.uid)
        uids.append(t.uid)
    return FrozenGraph.freeze(g)


def _two_pools(na, nb):
    return [SystemConfig(name=f"a{i}b{j}",
                         pools=[DevicePool("pa", ("a",), i),
                                DevicePool("pb", ("b",), j)],
                         shared=[SharedResource("x", 1)])
            for i in range(1, na + 1) for j in range(1, nb + 1)]


def _ramp(counts, tag=""):
    return [zynq_system(f"{tag}{n}acc", {"fpga:k": n}) for n in counts]


HEAP_CASES = {
    # ready-time ties everywhere: the tie-break picks the pop
    "zero_cost_ties": ("availability",
                       lambda: [(_random_dag(1, 40), _two_pools(3, 3))]),
    "zero_cost_ties_eft": ("eft",
                           lambda: [(_random_dag(2, 33), _two_pools(2, 4))]),
    # conditional DMA rows under +-SMP: skipped rows pass their ready time
    "conditional_smp": ("availability",
                        lambda: [(frozen_for(synth_trace(24), smp=True)[0],
                                  _ramp(range(1, 13)))]),
    "conditional_smp_eft": ("eft",
                            lambda: [(frozen_for(synth_trace(18), smp=True)[0],
                                      _ramp(range(1, 9)))]),
    # one launch over mixed graphs, row counts and pool templates
    "megabatch_mixed": ("availability", lambda: [
        (frozen_for(synth_trace(30), smp=True)[0], _ramp(range(1, 9), "s")),
        (frozen_for(synth_trace(11), smp=False)[0], _ramp(range(1, 6), "f")),
        (_random_dag(3, 150), _two_pools(2, 3)),
        (_random_dag(4, 7, zero_share=0.8), _two_pools(3, 2))]),
}


def _dag(n, edges):
    """A bare DAG over two device kinds with the given row edges."""
    g = TaskGraph()
    uids = []
    for i in range(n):
        kinds = ("a", "b") if i % 3 else ("b", "a")
        t = Task(uid=g.new_uid(), name=f"t{i}", devices=kinds,
                 costs={"a": 0.5 + (i % 5) * 0.25, "b": 1.0 + (i % 3) * 0.5},
                 creation_index=i, meta={"role": "compute"})
        g.add_task(t, infer_deps=False)
        uids.append(t.uid)
    for a, b in edges:
        g.add_edge(uids[a], uids[b])
    return FrozenGraph.freeze(g)


def _skip_graph(units=3):
    """A chain of units ``r -> d -> x -> z``: ``d`` is a transfer
    conditional on the compute ``x``, active only when ``x`` runs on
    ``a``.  Under ``eft`` the SMP wins ``x`` (0.5 against 2.0), so ``d``
    decides ``x``'s device at its own pop, is skipped, and passes its
    ready time on to ``x``."""
    g = TaskGraph()
    prev = None

    def add(name, devices, costs, ci, **meta):
        t = Task(uid=g.new_uid(), name=name, devices=devices, costs=costs,
                 creation_index=ci, meta=meta)
        g.add_task(t, infer_deps=False)
        return t.uid

    for u in range(units):
        r = add(f"r{u}", ("b",), {"b": 1.0}, 4 * u, role="compute")
        x = add(f"x{u}", ("a", "smp"), {"a": 2.0, "smp": 0.5}, 4 * u + 2,
                role="compute")
        d = add(f"d{u}", ("d",), {"d": 0.1}, 4 * u + 1, role="submit_in",
                conditional_on=x, active_kinds=("a",))
        z = add(f"z{u}", ("b",), {"b": 1.0}, 4 * u + 3, role="compute")
        if prev is not None:
            g.add_edge(prev, r)
        for a, b in ((r, d), (d, x), (x, z)):
            g.add_edge(a, b)
        prev = z
    fg = FrozenGraph.freeze(g)
    systems = [SystemConfig(name=f"s{i}{j}{k}",
                            pools=[DevicePool("smp", ("smp",), k),
                                   DevicePool("pa", ("a",), i),
                                   DevicePool("pb", ("b",), j),
                                   DevicePool("pd", ("d",), 1)],
                            shared=[SharedResource("x", 1)])
               for i in (1, 2) for j in (1, 2, 3) for k in (1, 2)]
    return [(fg, systems)]


def _commit_graph(units=4, width=3):
    """The commit's edge cases: each unit's root and its ``width``
    computes cost the same on every slot, so a pool's slots tie on their
    clocks and the first minimum picks the slot; each compute has a
    transfer conditional on it running on ``a``, which lanes with few
    ``a`` slots skip (the transfer is not live there, and must leave its
    pool's clocks and busy time alone) while lanes with many run it; and
    every lane has fewer slots than the launch's slot axis, so its unused
    slots stay ``+inf``."""
    g = TaskGraph()

    def add(name, devices, costs, ci, **meta):
        t = Task(uid=g.new_uid(), name=name, devices=devices, costs=costs,
                 creation_index=ci, meta=meta)
        g.add_task(t, infer_deps=False)
        return t.uid

    ci = 0
    for u in range(units):
        r = add(f"r{u}", ("a",), {"a": 1.0}, ci, role="compute")
        for w in range(width):
            x = add(f"x{u}.{w}", ("a", "smp"), {"a": 1.0, "smp": 1.0},
                    ci + 2, role="compute")
            d = add(f"d{u}.{w}", ("d",), {"d": 0.25}, ci + 1,
                    role="submit_in", conditional_on=x, active_kinds=("a",))
            g.add_edge(r, d)
            g.add_edge(d, x)
            ci += 2
        ci += 1
    fg = FrozenGraph.freeze(g)
    systems = [SystemConfig(name=f"s{i}{k}",
                            pools=[DevicePool("smp", ("smp",), k),
                                   DevicePool("pa", ("a",), i),
                                   DevicePool("pd", ("d",), 1)],
                            shared=[SharedResource("x", 1)])
               for i in (1, 2, 3, 5) for k in (1, 2)]
    return [(fg, systems)]


#: Nine independent rows joined by one, which three rows follow.
_FAN_IN = [(i, 9) for i in range(9)] + [(9, j) for j in (10, 11, 12)]

#: Graph shapes, each under both policies: the first policy named is the
#: one the shape was first pinned under.
_SHAPES = {
    # every row a root: all ready at once, the tie-break orders every pop
    "roots": ("availability", lambda: [(_dag(12, []), _two_pools(3, 3))]),
    # the join row waits on nine predecessors
    "widest_preds": ("availability",
                     lambda: [(_dag(13, _FAN_IN), _two_pools(3, 3))]),
    # conditional rows skipped: they pass their ready time through
    "skipped_conditional": ("eft", _skip_graph),
    # a DMA-in child decides its compute parent's device before the
    # parent's own pop
    "parent_placed_early": ("availability", lambda: [
        (frozen_for(synth_trace(16), smp=True)[0], _ramp(range(2, 9)))]),
    # one launch holds lanes of cohorts with different row counts
    "mixed_lengths": ("availability", lambda: [
        (frozen_for(synth_trace(10), smp=True)[0], _ramp(range(1, 7))),
        (frozen_for(synth_trace(17), smp=False)[0], _ramp(range(1, 7), "b"))]),
    # the launch is wider than the lanes it carries
    "padding_lanes": ("availability", lambda: [
        (frozen_for(synth_trace(12), smp=False)[0], _ramp(range(1, 6)))]),
    # tied slot clocks, transfers not live in some lanes, +inf slots
    "commit_edges": ("availability", _commit_graph),
}
for _name, (_policy, _build) in _SHAPES.items():
    _other = "eft" if _policy == "availability" else "availability"
    HEAP_CASES[_name] = (_policy, _build)
    HEAP_CASES[f"{_name}_{_other}"] = (_other, _build)


def _heap_run(items, policy, cut_frac=None, chunk=256):
    """Every lane of ``items`` through ``_heap_cohorts``; with
    ``cut_frac``, each lane's cutoff sits at that fraction between the
    smallest and largest exact makespan of its family."""
    cohorts = []
    for fg, systems in items:
        cuts = None
        if cut_frac is not None:
            ms = [simulate_fast(fg, s, policy).makespan for s in systems]
            cut = min(ms) + cut_frac * (max(ms) - min(ms))
            cuts = np.full(len(systems), cut)
        # a cohort holds one pool template, like a replay group
        by_tpl = {}
        for s in systems:
            names, _, kp = pool_layout(fg.kinds, s)
            by_tpl.setdefault((tuple(names), tuple(kp)), []).append(s)
        for group in by_tpl.values():
            idx = [systems.index(s) for s in group]
            cohorts.append((fg, group, [pool_layout(fg.kinds, s)
                                        for s in group],
                            None if cuts is None else cuts[idx]))
    stats = BatchStats()
    outs = _heap_cohorts([(fg, lays, cuts) for fg, _, lays, cuts in cohorts],
                         policy, chunk=chunk, stats=stats)
    return cohorts, outs, stats


@pytest.mark.parametrize("case", HEAP_CASES)
def test_heap_scan_is_bit_exact(case):
    """Every lane's makespan, busy times and placements equal
    ``simulate_fast``'s bit for bit; no lane is flagged."""
    policy, build = HEAP_CASES[case]
    cohorts, outs, stats = _heap_run(build(), policy)
    assert stats.heap_lane_steps > 0 and stats.heap_h2d_bytes > 0
    for (fg, systems, _lays, _cuts), (done, flagged, retired) in zip(cohorts,
                                                                     outs):
        assert not flagged and not retired
        assert sorted(done) == list(range(len(systems)))
        for pos, system in enumerate(systems):
            ref = simulate_fast(fg, system, policy)
            got = done[pos]
            assert got.makespan == ref.makespan, (case, system.name)
            assert got.busy == ref.busy, (case, system.name)
            assert got.placements == ref.placements, (case, system.name)
            assert got.pool_slots == ref.pool_slots


@pytest.mark.parametrize("chunk", [32, 256])
def test_heap_scan_cutoffs_retire_exactly_the_lanes_above_them(chunk):
    """Per-lane cutoffs: a lane whose exact makespan exceeds its cutoff
    retires with that makespan as its bound, the others complete; a
    narrow ``chunk`` splits the lanes over several launches."""
    items = [(frozen_for(synth_trace(20), smp=True)[0], _ramp(range(1, 25))),
             (_random_dag(5, 60), _two_pools(4, 4))]
    cohorts, outs, stats = _heap_run(items, "availability", cut_frac=0.5,
                                     chunk=chunk)
    n_lanes = sum(len(s) for _, s, _, _ in cohorts)
    launches = -(-n_lanes // chunk) if chunk < n_lanes else 1
    assert stats.heap_lane_steps >= launches * HEAP_MIN_LANES * HEAP_ROW_ALIGN
    n_retired = 0
    for (fg, systems, _lays, cuts), (done, flagged, retired) in zip(cohorts,
                                                                     outs):
        assert not flagged
        assert set(done) | set(retired) == set(range(len(systems)))
        for pos, system in enumerate(systems):
            ref = simulate_fast(fg, system, "availability")
            if ref.makespan > cuts[pos]:
                assert retired[pos] == ref.makespan
                n_retired += 1
            else:
                assert done[pos].makespan == ref.makespan
                assert done[pos].placements == ref.placements
    assert 0 < n_retired < n_lanes


@pytest.mark.parametrize("case", ["conditional_smp", "megabatch_mixed"])
def test_pool_caps_count_every_row_that_could_dispatch(case):
    """Both scans clamp the slot axis to ``_pool_caps``: every option of a
    compute row and the device of any other row, counted per pool — the
    plain loop over the rows is the reference."""
    from repro.core.jaxsim import _pool_caps
    _, build = HEAP_CASES[case]
    for fg, systems in build():
        (_u, _ci, _c, dev_first, dev_opts, *_r) = fg._runtime()
        for system in systems:
            names, _, kind_pool = pool_layout(fg.kinds, system)
            want = [0] * len(names)
            for r in range(fg.n):
                for k in (dev_opts[r] if fg.is_compute[r]
                          else (dev_first[r],)):
                    if kind_pool[k] >= 0:
                        want[kind_pool[k]] += 1
            assert _pool_caps(fg, kind_pool, len(names)).tolist() == want


def test_heap_scan_flags_a_lane_that_meets_a_bad_row():
    """A row with no cost on an eligible kind: the reference raises on it,
    so the heap scan flags the lane for the exact path."""
    g = TaskGraph()
    a = Task(uid=g.new_uid(), name="ok", devices=("a",), costs={"a": 1.0},
             creation_index=0, meta={"role": "compute"})
    b = Task(uid=g.new_uid(), name="nocost", devices=("b",), costs={},
             creation_index=1, meta={"role": "compute"})
    g.add_task(a, infer_deps=False)
    g.add_task(b, infer_deps=False)
    g.add_edge(a.uid, b.uid)
    fg = FrozenGraph.freeze(g)
    systems = _two_pools(2, 1)
    with pytest.raises(KeyError):
        simulate_fast(fg, systems[0], "availability")
    (done, flagged, retired), = _heap_cohorts(
        [(fg, [pool_layout(fg.kinds, s) for s in systems], None)],
        "availability", chunk=256)
    assert sorted(flagged) == [0, 1] and not done and not retired


def test_heap_scan_module_name_and_dense_state_writes():
    """The trace reads the heap scan by its module, ``jit_run_heap``; the
    module, its loop included, holds no scatter at all (the row-indexed
    state and the pool clocks, busy and seen are written with dense
    ``where``s), and its step input is stacked over the padded rows, the
    loop's trip count."""
    R, G, K, NK, P, S, B = 64, 4, 2, 3, 3, 8, 32
    with jax.enable_x64(True):
        def sd(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)
        tabs = {"npred": sd((R, G), jnp.int32), "c": sd((R, G), jnp.int32),
                "is_comp": sd((R, G), jnp.bool_),
                "k_first": sd((R, G), jnp.int32),
                "opts": sd((R, G, K), jnp.int32),
                "cost": sd((R, G, NK), jnp.int64),
                "act": sd((R, G, NK), jnp.bool_),
                "bad_row": sd((R, G), jnp.bool_),
                "succ": sd((R, G, R // 32), jnp.uint32)}
        lowered = jax.jit(_heap_runner()).lower(
            tabs, sd((P, S, B), jnp.int64), sd((B, NK), jnp.int32),
            sd((B,), jnp.int32), sd((B,), jnp.int32), sd((B,), jnp.int32),
            sd((), jnp.bool_))
    assert "module @jit_run_heap" in lowered.as_text()
    hlo = lowered.as_text(dialect="hlo")
    assert "scatter(" not in hlo
    (whl,) = [ln for ln in hlo.splitlines() if " while(" in ln]
    assert f"pred[{R},{B}]" in whl


# ---------------------------------------------------------------------------
# routing: replay.simulate_many sends every lane of a large group to the heap
# ---------------------------------------------------------------------------


def _ramp_items():
    fg1, _ = frozen_for(synth_trace(40), smp=True)
    fg2, _ = frozen_for(synth_trace(24), smp=False)
    return [(fg1, _ramp(range(1, 25), "a")), (fg2, _ramp(range(1, 13), "b"))]


def _group_sizes(items):
    """The lanes of each pool-template group, per family."""
    sizes = []
    for fg, systems in items:
        groups = {}
        for s in systems:
            names, _, kp = pool_layout(fg.kinds, s)
            groups[(tuple(names), tuple(kp))] = \
                groups.get((tuple(names), tuple(kp)), 0) + 1
        sizes.extend(groups.values())
    return sizes


def _assert_equals_batch(items, res, policy):
    """Every lane's result equals the batch engine's bit for bit."""
    for (fg, systems), sims in zip(items, res):
        ref = simulate_batch(fg, systems, policy, min_lockstep=2)
        for system, sim, want in zip(systems, sims, ref):
            assert (sim.makespan, sim.busy, sim.placements) == \
                (want.makespan, want.busy, want.placements), system.name


@pytest.mark.parametrize("min_lockstep", [2, 4, 13, 64])
def test_routing_sends_groups_with_enough_unserved_lanes_to_the_heap(
        min_lockstep):
    """Every lane of a group with at least ``min_lockstep`` lanes
    completes in the heap scan (counted as a lockstep lane), with no
    discovery and no divergence; a smaller group takes
    the exact serial path.  Every lane is classified once and every result
    equals the batch engine's."""
    items = _ramp_items()
    stats = BatchStats()
    res = simulate_jax_many(items, "availability", min_lockstep=min_lockstep,
                            stats=stats)
    n_lanes = sum(len(s) for _, s in items)
    big = sum(n for n in _group_sizes(items) if n >= min_lockstep)
    assert stats.heap_lanes == stats.lockstep_lanes == big
    assert stats.small_group_lanes == n_lanes - big
    assert stats.reference_lanes == stats.diverged_lanes == 0
    assert stats.order_hits == stats.order_pinned_lanes == 0
    assert stats.serial_fallback_lanes == 0
    assert stats.scan_lane_steps == stats.h2d_bytes == 0
    assert (stats.heap_lane_steps > 0) == (big > 0)
    _assert_equals_batch(items, res, "availability")


def test_heap_lanes_write_nothing_to_the_library():
    """A jax megabatch sweep neither reads nor writes the order library:
    cold, it leaves the library empty; warmed by the batch engine, it
    leaves it as it was and routes no lane by it.  Either way every lane
    completes in the heap scan and ranks as the batch engine does."""
    trace, reports, cands, smp_fn = _matmul(256, 64, range(1, 9))
    kw = dict(smp_seconds_fn=smp_fn, budget={})
    lib = ReplayLibrary()
    cold = Explorer(trace, reports, engine="jax", order_library=lib, **kw)
    got_cold = cold.explore(cands)
    assert len(lib) == 0 and lib.counts()["orders"] == 0
    batch = Explorer(trace, reports, engine="batch", order_library=lib, **kw)
    ref = batch.explore(cands)
    warmed = lib.counts()
    assert warmed["orders"] > 0
    warm = Explorer(trace, reports, engine="jax", order_library=lib, **kw)
    got_warm = warm.explore(cands)
    assert lib.counts() == warmed
    for ex in (cold, warm):
        st = ex.batch_stats
        assert st.heap_lanes == st.lockstep_lanes == len(cands)
        assert st.reference_lanes == st.diverged_lanes == 0
        assert st.order_hits == st.order_pinned_lanes == 0
    for got in (got_cold, got_warm):
        assert [(o.name, o.makespan_s) for o in got.ranked] == \
            [(o.name, o.makespan_s) for o in ref.ranked]


def test_a_jax_sweep_pins_nothing_for_the_batch_engine():
    """The library is shared across engines: after a jax megabatch sweep
    the library holds no order and no pin, so a batch sweep on it runs as
    a batch sweep on a fresh library does, count for count, and ranks the
    same."""
    trace, reports, cands, smp_fn = _matmul(256, 64, range(1, 9))
    lib = ReplayLibrary()
    jx = Explorer(trace, reports, engine="jax", smp_seconds_fn=smp_fn,
                  budget={}, order_library=lib)
    jx.explore(cands)
    assert jx.batch_stats.heap_lanes > 0
    with lib._lock:
        assert not any(e.pins for e in lib._entries.values())
    shared = Explorer(trace, reports, engine="batch", smp_seconds_fn=smp_fn,
                      budget={}, order_library=lib)
    res = shared.explore(cands)
    fresh = Explorer(trace, reports, engine="batch", smp_seconds_fn=smp_fn,
                     budget={})
    ref = fresh.explore(cands)
    assert shared.batch_stats.order_pinned_lanes == \
        fresh.batch_stats.order_pinned_lanes
    assert shared.batch_stats.reference_lanes == \
        fresh.batch_stats.reference_lanes
    assert [(o.name, o.makespan_s) for o in res.ranked] == \
        [(o.name, o.makespan_s) for o in ref.ranked]


def test_over_budget_fallbacks_take_the_heap(monkeypatch):
    """The discovery budget bounds only the library protocol: a jax
    megabatch sweep with no budget (``max_rescue_rounds=0``) routes as one
    with the default budget, every lane to the heap scan, nothing serial,
    and ranks as the batch engine does under ``eft``.  Each sweep starts
    with no device tables memoised, so both count the same uploads
    whatever ran before them in the process."""
    trace, reports, cands, smp_fn = _matmul(256, 64, range(1, 9))
    kw = dict(smp_seconds_fn=smp_fn, budget={}, policy="eft")
    ref = Explorer(trace, reports, engine="batch", **kw).explore(cands)
    counts = []
    for rounds in (0, 32):
        monkeypatch.setattr(jaxsim, "_DEV_XS_CACHE",
                            collections.OrderedDict())
        ex = Explorer(trace, reports, engine="jax", max_rescue_rounds=rounds,
                      **kw)
        res = ex.explore(cands)
        st = ex.batch_stats
        assert st.heap_lanes == len(cands)
        assert st.reference_lanes == st.serial_fallback_lanes == 0
        assert st.scan_lane_steps == 0
        counts.append(st.as_dict())
        assert [(o.name, o.makespan_s) for o in res.ranked] == \
            [(o.name, o.makespan_s) for o in ref.ranked]
    assert counts[0] == counts[1]


def test_prune_megabatch_seeds_serially_then_retires_heap_lanes_exactly():
    """Prune mode: while the incumbent needs completions, the group's
    most-parallel lanes run serially first (nothing recorded); every other
    lane goes to the heap launch with the live cutoff, and retires exactly
    when its exact makespan exceeds it, with that makespan as its bound.
    The top-k equals the unpruned sweep's."""
    items = _ramp_items()
    k = 3
    inc = Incumbent(k)
    stats = BatchStats()
    res = simulate_jax_many(
        items, "availability", min_lockstep=2, stats=stats,
        prunes=[PruneContext(inc, tolerance=JAX_RTOL) for _ in items])
    n_lanes = sum(len(s) for _, s in items)
    fg1, sys1 = items[0]
    seeds = sorted(range(len(sys1)),
                   key=lambda i: (-sum(pool_layout(fg1.kinds,
                                                   sys1[i])[1]), i))[:k]
    assert stats.serial_fallback_lanes == k
    assert stats.reference_lanes == stats.diverged_lanes == 0
    assert stats.heap_lanes + stats.retired_lanes == n_lanes - k
    assert stats.retired_lanes > 0 and stats.retire_sweeps >= 1
    seed_ms = sorted(simulate_fast(fg1, sys1[i], "availability").makespan
                     for i in seeds)
    cut = seed_ms[k - 1] * (1.0 + 4.0 * JAX_RTOL)
    for gi, ((fg, systems), sims) in enumerate(zip(items, res)):
        for i, (system, sim) in enumerate(zip(systems, sims)):
            ref = simulate_fast(fg, system, "availability")
            if gi == 0 and i in seeds:
                assert sim.makespan == ref.makespan
            elif ref.makespan > cut:
                assert isinstance(sim, Retired) and sim.bound == ref.makespan
            else:
                assert (sim.makespan, sim.placements) == \
                    (ref.makespan, ref.placements), system.name

    def top(results):
        done = [(sim.makespan, system.name)
                for (_fg, systems), sims in zip(items, results)
                for system, sim in zip(systems, sims)
                if not isinstance(sim, Retired)]
        return sorted(done)[:k]
    assert top(res) == top(simulate_jax_many(items, "availability",
                                             min_lockstep=2))


def test_mixed_sweep_sends_the_small_group_serial_and_the_large_to_the_heap():
    """One group below ``min_lockstep`` and one above, in one sweep: the
    small group runs on the exact serial path, the large one in the heap
    scan, and every result is bit-identical to the exact reference."""
    fg1, g1 = frozen_for(synth_trace(30), smp=True)
    fg2, g2 = frozen_for(synth_trace(18), smp=False)
    items = [(fg1, _ramp(range(1, 5), "s")), (fg2, _ramp(range(1, 13), "l"))]
    stats = BatchStats()
    res = simulate_jax_many(items, "availability", stats=stats)
    assert stats.small_group_lanes == 4
    assert stats.heap_lanes == stats.lockstep_lanes == 12
    assert stats.reference_lanes == stats.diverged_lanes == 0
    assert stats.serial_fallback_lanes == stats.scan_lane_steps == 0
    for g, (_fg, systems), sims in zip((g1, g2), items, res):
        for system, sim in zip(systems, sims):
            ref = Simulator(g, system, policy="availability").run()
            assert sims_equivalent(sim, ref, 0.0), system.name


# ---------------------------------------------------------------------------
# the paper's applications through Explorer, on every engine
# ---------------------------------------------------------------------------


def _matmul(n, bs, accs):
    trace = mm.trace_matmul(n=n, bs=bs, verify=False)
    rep = HLSSynthesisModel().matmul_block(bs, kind=f"fpga:mxm{bs}")
    cands = []
    for a in accs:
        for smp in (False, True):
            name = f"{a}acc{bs}" + ("+smp" if smp else "")
            kinds = (rep.device_kind, "smp") if smp else (rep.device_kind,)
            cands.append(Candidate(
                name=name, system=zynq_system(name, {rep.device_kind: a}),
                eligibility=Eligibility({"mxm_block": kinds}),
                fabric=[(rep, a)]))
    return (trace, {("mxm_block", rep.device_kind): rep}, cands,
            a9_smp_seconds("float32"))


def _cholesky(n, bs, accs):
    trace = chol.trace_cholesky(n=n, bs=bs, verify=False)
    rep = chol.hls_reports(bs)["dgemm"][False]
    cands = []
    for a in accs:
        for smp in (False, True):
            name = f"dgemm{a}" + ("+smp" if smp else "")
            elig = {op: ("smp",) for op in ("dpotrf", "dsyrk", "dtrsm")}
            elig["dgemm"] = (rep.device_kind, "smp") if smp \
                else (rep.device_kind,)
            cands.append(Candidate(
                name=name, system=zynq_system(name, {rep.device_kind: a}),
                eligibility=Eligibility(elig), fabric=[(rep, a)]))
    return trace, chol.report_map(bs), cands, a9_smp_seconds("float64")


# each app's two pool-template groups (+-SMP) hold 8 or 12 lanes, at least
# MIN_LOCKSTEP: the jax megabatch runs every lane in the heap scan
APPS = {
    "matmul256-bs64": lambda: _matmul(256, 64, range(1, 9)),
    "matmul256-bs128": lambda: _matmul(256, 128, range(1, 9)),
    "cholesky512-bs64": lambda: _cholesky(512, 64, range(1, 13)),
}


@pytest.mark.parametrize("app", APPS)
def test_paper_apps_rank_identically_on_every_engine(app):
    trace, reports, cands, smp_fn = APPS[app]()
    ranked = {}
    for engine in ("reference", "fast", "batch", "jax"):
        ex = Explorer(trace, reports, engine=engine, smp_seconds_fn=smp_fn,
                      budget={})
        res = ex.explore(cands)
        assert not res.failed and ex.engine == engine
        ranked[engine] = [(o.name, o.makespan_s) for o in res.ranked]
        if engine == "jax":
            st = ex.batch_stats
            assert st.heap_lanes == len(cands), st
            assert st.serial_fallback_lanes == st.reference_lanes == 0
    assert len(ranked["jax"]) == len(cands)
    for engine in ("fast", "batch", "jax"):
        assert ranked[engine] == ranked["reference"], engine


def test_heap_launch_fault_demotes_the_jax_sweep():
    """``fail_lockstep`` fires at the heap launch too: the jax sweep
    degrades to ``batch`` for the rest of the sweep and still ranks like
    a clean exact run."""
    trace, reports, cands, smp_fn = _matmul(256, 64, range(1, 9))
    clean = Explorer(trace, reports, engine="batch", smp_seconds_fn=smp_fn,
                     budget={}).explore(cands)
    with faults.install("fail_lockstep:1"):
        ex = Explorer(trace, reports, engine="jax", smp_seconds_fn=smp_fn,
                      budget={})
        with pytest.warns(UserWarning, match="degraded to 'batch'"):
            res = ex.explore(cands)
    assert ex.engine == "batch" and ex.stats.engine_demotions == 1
    assert [(o.name, o.makespan_s) for o in res.ranked] == \
        [(o.name, o.makespan_s) for o in clean.ranked]
