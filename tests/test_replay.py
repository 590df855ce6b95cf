"""The multi-order replay library: reuse, rescue, persistence, corruption.

The contract under test: a :class:`repro.core.replay.ReplayLibrary` carries
discovered dispatch orders across calls, engines, processes and runs —
warm sweeps route every lane to its remembered order (no serial reference
run, no diverge-detect-resimulate cycle, zero serial fallbacks) while every
completion stays either a validated lockstep lane or an exact serial run,
so batch results remain bit-identical to ``Simulator.run()`` and jax stays
inside its rtol tier *with rescued lanes included*.  Library payloads are
corruption-checked like graph entries: a corrupted, stale or wrong-policy
order entry degrades to rediscovery, never to a wrong replay.
"""
import json
import os
import pickle

import hypothesis
import hypothesis.strategies as st
import pytest

from repro.core import Explorer, zynq_system
from repro.core.batchsim import BatchStats, simulate_batch
from repro.core.diskcache import DiskCache
from repro.core.explore import _process_eval_chunk
from repro.core.fastsim import FrozenGraph, simulate_fast
from repro.core.jaxsim import simulate_jax
from repro.core.replay import (JAX_RTOL, ReplayLibrary, order_valid,
                               sims_equivalent, simulate_grouped)
from repro.core.trace import Trace, TraceEvent
from repro.testing.synth import (frozen_for, synth_candidates, synth_report,
                                 synth_reports, synth_trace)


def assert_bit_identical(fg, systems, policy, sims):
    for sim, system in zip(sims, systems):
        ref = simulate_fast(fg, system, policy)
        assert sim.makespan == ref.makespan, system.name
        assert sim.placements == ref.placements
        assert sim.busy == ref.busy
        assert sim.pool_slots == ref.pool_slots


def ramp(counts, kind="fpga:k"):
    return [zynq_system(f"{n}acc{i}", {kind: n})
            for i, n in enumerate(counts)]


# ---------------------------------------------------------------------------
# ReplayLibrary primitive
# ---------------------------------------------------------------------------


def test_library_records_dedupes_and_caps():
    fg, _ = frozen_for(synth_trace(10), smp=False)
    lib = ReplayLibrary(max_orders_per_key=2)
    system = zynq_system("s", {"fpga:k": 2})
    from repro.core.fastsim import pool_layout
    layout = pool_layout(fg.kinds, system)
    key = lib.key(fg, layout, "availability")
    order = []
    simulate_fast(fg, system, "availability", order_out=order)
    assert lib.record(key, order, (2, 1, 1)) == 0
    assert lib.record(key, order) == 0          # dedupe by content
    assert len(lib) == 1
    other = list(order)
    other[0], other[1] = order[1], order[0]     # any distinct content
    assert lib.record(key, other) == 1
    assert lib.record(key, list(reversed(order))) is None   # cap reached
    assert len(lib) == 2
    orders, sigs, pins = lib.lookup(key)
    assert sigs == {(2, 1, 1): 0} and not pins
    # keys are isolated by policy and template
    assert lib.lookup((key[0], key[1], "eft")) == ([], {}, set())


def test_order_valid_rejects_malformed_orders():
    fg, _ = frozen_for(synth_trace(12), smp=True)
    order = []
    simulate_fast(fg, zynq_system("s", {"fpga:k": 2}), "availability",
                  order_out=order)
    assert order_valid(fg, order)
    assert not order_valid(fg, order[:-1])              # wrong length
    assert not order_valid(fg, list(order) + [0])       # duplicate row
    assert not order_valid(fg, [order[-1]] + order[1:])  # not topological
    assert not order_valid(fg, ["x"] * fg.n)            # not ints
    assert not order_valid(fg, [10 ** 9] + order[1:])   # out of range


def test_export_merge_roundtrip_and_validation():
    fg, _ = frozen_for(synth_trace(16), smp=False)
    systems = ramp(range(1, 9))
    lib = ReplayLibrary()
    simulate_batch(fg, systems, "availability", min_lockstep=2, library=lib)
    payload = lib.export(fg.content_hash(), "availability")
    assert payload and all("orders" in e for e in payload.values())

    fresh = ReplayLibrary()
    added = fresh.merge(fg, "availability", payload)
    assert added == len(fresh) > 0
    stats = BatchStats()
    sims = simulate_batch(fg, systems, "availability", min_lockstep=2,
                          library=fresh, stats=stats)
    assert_bit_identical(fg, systems, "availability", sims)
    assert stats.reference_lanes == 0 and stats.serial_fallback_lanes == 0

    # garbage payloads are rejected wholesale or per entry, never replayed
    assert ReplayLibrary().merge(fg, "availability", "not a dict") == 0
    template = next(iter(payload))
    bad = {template: {"orders": [list(range(fg.n))[::-1], [0] * fg.n],
                      "sigs": {("x",): 0, (1,): "y"}, "pins": [None]}}
    victim = ReplayLibrary()
    assert victim.merge(fg, "availability", bad) == 0
    assert len(victim) == 0


def _one_key(fg, policy="availability"):
    from repro.core.fastsim import pool_layout
    system = zynq_system("s", {"fpga:k": 2})
    layout = pool_layout(fg.kinds, system)
    order = []
    simulate_fast(fg, system, policy, order_out=order)
    lib = ReplayLibrary()
    return lib, lib.key(fg, layout, policy), order


def test_merge_from_store_never_touches_other_dirty_marks():
    """Loading from the store must neither schedule a write-back of its
    own nor wipe a dirty mark another thread/sweep set concurrently."""
    fg, _ = frozen_for(synth_trace(10), smp=False)
    lib, key, order = _one_key(fg)
    lib.record(key, order)
    payload = lib.export(fg.content_hash(), "availability")
    # a pure load applies content but leaves nothing pending to flush
    fresh = ReplayLibrary()
    fresh.merge(fg, "availability", payload, mark_dirty=False)
    assert len(fresh) == 1 and fresh.take_dirty("availability") == []
    # a concurrent local discovery's mark survives a store load
    busy = ReplayLibrary()
    local = list(order)
    local[0], local[1] = order[1], order[0]
    busy.record(key, local)                       # locally discovered
    busy.merge(fg, "availability", payload, mark_dirty=False)
    assert busy.take_dirty("availability") == [fg.content_hash()]


def test_validated_lockstep_lifts_a_pin_but_hearsay_does_not():
    fg, _ = frozen_for(synth_trace(10), smp=False)
    lib, key, order = _one_key(fg)
    lib.record(key, order)
    sig = (2, 1, 1)
    lib.pin_sig(key, sig)
    assert sig in lib.lookup(key)[2]
    # a merged payload's sig map is hearsay: the pin stays
    donor = ReplayLibrary()
    donor.record(key, order, sig)
    lib.merge(fg, "availability", donor.export(fg.content_hash(),
                                               "availability"))
    assert sig in lib.lookup(key)[2]
    # this process's own lockstep validation lifts it
    lib.map_sig(key, sig, 0)
    assert sig not in lib.lookup(key)[2]
    assert lib.lookup(key)[1][sig] == 0


def test_drop_graph_forgets_entries_and_marks():
    fg, _ = frozen_for(synth_trace(10), smp=False)
    lib, key, order = _one_key(fg)
    lib.record(key, order, (2, 1, 1))
    lib.drop_graph(fg.content_hash())
    assert len(lib) == 0
    assert lib.lookup(key) == ([], {}, set())
    assert lib.take_dirty("availability") == []


# ---------------------------------------------------------------------------
# warm replay, rescue, bounds
# ---------------------------------------------------------------------------


def test_warm_library_eliminates_serial_work():
    fg, _ = frozen_for(synth_trace(40), smp=True)
    systems = ramp(range(1, 33))
    lib = ReplayLibrary()
    cold = BatchStats()
    sims = simulate_batch(fg, systems, "availability", min_lockstep=2,
                          stats=cold, library=lib)
    assert_bit_identical(fg, systems, "availability", sims)
    assert cold.reference_lanes >= 1 and len(lib) >= 1

    warm = BatchStats()
    sims2 = simulate_batch(fg, systems, "availability", min_lockstep=2,
                           stats=warm, library=lib)
    assert_bit_identical(fg, systems, "availability", sims2)
    assert warm.reference_lanes == 0, "no serial reference run when warm"
    assert warm.serial_fallback_lanes == 0
    assert warm.diverged_lanes == 0, "signature routing never re-diverges"
    assert warm.order_hits == len(systems)
    assert (warm.lockstep_lanes + warm.order_pinned_lanes) == len(systems)


def test_rescue_rebatches_shared_order_cohorts():
    """Diverged lanes sharing a heap order are re-batched in lockstep
    against the discovered order instead of each paying a serial loop."""
    fg, _ = frozen_for(synth_trace(40), smp=False)
    systems = [zynq_system(f"r{n}-{i}", {"fpga:k": n})
               for n in (1, 2, 3, 16) for i in range(8)]
    lib = ReplayLibrary()
    stats = BatchStats()
    sims = simulate_batch(fg, systems, "availability", min_lockstep=2,
                          rescue_min=2, stats=stats, library=lib)
    assert_bit_identical(fg, systems, "availability", sims)
    assert stats.diverged_lanes > 0
    assert stats.rescued_lanes > 0, "shared-order cohorts must be rescued"
    assert stats.serial_fallback_lanes == 0

    warm = BatchStats()
    simulate_batch(fg, systems, "availability", min_lockstep=2,
                   rescue_min=2, stats=warm, library=lib)
    assert warm.reference_lanes == 0 and warm.diverged_lanes == 0
    assert warm.lockstep_lanes + warm.order_pinned_lanes == len(systems)


def test_unprovable_orders_get_pinned_not_looped():
    """The monotonicity check is conservative: a lane can diverge even on
    its own recorded order.  The library pins such signatures to the exact
    serial path, so warm sweeps never re-gamble on a doomed lockstep."""
    fg, _ = frozen_for(synth_trace(40), smp=True)
    systems = [zynq_system(f"sat{i}", {"fpga:k": 12}) for i in range(4)] + \
              [zynq_system(f"low{i}", {"fpga:k": 1}) for i in range(10)]
    lib = ReplayLibrary()
    sims = simulate_batch(fg, systems, "availability", min_lockstep=2,
                          rescue_min=2, library=lib)
    assert_bit_identical(fg, systems, "availability", sims)
    warm = BatchStats()
    sims2 = simulate_batch(fg, systems, "availability", min_lockstep=2,
                           rescue_min=2, stats=warm, library=lib)
    assert_bit_identical(fg, systems, "availability", sims2)
    assert warm.reference_lanes == 0 and warm.serial_fallback_lanes == 0
    assert warm.order_pinned_lanes > 0


def test_max_rounds_bounds_discovery():
    fg, _ = frozen_for(synth_trace(40), smp=True)
    systems = ramp(range(1, 33))
    stats = BatchStats()
    sims = simulate_batch(fg, systems, "availability", min_lockstep=2,
                          stats=stats, max_rounds=1)
    assert_bit_identical(fg, systems, "availability", sims)
    assert stats.reference_lanes == 1
    assert stats.serial_fallback_lanes > 0, \
        "past the rounds budget lanes degrade to plain serial fallbacks"


def test_library_cap_degrades_to_serial_fallback():
    fg, _ = frozen_for(synth_trace(40), smp=True)
    systems = ramp(range(1, 33))
    lib = ReplayLibrary(max_orders_per_key=1)
    stats = BatchStats()
    sims = simulate_batch(fg, systems, "availability", min_lockstep=2,
                          stats=stats, library=lib)
    assert_bit_identical(fg, systems, "availability", sims)
    assert len(lib) == 1
    assert stats.serial_fallback_lanes > 0


def test_schedule_free_flag_controls_serial_records():
    """The reference/discovery lanes honor the schedule-free flag: sweeps
    rank schedule-free by default (no ScheduledTask ever materialised),
    while ``schedule_free=False`` gives serially-evaluated lanes full
    records (lockstep lanes are schedule-free by construction)."""
    from repro.core.batchsim import _run_lockstep
    fg, _ = frozen_for(synth_trace(24), smp=True)
    systems = ramp(range(1, 9))
    lite = simulate_grouped(fg, systems, "availability", min_lockstep=2,
                            lockstep_fn=_run_lockstep)
    assert all(sim.schedule == [] for sim in lite)
    stats = BatchStats()
    full = simulate_grouped(fg, systems, "availability", min_lockstep=2,
                            schedule_free=False, stats=stats,
                            lockstep_fn=_run_lockstep)
    with_records = [sim for sim in full if sim.schedule]
    assert len(with_records) == stats.reference_lanes \
        + stats.order_pinned_lanes + stats.serial_fallback_lanes \
        + stats.small_group_lanes
    assert with_records, "serial lanes must carry records on request"
    for sim, ref in zip(full, lite):
        assert sim.makespan == ref.makespan


# ---------------------------------------------------------------------------
# randomized: exactness tiers hold with rescued lanes included
# ---------------------------------------------------------------------------


@st.composite
def random_trace(draw):
    n = draw(st.integers(4, 20))
    n_regions = draw(st.integers(1, 5))
    events = [TraceEvent(index=i, name="k", created_at=i * 1e-6,
                         elapsed_smp=draw(st.floats(1e-4, 5e-3)),
                         accesses=[((i % n_regions,), "inout", 512)],
                         devices=("fpga", "smp"))
              for i in range(n)]
    return Trace(events=events, wall_seconds=1.0)


@hypothesis.given(random_trace(), st.booleans(),
                  st.sampled_from(["availability", "eft"]),
                  st.lists(st.integers(1, 12), min_size=2, max_size=10))
@hypothesis.settings(deadline=None, max_examples=20)
def test_batch_bit_identical_with_warm_library(tr, smp, policy, slot_counts):
    """Cold discovery, rescue and warm signature routing all stay pinned
    bit-identical to ``simulate_fast`` (itself pinned to the reference)."""
    fg, _ = frozen_for(tr, smp)
    systems = [zynq_system(f"{n}acc{i}", {"fpga:k": n})
               for i, n in enumerate(slot_counts)]
    lib = ReplayLibrary()
    for _ in range(2):                     # cold, then warm
        sims = simulate_batch(fg, systems, policy, min_lockstep=2,
                              rescue_min=2, library=lib)
        assert_bit_identical(fg, systems, policy, sims)


@hypothesis.given(random_trace(), st.booleans(),
                  st.lists(st.integers(1, 10), min_size=2, max_size=8))
@hypothesis.settings(deadline=None, max_examples=6)
def test_jax_tier_holds_with_warm_library(tr, smp, slot_counts):
    fg, _ = frozen_for(tr, smp)
    systems = [zynq_system(f"{n}acc{i}", {"fpga:k": n})
               for i, n in enumerate(slot_counts)]
    lib = ReplayLibrary()
    for _ in range(2):
        sims = simulate_jax(fg, systems, "availability", min_lockstep=2,
                            rescue_min=2, library=lib)
        for sim, system in zip(sims, systems):
            ref = simulate_fast(fg, system, "availability")
            assert sims_equivalent(sim, ref, JAX_RTOL), system.name
            assert sim.placements == ref.placements


def test_library_is_shared_across_engines():
    """Orders are engine-agnostic: a batch-warmed library serves the jax
    scan (and vice versa) — recorded by the exact path, re-validated per
    lane per backend."""
    fg, _ = frozen_for(synth_trace(30), smp=True)
    systems = ramp(range(1, 17))
    lib = ReplayLibrary()
    simulate_batch(fg, systems, "availability", min_lockstep=2, library=lib)
    jstats = BatchStats()
    sims = simulate_jax(fg, systems, "availability", min_lockstep=2,
                        library=lib, stats=jstats)
    assert jstats.reference_lanes == 0, "batch-warmed orders serve the scan"
    assert jstats.order_hits > 0
    for sim, system in zip(sims, systems):
        ref = simulate_fast(fg, system, "availability")
        assert sims_equivalent(sim, ref, JAX_RTOL)


# ---------------------------------------------------------------------------
# on-disk persistence: warm starts, corruption, staleness, wrong policy
# ---------------------------------------------------------------------------


@pytest.fixture()
def world():
    return synth_trace(40), synth_reports(), synth_report()


def _entry_kind(path):
    """First element of the stored key-text JSON ("graph"/"sim"/"orders")."""
    if not os.path.isfile(path):           # e.g. the quarantine/ directory
        return None
    blob = open(path, "rb").read()
    try:
        wrapper = pickle.loads(blob[65:])
        return json.loads(wrapper["key"])[0]
    except Exception:                      # noqa: BLE001 — corrupt entry
        return None


def _drop_entries(root, kinds):
    for f in os.listdir(root):
        p = os.path.join(root, f)
        if _entry_kind(p) in kinds:
            os.unlink(p)


def test_orders_persist_across_runs(tmp_path, world):
    trace, reports, rep = world
    cands = synth_candidates(range(1, 17), rep)
    ex1 = Explorer(trace, reports, cache_dir=str(tmp_path))
    r1 = ex1.explore(cands)
    assert ex1.batch_stats.reference_lanes > 0
    kinds = {_entry_kind(os.path.join(str(tmp_path), f))
             for f in os.listdir(str(tmp_path))}
    assert "orders" in kinds, "order entries land in the store"

    # a fresh process re-simulating (sim entries dropped, orders kept)
    # starts warm: no reference runs, no serial fallbacks, same ranking
    _drop_entries(str(tmp_path), {"sim"})
    ex2 = Explorer(trace, reports, cache_dir=str(tmp_path))
    r2 = ex2.explore(cands)
    assert ex2.batch_stats.reference_lanes == 0
    assert ex2.batch_stats.serial_fallback_lanes == 0
    assert ex2.batch_stats.order_hits > 0
    assert [(o.name, o.makespan_s) for o in r2.ranked] == \
        [(o.name, o.makespan_s) for o in r1.ranked]


def test_corrupted_order_entries_rediscovered(tmp_path, world):
    trace, reports, rep = world
    cands = synth_candidates(range(1, 17), rep)
    r1 = Explorer(trace, reports, cache_dir=str(tmp_path)).explore(cands)
    # corrupt every order entry (bit flip past the digest) and drop sims
    for f in os.listdir(str(tmp_path)):
        p = os.path.join(str(tmp_path), f)
        if _entry_kind(p) == "orders":
            blob = open(p, "rb").read()
            open(p, "wb").write(blob[:70] + b"\xde\xad" + blob[72:])
    _drop_entries(str(tmp_path), {"sim"})
    ex = Explorer(trace, reports, cache_dir=str(tmp_path))
    r = ex.explore(cands)
    assert ex.batch_stats.reference_lanes > 0, "orders rediscovered"
    assert [(o.name, o.makespan_s) for o in r.ranked] == \
        [(o.name, o.makespan_s) for o in r1.ranked]
    # and the rewritten entries are healthy again
    _drop_entries(str(tmp_path), {"sim"})
    ex3 = Explorer(trace, reports, cache_dir=str(tmp_path))
    ex3.explore(cands)
    assert ex3.batch_stats.reference_lanes == 0


def test_tampered_order_payload_discarded_by_validation(tmp_path, world):
    """An entry that passes the DiskCache integrity check but carries
    orders for some other graph (stale re-home / manual tampering) must be
    rejected by the topological validation, not replayed."""
    trace, reports, rep = world
    cands = synth_candidates(range(1, 17), rep)
    ex1 = Explorer(trace, reports, cache_dir=str(tmp_path))
    r1 = ex1.explore(cands)
    dc = DiskCache(str(tmp_path))
    rewritten = 0
    for f in list(dc.entries()):
        p = os.path.join(str(tmp_path), f)
        if _entry_kind(p) != "orders":
            continue
        wrapper = pickle.loads(open(p, "rb").read()[65:])
        payload = wrapper["value"]
        for entry in payload.values():
            entry["orders"] = [list(reversed(o)) for o in entry["orders"]]
        dc.put(wrapper["key"], payload)    # internally-consistent, wrong
        rewritten += 1
    assert rewritten > 0
    _drop_entries(str(tmp_path), {"sim"})
    ex = Explorer(trace, reports, cache_dir=str(tmp_path))
    r = ex.explore(cands)
    assert ex.batch_stats.reference_lanes > 0, \
        "invalid orders must be discarded and rediscovered"
    assert [(o.name, o.makespan_s) for o in r.ranked] == \
        [(o.name, o.makespan_s) for o in r1.ranked]


def test_wrong_policy_orders_never_reused(tmp_path, world):
    trace, reports, rep = world
    cands = synth_candidates(range(1, 17), rep)
    Explorer(trace, reports, cache_dir=str(tmp_path)).explore(cands)
    eft = Explorer(trace, reports, policy="eft", cache_dir=str(tmp_path))
    eft.explore(cands)
    assert eft.batch_stats.reference_lanes > 0, \
        "availability orders must not satisfy an eft sweep"
    assert eft.batch_stats.order_hits == 0


def test_orders_keyed_by_graph_content(tmp_path, world):
    trace, reports, rep = world
    cands = synth_candidates(range(1, 17), rep)
    Explorer(trace, reports, cache_dir=str(tmp_path)).explore(cands)
    other = synth_trace(40, n_regions=3)           # different dependences
    exo = Explorer(other, reports, cache_dir=str(tmp_path))
    exo.explore(cands)
    assert exo.batch_stats.order_hits == 0, \
        "another trace's graphs never reuse these orders"


# ---------------------------------------------------------------------------
# cross-process warm start (worker registry ships orders both ways)
# ---------------------------------------------------------------------------


def test_worker_chunks_replay_shipped_orders(world):
    trace, reports, rep = world
    fg, _ = frozen_for(trace, smp=True)
    systems = ramp(range(1, 17))
    lib = ReplayLibrary()
    simulate_batch(fg, systems, "availability", library=lib)
    export = lib.export(fg.content_hash(), "availability")
    items = list(enumerate(systems))
    got, worker_orders, wstats = _process_eval_chunk(
        "h-orders", fg, items, "availability", True, export, 32)
    assert wstats["reference_lanes"] == 0 and wstats["order_hits"] > 0
    assert worker_orders, "the worker ships its order set back"
    ref = {i: simulate_fast(fg, s, "availability").makespan
           for i, s in items}
    assert {pos: sim.makespan for pos, sim in got} == ref
    # the returned payload merges cleanly into a fresh parent library
    fresh = ReplayLibrary()
    fresh.merge(fg, "availability", worker_orders)
    assert len(fresh) == len(lib)


def test_process_pool_sweeps_merge_worker_discoveries(tmp_path, world):
    trace, reports, rep = world
    cands = synth_candidates(range(1, 17), rep)
    serial = Explorer(trace, reports).explore(cands)
    lib = ReplayLibrary()
    exp = Explorer(trace, reports, processes=2, order_library=lib)
    rp = exp.explore(cands)
    assert [(o.name, o.makespan_s) for o in rp.ranked] == \
        [(o.name, o.makespan_s) for o in serial.ranked]
    assert len(lib) > 0, "worker discoveries flow back to the sweep library"
    # cross-process warm start through the store: orders persisted by a
    # serial run serve a later process-pool run (sims dropped to force
    # the engines to actually replay)
    Explorer(trace, reports, cache_dir=str(tmp_path)).explore(cands)
    _drop_entries(str(tmp_path), {"sim"})
    warm = Explorer(trace, reports, cache_dir=str(tmp_path), processes=2)
    rw = warm.explore(cands)
    assert [(o.name, o.makespan_s) for o in rw.ranked] == \
        [(o.name, o.makespan_s) for o in serial.ranked]
    assert warm.batch_stats.order_hits > 0
    assert warm.batch_stats.reference_lanes == 0


# ---------------------------------------------------------------------------
# explorer-level telemetry
# ---------------------------------------------------------------------------


def test_cache_stats_mirror_lane_telemetry(world):
    trace, reports, rep = world
    cands = synth_candidates(range(1, 17), rep)
    ex = Explorer(trace, reports)
    res = ex.explore(cands)
    assert res.cache["diverged_lanes"] == ex.batch_stats.diverged_lanes
    assert res.cache["serial_fallback_lanes"] == 0
    assert ex.stats.diverged_lanes == ex.batch_stats.diverged_lanes
    # warm re-rank hits the sim cache: the second delta records no lanes
    res2 = ex.explore(cands)
    assert res2.cache["diverged_lanes"] == 0


def test_explorer_rejects_bad_rescue_rounds(world):
    trace, reports, _ = world
    with pytest.raises(ValueError, match="max_rescue_rounds"):
        Explorer(trace, reports, max_rescue_rounds=-1)
