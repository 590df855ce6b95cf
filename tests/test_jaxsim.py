"""The jax scan engine's tier contract, and the exactness of everyone else.

``repro.core.jaxsim`` is pinned at the relaxed equivalence tier
(``JAX_RTOL`` relative makespan/busy error, discrete-identical placements,
ranking-stable under the documented tie-break), while ``fastsim`` and
``batchsim`` stay bit-identical to the reference object engine.  Both
contracts live in ``repro.core.replay`` and both are enforced here — the
regression half of this file exists so a future change can never silently
launder rtol-level results into the exact engines (through the sim caches
or otherwise).
"""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Explorer, zynq_system
from repro.core.batchsim import simulate_batch
from repro.core.devices import DevicePool, SharedResource, SystemConfig
from repro.core.explore import ENGINE_NAMES, CacheStats
from repro.core.fastsim import FrozenGraph, simulate_fast
from repro.core.jaxsim import _bits_add, _bits_sub, simulate_jax
from repro.core.replay import (BatchStats, ENGINE_TOLERANCE, JAX_RTOL,
                               makespans_close, rankings_equivalent,
                               sims_equivalent)
from repro.core.simulator import simulate
from repro.core.taskgraph import Task, TaskGraph
from repro.core.trace import Trace, TraceEvent
from repro.testing.synth import (frozen_for, synth_candidates, synth_report,
                                 synth_reports, synth_trace)


def assert_jax_tier(fg, systems, policy, **kw):
    """Every lane within the jax tier of its own ``simulate_fast`` run."""
    sims = simulate_jax(fg, systems, policy, **kw)
    refs = [simulate_fast(fg, system, policy) for system in systems]
    for sim, ref, system in zip(sims, refs, systems):
        assert sim.schedule == []
        assert sim.system == system.name and sim.policy == policy
        assert sims_equivalent(sim, ref, ENGINE_TOLERANCE["jax"]), \
            (system.name, sim.makespan, ref.makespan)
        # the discrete halves of the contract are never relaxed
        assert sim.placements == ref.placements
        assert sim.pool_slots == ref.pool_slots
    got = [s.name for _, s in sorted(
        ((sim.makespan, i), systems[i]) for i, sim in enumerate(sims))]
    want = [s.name for _, s in sorted(
        ((ref.makespan, i), systems[i]) for i, ref in enumerate(refs))]
    spans = {system.name: ref.makespan for system, ref in zip(systems, refs)}
    assert rankings_equivalent(got, want, spans, JAX_RTOL)
    return sims


# ---------------------------------------------------------------------------
# randomized tier equivalence: policies × conditional DMA × hetero slots
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
                  st.lists(st.integers(1, 12), min_size=2, max_size=8))
@hypothesis.settings(deadline=None, max_examples=8)
def test_jax_tier_on_augmented_graphs(tr, smp, policy, slot_counts):
    """±smp exercises the conditional per-lane masking both ways; random
    slot lists mix saturated lanes (lockstep) with contended ones (the
    divergence fallback)."""
    fg, _ = frozen_for(tr, smp)
    systems = [zynq_system(f"{n}acc{i}", {"fpga:k": n})
               for i, n in enumerate(slot_counts)]
    assert_jax_tier(fg, systems, policy, min_lockstep=2)


@hypothesis.given(st.integers(2, 20), st.integers(1, 3), st.integers(1, 3),
                  st.sampled_from(["availability", "eft"]))
@hypothesis.settings(deadline=None, max_examples=8)
def test_jax_tier_on_bare_dags_with_two_pools(n, ca, cb, policy):
    """Hand DAGs with two device kinds and counts varying on both pools —
    heterogeneous slot counts beyond the single-accelerator shape."""
    g = TaskGraph()
    uids = []
    for i in range(n):
        kinds = ("a", "b") if i % 3 else ("b", "a")
        t = Task(uid=g.new_uid(), name=f"t{i}", devices=kinds,
                 costs={"a": 0.5 + (i % 5) * 0.25, "b": 1.0 + (i % 3) * 0.5},
                 creation_index=i, meta={"role": "compute"})
        g.add_task(t, infer_deps=False)
        uids.append(t.uid)
        if i >= 1 and i % 2:
            g.add_edge(uids[i - 1], t.uid)
    fg = FrozenGraph.freeze(g)
    systems = [SystemConfig(name=f"s{i}-{j}",
                            pools=[DevicePool("pa", ("a",), i),
                                   DevicePool("pb", ("b",), j)],
                            shared=[SharedResource("x", 1)])
               for i in range(1, ca + 1) for j in range(1, cb + 1)]
    assert_jax_tier(fg, systems, policy, min_lockstep=2)


def test_jax_divergent_lanes_fall_back_exactly():
    """A wide slot ramp forces event-order divergence; diverged lanes must
    be flagged by the in-scan monotonicity check and re-simulated through
    the exact path, with the whole batch staying inside the tier."""
    fg, _ = frozen_for(synth_trace(40), smp=True)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 25)]
    stats = BatchStats()
    assert_jax_tier(fg, systems, "availability", min_lockstep=2, stats=stats)
    assert stats.groups == 1
    assert stats.reference_lanes >= 1, "every discovery records an order"
    assert stats.diverged_lanes > 0, "ramp should force exact fallbacks"
    assert stats.lockstep_lanes > 0, "saturated lanes should stay in the scan"
    assert (stats.lockstep_lanes + stats.order_pinned_lanes
            + stats.reference_lanes + stats.serial_fallback_lanes
            + stats.small_group_lanes) == len(systems)
    # diverged lanes come from the exact path: bit-identical, not just close
    sims = simulate_jax(fg, systems, "availability", min_lockstep=2)
    for sim, system in zip(sims, systems):
        if sim.makespan == simulate_fast(fg, system, "availability").makespan:
            continue
        pytest.fail(f"{system.name}: fallback lane not bit-identical")


def test_jax_chunking_is_invariant():
    """Chunk width is a perf knob, never a semantics knob: every chunking
    of the lane axis yields the same results."""
    fg, _ = frozen_for(synth_trace(20), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 13)]
    base = simulate_jax(fg, systems, "availability", min_lockstep=2)
    for chunk in (2, 3, 8, 64):
        got = simulate_jax(fg, systems, "availability", min_lockstep=2,
                           chunk=chunk)
        assert [s.makespan for s in got] == [s.makespan for s in base]
        assert [s.placements for s in got] == [s.placements for s in base]


def test_jax_rejects_unknown_policy():
    fg, _ = frozen_for(synth_trace(4), smp=False)
    with pytest.raises(ValueError, match="policy"):
        simulate_jax(fg, [zynq_system("s", {"fpga:k": 1})], "heft")


def test_jax_pure_smp_lanes_skip_inactive_dma_rows():
    """A pool template with no accelerator (and no DMA resources) forces
    every compute task onto the SMP, so every DMA row is conditionally
    inactive: the exact engines evaluate this fine, and so must the scan —
    row validity is runtime state, never an eager check (regression for
    the eager `_validate_rows` bug)."""
    fg, _ = frozen_for(synth_trace(24), smp=True)
    systems = [SystemConfig(name=f"smp{i}",
                            pools=[DevicePool("smp", ("smp",), i)],
                            shared=[SharedResource("submit", 1)])
               for i in range(1, 9)]
    sims = simulate_jax(fg, systems, "availability", min_lockstep=2)
    for sim, system in zip(sims, systems):
        ref = simulate_fast(fg, system, "availability")
        assert sim.makespan == ref.makespan
        assert sim.placements == ref.placements


def test_jax_raises_reference_error_on_live_bad_dispatch():
    """A row the reference engine raises on (no compatible pool) must
    surface the same error from the scan — via the exact fallback."""
    g = TaskGraph()
    for i in range(10):
        kinds = ("a",) if i != 5 else ("gpu",)
        g.add_task(Task(uid=g.new_uid(), name=f"t{i}", devices=kinds,
                        costs={kinds[0]: 1.0}, creation_index=i,
                        meta={"role": "compute"}), infer_deps=False)
    fg = FrozenGraph.freeze(g)
    systems = [SystemConfig(name=f"s{i}", pools=[DevicePool("pa", ("a",), i)],
                            shared=[SharedResource("x", 1)])
               for i in range(1, 9)]
    with pytest.raises(RuntimeError, match="no compatible pool"):
        simulate_jax(fg, systems, "availability", min_lockstep=2)


# ---------------------------------------------------------------------------
# the tolerance tier machinery itself
# ---------------------------------------------------------------------------


def test_engine_tolerance_tiers():
    """The exact engines are pinned at tolerance 0 — the tier table is the
    contract the equivalence tests (and fig6 asserts) read, so an rtol
    sneaking into fastsim/batchsim must fail here first."""
    assert ENGINE_TOLERANCE["reference"] == 0.0
    assert ENGINE_TOLERANCE["fast"] == 0.0
    assert ENGINE_TOLERANCE["batch"] == 0.0
    assert ENGINE_TOLERANCE["jax"] == JAX_RTOL > 0.0


def test_makespans_close_tiers():
    assert makespans_close(1.0, 1.0, 0.0)
    assert not makespans_close(1.0, 1.0 + 1e-12, 0.0)   # exact means exact
    assert makespans_close(1.0, 1.0 + 5e-7, 1e-6)
    assert not makespans_close(1.0, 1.0 + 5e-6, 1e-6)


def test_sims_equivalent_relaxes_floats_only():
    ref = simulate_fast(*_one_sim())
    close = _replace_makespan(ref, ref.makespan * (1 + 5e-7))
    far = _replace_makespan(ref, ref.makespan * (1 + 5e-5))
    assert sims_equivalent(ref, ref, 0.0)
    assert not sims_equivalent(close, ref, 0.0)
    assert sims_equivalent(close, ref, JAX_RTOL)
    assert not sims_equivalent(far, ref, JAX_RTOL)
    # discrete mismatches fail at every tier
    import dataclasses
    flipped = dataclasses.replace(
        ref, placements={u: "smp" for u in ref.placements})
    if ref.placements:
        assert not sims_equivalent(flipped, ref, JAX_RTOL)


def _one_sim():
    fg, _ = frozen_for(synth_trace(8), smp=True)
    return fg, zynq_system("s", {"fpga:k": 2}), "availability"


def _replace_makespan(sim, value):
    import dataclasses
    return dataclasses.replace(sim, makespan=value)


def test_rankings_equivalent_tie_break():
    spans = {"a": 1.0, "b": 1.0 + 1e-8, "c": 2.0}
    assert rankings_equivalent(["a", "b", "c"], ["a", "b", "c"], spans, 0.0)
    # a sub-tolerance swap is a legal tie resolution...
    assert rankings_equivalent(["b", "a", "c"], ["a", "b", "c"], spans,
                               JAX_RTOL)
    # ...but never at the exact tier, and never across a real gap
    assert not rankings_equivalent(["b", "a", "c"], ["a", "b", "c"], spans,
                                   0.0)
    assert not rankings_equivalent(["c", "b", "a"], ["a", "b", "c"], spans,
                                   JAX_RTOL)
    # and the two rankings must rank the same candidate set
    assert not rankings_equivalent(["a", "b"], ["a", "c"], spans, JAX_RTOL)


# ---------------------------------------------------------------------------
# regression: exact engines stay exact (no silent rtol leak)
# ---------------------------------------------------------------------------


def test_exact_engines_still_bit_identical():
    """`fast` and `batch` are pinned with `==`, not rtol: this is the
    canary that fails if anyone relaxes the exact engines' assertions."""
    tr = synth_trace(24)
    for smp in (False, True):
        fg, graph = frozen_for(tr, smp)
        for policy in ("availability", "eft"):
            systems = [zynq_system(f"{n}acc", {"fpga:k": n})
                       for n in range(1, 9)]
            batch = simulate_batch(fg, systems, policy, min_lockstep=2)
            for sim, system in zip(batch, systems):
                fast = simulate_fast(fg, system, policy)
                ref = simulate(graph, system, policy=policy)
                assert fast.makespan == ref.makespan       # bit-identity,
                assert sim.makespan == ref.makespan        # not rtol
                assert fast.busy == ref.busy == sim.busy
                assert fast.placements == ref.placements == sim.placements


def test_jax_tier_never_leaks_into_exact_sim_cache(tmp_path):
    """A jax-tier result persisted to the shared on-disk store must never
    satisfy an exact engine's lookup: the sim-cache key is namespaced by
    tier, so the exact sweep recomputes and stays bit-identical."""
    reports, rep = synth_reports(), synth_report()
    tr = synth_trace(24)
    cands = synth_candidates(range(1, 7), rep)
    cache_dir = str(tmp_path / "store")
    jaxr = Explorer(tr, reports, engine="jax",
                    cache_dir=cache_dir).explore(cands)
    exact = Explorer(tr, reports, engine="batch",
                     cache_dir=cache_dir).explore(cands)
    # graphs are exact artifacts and ARE shared across tiers
    assert exact.cache["disk_hits"] >= 1
    # ...but every exact makespan must equal the reference float-for-float
    ref = Explorer(tr, reports, engine="fast").explore(cands)
    assert [(o.name, o.makespan_s) for o in exact.ranked] == \
        [(o.name, o.makespan_s) for o in ref.ranked]
    # and the jax sweep agrees with the exact one under the tie-break
    spans = {o.name: o.makespan_s for o in ref.ranked}
    assert rankings_equivalent([o.name for o in jaxr.ranked],
                               [o.name for o in ref.ranked], spans, JAX_RTOL)


def test_exact_sim_cache_serves_jax_reads(tmp_path):
    """Tier blocking is one-directional: a warm *exact* store must serve a
    jax re-rank (bit-exact trivially satisfies rtol) — only rtol entries
    feeding exact lookups is forbidden."""
    reports, rep = synth_reports(), synth_report()
    tr = synth_trace(24)
    cands = synth_candidates(range(1, 7), rep)
    cache_dir = str(tmp_path / "store")
    Explorer(tr, reports, engine="batch", cache_dir=cache_dir).explore(cands)
    jaxr = Explorer(tr, reports, engine="jax",
                    cache_dir=cache_dir).explore(cands)
    # every sim lookup read through to the exact entries — no graph builds,
    # no scan runs, just re-ranking from disk
    assert jaxr.cache["disk_hits"] >= len(cands)
    assert jaxr.cache["eval_misses"] == len(cands)
    exact = Explorer(tr, reports, engine="fast").explore(cands)
    assert [(o.name, o.makespan_s) for o in jaxr.ranked] == \
        [(o.name, o.makespan_s) for o in exact.ranked]


# ---------------------------------------------------------------------------
# explorer wiring: engine names, jax dispatch, guardrails
# ---------------------------------------------------------------------------


def test_engine_names_and_validation():
    reports = synth_reports()
    tr = synth_trace(4)
    assert ENGINE_NAMES == ("reference", "fast", "batch", "jax")
    with pytest.raises(ValueError) as ei:
        Explorer(tr, reports, engine="heft")
    # the error names every valid engine (the "falls through" fix)
    for name in ENGINE_NAMES:
        assert repr(name) in str(ei.value)
    # engine= overrides the legacy booleans
    assert Explorer(tr, reports, engine="reference", fast=True).fast is False
    assert Explorer(tr, reports, engine="fast").batch is False
    assert Explorer(tr, reports, engine="batch").batch is True
    # legacy spellings resolve to engine names
    assert Explorer(tr, reports, fast=False).engine == "reference"
    assert Explorer(tr, reports, batch=False).engine == "fast"
    assert Explorer(tr, reports).engine == "batch"


def test_explorer_jax_matches_batch_and_replays_topk():
    reports, rep = synth_reports(), synth_report()
    tr = synth_trace(30)
    cands = synth_candidates(range(1, 9), rep)
    jaxr = Explorer(tr, reports, engine="jax").explore(cands, top_k=2)
    batch = Explorer(tr, reports, engine="batch").explore(cands, top_k=2)
    spans = {o.name: o.makespan_s for o in batch.ranked}
    assert rankings_equivalent([o.name for o in jaxr.ranked],
                               [o.name for o in batch.ranked], spans,
                               JAX_RTOL)
    # top-k winners are replayed through the exact full-record path
    winners = [o.name for o in jaxr.ranked[:2]]
    for name, est in jaxr.estimates.items():
        assert bool(est.sim.schedule) == (name in winners)


def test_explorer_jax_rejects_processes():
    with pytest.raises(ValueError, match="jax"):
        Explorer(synth_trace(4), synth_reports(), engine="jax", processes=2)


def test_bad_chunk_values_fail_fast():
    """Non-positive chunk widths get a clear ValueError at the API
    boundary — never an opaque range() crash or None-poisoned caches."""
    fg, _ = frozen_for(synth_trace(8), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 9)]
    for bad in (0, -4):
        with pytest.raises(ValueError, match="chunk"):
            simulate_jax(fg, systems, chunk=bad)
        with pytest.raises(ValueError, match="jax_chunk"):
            Explorer(synth_trace(4), synth_reports(), engine="jax",
                     jax_chunk=bad)
    # inapplicable knobs are rejected, not silently ignored
    with pytest.raises(ValueError, match="jax_chunk"):
        Explorer(synth_trace(4), synth_reports(), engine="batch",
                 jax_chunk=16)


def test_scan_inputs_memoised_on_frozen_graph():
    """Repeat sweeps over the same payload reuse the per-step scan inputs
    (and pickling drops them, like `_rt`)."""
    import pickle
    fg, _ = frozen_for(synth_trace(12), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 9)]
    first = simulate_jax(fg, systems, "availability", min_lockstep=2)
    cache = fg._jax_xs
    assert len(cache) == 1
    xs_id = id(next(iter(cache.values())))
    again = simulate_jax(fg, systems, "availability", min_lockstep=2)
    assert id(next(iter(fg._jax_xs.values()))) == xs_id   # reused, not rebuilt
    assert [s.makespan for s in again] == [s.makespan for s in first]
    assert not hasattr(pickle.loads(pickle.dumps(fg)), "_jax_xs")


def test_scan_module_and_scope_names_are_stable(monkeypatch):
    """The trace reads the scan by name: its module is ``jit_run`` and its
    operations carry the ``lockstep_scan`` scope in their metadata."""
    from repro.core import jaxsim
    launched = []
    real = jaxsim._load_runner

    def load(cc, args):
        launched.append(args)
        return real(cc, args)

    monkeypatch.setattr(jaxsim, "_load_runner", load)
    fg, _ = frozen_for(synth_trace(8), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 7)]
    simulate_jax(fg, systems, "availability", min_lockstep=2)
    with jax.enable_x64(True):
        lowered = jax.jit(jaxsim._runner()).lower(*launched[0])
    text = lowered.as_text(debug_info=True)
    assert "module @jit_run" in text
    assert "lockstep_scan/" in text
    hlo = lowered.compile().as_text()
    assert 'op_name="jit(run)/lockstep_scan/while' in hlo


def test_cache_stats_repr_has_disk_counters():
    s = CacheStats(graph_hits=3, graph_misses=1, eval_hits=7, eval_misses=2,
                   disk_hits=5, disk_misses=4, diverged_lanes=6,
                   rescued_lanes=2, serial_fallback_lanes=1)
    r = repr(s)
    assert "disk 5h/4m" in r and "graph 3h/1m" in r and "eval 7h/2m" in r
    # the fallback telemetry (diverged/rescued/serial-fallback) is visible
    assert "lanes 6d/2r/1f" in r


# ---------------------------------------------------------------------------
# exact float64 time on int64 bit patterns (the lax step's representation)
# ---------------------------------------------------------------------------


def test_bits_add_and_sub_are_ieee_float64():
    """``_bits_add`` / ``_bits_sub`` on float64 bit patterns equal IEEE
    float64 arithmetic bit for bit: scan-sized times, wide exponent gaps,
    subnormals, huge values, exact ties, one-ulp neighbours, 0 and +inf."""
    rng = np.random.default_rng(11)

    def draw(n):
        return np.concatenate([
            rng.random(n) * 1e-1, rng.random(n) * 1e-4, rng.random(n) * 1e3,
            np.exp(rng.uniform(-745.0, 709.0, n)),
            [0.0, 5e-324, 1e-310, 2.2250738585072014e-308, 1.0, 1e308,
             np.inf]])

    a, b = draw(4000), draw(4000)
    rng.shuffle(b)
    b[:50] = a[:50]                                     # exact ties
    b[50:100] = np.nextafter(a[50:100], np.inf)         # one ulp apart
    hi, lo = np.maximum(a, b), np.minimum(a, b)
    with jax.enable_x64(True):
        def bits(x):
            return jnp.asarray(x.view(np.int64))
        s = np.asarray(jax.jit(_bits_add)(bits(a), bits(b)))
        d = np.asarray(jax.jit(_bits_sub)(bits(hi), bits(lo)))
    assert np.array_equal(s.view(np.float64), a + b)
    fin = np.isfinite(hi)
    assert np.array_equal(d.view(np.float64)[fin], (hi - lo)[fin])
    assert not np.signbit(d.view(np.float64)).any()     # never -0.0


@pytest.mark.parametrize("policy", ["availability", "eft"])
def test_lax_scan_times_are_bit_exact(policy):
    """The lax step's bit-pattern arithmetic is exact IEEE float64, so its
    lockstep lanes match the reference engine at tolerance 0 — the jax
    tier's rtol is headroom, not error, on every backend."""
    fg, _ = frozen_for(synth_trace(24), smp=True)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 9)]
    stats = BatchStats()
    sims = simulate_jax(fg, systems, policy, min_lockstep=2, stats=stats)
    assert stats.lockstep_lanes > 0
    for sim, system in zip(sims, systems):
        assert sims_equivalent(sim, simulate_fast(fg, system, policy), 0.0)


# ---------------------------------------------------------------------------
# the step-indexed scan state: pulled ready times, in-place placement slots
# ---------------------------------------------------------------------------


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


def _two_pools(ca, cb):
    return [SystemConfig(name=f"s{i}-{j}",
                         pools=[DevicePool("pa", ("a",), i),
                                DevicePool("pb", ("b",), j)],
                         shared=[SharedResource("x", 1)])
            for i in range(1, ca + 1) for j in range(1, cb + 1)]


def _ramp(ns, prefix="a"):
    return [zynq_system(f"{prefix}{n}", {"fpga:k": n}) for n in ns]


def _skip_graph(units=3):
    """A chain of units ``r -> d -> x -> z``: ``d`` is a transfer
    conditional on the compute ``x``, active only when ``x`` runs on
    ``a``.  Under ``eft`` the SMP wins ``x`` (0.5 against 2.0), so ``d``
    decides ``x``'s device at its own step, is skipped, and passes its
    ready time on to ``x``, which pops after it (a higher creation index)
    with the heap key still increasing: the lanes stay in lockstep."""
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


#: Nine independent rows joined by one, which three rows follow.
_FAN_IN = [(i, 9) for i in range(9)] + [(9, j) for j in (10, 11, 12)]

#: case -> (policy, families, what the scan must have run for the case to
#: count).
STATE_CASES = {
    # every row a root: all predecessor slots are the pad
    "roots": ("availability", lambda: [(_dag(12, []), _two_pools(3, 3))],
              lambda run: (run.xs[0]["pred"] == run.T[0]).all()),
    # the join row fills the whole predecessor list
    "widest_preds": (
        "availability", lambda: [(_dag(13, _FAN_IN), _two_pools(3, 3))],
        lambda run: run.xs[0]["pred"].shape[1] == 9
        and (run.xs[0]["pred"] < run.T[0]).all(axis=1).any()),
    # lanes the scan completed passed conditional rows through untouched
    "skipped_conditional": (
        "eft", _skip_graph,
        lambda run: all(any(st.kind == "skipped" for st in simulate_fast(
            fg, s, "eft", with_schedule=True).schedule)
            for fg, s in run.kept)),
    # a DMA-in child decided its compute parent's device before the
    # parent's own step: the parent reads an odd (parent) slot
    "parent_placed_early": (
        "availability",
        lambda: [(frozen_for(synth_trace(16), smp=True)[0], _ramp(range(2, 9)))],
        lambda run: (run.xs[0]["at_own"] % 2 == 1).any()),
    # one launch holds lanes of cohorts with different step counts
    "mixed_lengths": (
        "availability",
        lambda: [(frozen_for(synth_trace(10), smp=True)[0], _ramp(range(1, 7))),
                 (frozen_for(synth_trace(17), smp=False)[0],
                  _ramp(range(1, 7), "b"))],
        lambda run: any(len(set(v.sum(axis=0).tolist())) > 1
                        for v in run.valid)),
    # the launches are wider than the lanes they carry
    "padding_lanes": (
        "availability",
        lambda: [(frozen_for(synth_trace(12), smp=False)[0], _ramp(range(1, 6)))],
        lambda run: sum(v.shape[1] for v in run.valid) > sum(run.lanes)),
}


def _run_recorded(monkeypatch, items, policy):
    """One ``_scan_cohorts`` call over every pool-template group of
    ``items``, each group replaying the order of its most-parallel lane
    (a cold reference discovery), recording each launch's ``valid`` block
    and real lane count, each cohort's step inputs, the ``(fg, system)``
    of every lane the scan completed, and its result."""
    import types
    from repro.core import jaxsim
    from repro.core.fastsim import pool_layout
    run = types.SimpleNamespace(valid=[], kept=[], sims=[])
    real_load = jaxsim._load_runner

    def load(cc, args):
        run.valid.append(np.asarray(args[0]["valid"]))
        return real_load(cc, args)

    monkeypatch.setattr(jaxsim, "_load_runner", load)
    cohorts, members = [], []
    for fg, systems in items:
        groups = {}
        for system in systems:
            lay = pool_layout(fg.kinds, system)
            groups.setdefault((tuple(lay[0]), tuple(lay[2])), []).append(
                (system, lay))
        for group in groups.values():
            order = []
            simulate_fast(fg, max(group, key=lambda m: sum(m[1][1]))[0],
                          policy, order_out=order)
            cohorts.append((fg, order, [lay for _, lay in group], None))
            members.append(group)
    outs = jaxsim._scan_cohorts(cohorts, policy,
                                chunk=jaxsim.MEGABATCH_CHUNK)
    run.lanes = [sum(len(group) for group in members)]
    for (fg, _order, _lays, _cut), group, (done, _div, _ret) in zip(
            cohorts, members, outs):
        run.kept.extend((fg, group[pos][0]) for pos in done)
        run.sims.extend(done.values())
    run.xs = [next(iter(fg._jax_xs.values())) for fg, _ in items]
    run.T = [len(x["r"]) for x in run.xs]
    return run


@pytest.mark.parametrize("case", STATE_CASES)
def test_step_indexed_state_is_bit_exact(monkeypatch, case):
    """Ready times pulled from the predecessors' steps and placements read
    at their latest touch give every lane ``simulate_fast``'s makespan,
    busy times and placements bit for bit, in one multi-cohort launch of
    the scan and on the per-graph path, in each case the step-indexed
    state has to get right."""
    policy, build, ran = STATE_CASES[case]
    items = build()
    run = _run_recorded(monkeypatch, items, policy)
    assert run.kept, "no lane ran the scan"
    assert ran(run), f"the scan never exercised {case}"
    for (fg, system), sim in zip(run.kept, run.sims):
        ref = simulate_fast(fg, system, policy)
        assert sims_equivalent(sim, ref, 0.0), (case, system.name)
    for fg, systems in items:
        per_graph = simulate_jax(fg, systems, policy, min_lockstep=2)
        for system, pg in zip(systems, per_graph):
            ref = simulate_fast(fg, system, policy)
            assert sims_equivalent(pg, ref, 0.0), (case, system.name)


def test_scan_step_scatters_into_no_task_indexed_state():
    """The loop body writes its ``[T+1, B]`` ends and ``[2T+1, B]``
    placements with one in-place dynamic-update-slice each; every scatter
    left (clocks, busy, seen) is O(lanes)-sized, and none combines with a
    64-bit ``max`` — the property the step's cost on the chip rests on."""
    import re
    from repro.core import jaxsim
    T, P, S, B, K, NK, PC = 37, 3, 4, 8, 2, 3, 5
    with jax.enable_x64(True):
        def sd(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)
        xs = {"valid": sd((T, B), jnp.bool_), "tb": sd((T, B), jnp.int64),
              "c": sd((T, B), jnp.int32), "is_comp": sd((T, B), jnp.bool_),
              "k_first": sd((T, B), jnp.int32),
              "own_opts": sd((T, B, K), jnp.int32),
              "own_cost": sd((T, B, NK), jnp.int64),
              "par_opts": sd((T, B, K), jnp.int32),
              "par_cost": sd((T, B, NK), jnp.int64),
              "act": sd((T, B, NK), jnp.bool_),
              "bad_row": sd((T, B), jnp.bool_),
              "pred": sd((T, B, PC), jnp.int32),
              "at_own": sd((T, B), jnp.int32),
              "at_par": sd((T, B), jnp.int32)}
        hlo = jax.jit(jaxsim._runner()).lower(
            xs, sd((P, S, B), jnp.int64), sd((T + 1, B), jnp.int64),
            sd((2 * T + 1, B), jnp.int32), sd((P, B), jnp.int64),
            sd((P, B), jnp.bool_), sd((B, NK), jnp.int32),
            sd((B,), jnp.int32), sd((), jnp.bool_)).as_text(dialect="hlo")
    regions = dict(re.findall(r"\n(\S+) [^\n]*\{\n(.*?)\n\}", hlo, re.S))
    scatters = re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(.*to_apply=([\w.]+)",
                          hlo)
    assert scatters, "clocks, busy and seen are scattered"
    for dims, region in scatters:
        assert max(int(d) for d in dims.split(",")) < T, dims
        assert "maximum" not in regions[region], (dims, region)
    updates = re.findall(r"= \w+\[([\d,]*)\]\S* dynamic-update-slice\(", hlo)
    assert sorted(updates) == sorted([f"{T + 1},{B}", f"{2 * T + 1},{B}"])
