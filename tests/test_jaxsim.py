"""The jax scan engine's results, and the exactness of everyone else.

``repro.core.jaxsim`` is declared at the relaxed equivalence tier
(``JAX_RTOL`` relative makespan/busy error, discrete-identical placements,
ranking-stable under the documented tie-break), while ``fastsim`` and
``batchsim`` stay bit-identical to the reference object engine.  Both
contracts live in ``repro.core.replay`` and both are enforced here.  The
jax engine's one path (``simulate_jax_many``: the heap scan, and the exact
serial path for small groups and flagged lanes) is held to tolerance 0
here — bit for bit — so the tier is headroom, not error.  The regression
half of this file exists so a future change can never silently launder
rtol-level results into the exact engines (through the sim caches or
otherwise).
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
from repro.core.jaxsim import _bits_add, _bits_sub, simulate_jax_many
from repro.core.replay import (BatchStats, ENGINE_TOLERANCE, JAX_RTOL,
                               makespans_close, rankings_equivalent,
                               sims_equivalent)
from repro.core.simulator import simulate
from repro.core.taskgraph import Task, TaskGraph
from repro.core.trace import Trace, TraceEvent
from repro.testing.synth import (frozen_for, synth_candidates, synth_report,
                                 synth_reports, synth_trace)


def jax_sims(fg, systems, policy="availability", **kw):
    """One family through the jax engine's path."""
    (sims,) = simulate_jax_many([(fg, systems)], policy, **kw)
    return sims


def assert_jax_tier(fg, systems, policy, **kw):
    """Every lane equal to its own ``simulate_fast`` run bit for bit
    (tolerance 0, inside the jax tier), and the ranking exact."""
    sims = jax_sims(fg, systems, policy, **kw)
    refs = [simulate_fast(fg, system, policy) for system in systems]
    for sim, ref, system in zip(sims, refs, systems):
        assert sim.schedule == []
        assert sim.system == system.name and sim.policy == policy
        assert sims_equivalent(sim, ref, 0.0), \
            (system.name, sim.makespan, ref.makespan)
        assert sim.placements == ref.placements
        assert sim.pool_slots == ref.pool_slots
    got = [s.name for _, s in sorted(
        ((sim.makespan, i), systems[i]) for i, sim in enumerate(sims))]
    want = [s.name for _, s in sorted(
        ((ref.makespan, i), systems[i]) for i, ref in enumerate(refs))]
    spans = {system.name: ref.makespan for system, ref in zip(systems, refs)}
    assert rankings_equivalent(got, want, spans, 0.0)
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
    slot lists mix saturated lanes with contended ones, every lane
    popping its own heap."""
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


def test_jax_chunking_is_invariant():
    """Chunk width is a perf knob, never a semantics knob: every chunking
    of the lane axis yields the same results."""
    fg, _ = frozen_for(synth_trace(20), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 13)]
    base = jax_sims(fg, systems, min_lockstep=2)
    assert [s.makespan for s in base] == \
        [simulate_fast(fg, s, "availability").makespan for s in systems]
    for chunk in (2, 3, 8, 64):
        stats = BatchStats()
        got = jax_sims(fg, systems, min_lockstep=2, chunk=chunk,
                       stats=stats)
        assert stats.heap_lanes == len(systems)
        assert [s.makespan for s in got] == [s.makespan for s in base]
        assert [s.placements for s in got] == [s.placements for s in base]


def test_jax_rejects_unknown_policy():
    fg, _ = frozen_for(synth_trace(4), smp=False)
    with pytest.raises(ValueError, match="policy"):
        jax_sims(fg, [zynq_system("s", {"fpga:k": 1})], "heft")


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
    stats = BatchStats()
    sims = jax_sims(fg, systems, min_lockstep=2, stats=stats)
    assert stats.heap_lanes == len(systems)
    for sim, system in zip(sims, systems):
        ref = simulate_fast(fg, system, "availability")
        assert sim.makespan == ref.makespan
        assert sim.placements == ref.placements


def test_jax_raises_reference_error_on_live_bad_dispatch():
    """A row the reference engine raises on (no compatible pool) must
    surface the same error from the heap scan — it flags the lane, and
    the exact fallback raises."""
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
        jax_sims(fg, systems, min_lockstep=2)


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
            jax_sims(fg, systems, chunk=bad)
        with pytest.raises(ValueError, match="jax_chunk"):
            Explorer(synth_trace(4), synth_reports(), engine="jax",
                     jax_chunk=bad)
    # inapplicable knobs are rejected, not silently ignored
    with pytest.raises(ValueError, match="jax_chunk"):
        Explorer(synth_trace(4), synth_reports(), engine="batch",
                 jax_chunk=16)


def test_scan_inputs_memoised_on_frozen_graph():
    """Repeat sweeps over the same payload reuse the heap scan's graph
    tables (and pickling drops them, like `_rt`)."""
    import pickle
    fg, _ = frozen_for(synth_trace(12), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 9)]
    first = jax_sims(fg, systems, min_lockstep=2)
    cache = fg._jax_xs
    assert len(cache) == 1
    xs_id = id(next(iter(cache.values())))
    again = jax_sims(fg, systems, min_lockstep=2)
    assert id(next(iter(fg._jax_xs.values()))) == xs_id   # reused, not rebuilt
    assert [s.makespan for s in again] == [s.makespan for s in first]
    assert not hasattr(pickle.loads(pickle.dumps(fg)), "_jax_xs")


def test_scan_module_and_scope_names_are_stable(monkeypatch):
    """The trace reads the scan by name: its module is ``jit_run_heap``
    and its operations carry the ``heap_scan`` scope in their metadata."""
    from repro.core import jaxsim
    launched = []
    real = jaxsim._load_heap_runner

    def load(cc, args):
        launched.append(args)
        return real(cc, args)

    monkeypatch.setattr(jaxsim, "_load_heap_runner", load)
    fg, _ = frozen_for(synth_trace(8), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 7)]
    jax_sims(fg, systems, min_lockstep=2)
    with jax.enable_x64(True):
        lowered = jax.jit(jaxsim._heap_runner()).lower(*launched[0])
    text = lowered.as_text(debug_info=True)
    assert "module @jit_run_heap" in text
    assert "heap_scan/" in text
    hlo = lowered.compile().as_text()
    assert 'op_name="jit(run_heap)/heap_scan/while' in hlo


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
    heap lanes match the reference engine at tolerance 0 — the jax tier's
    rtol is headroom, not error, on every backend."""
    fg, _ = frozen_for(synth_trace(24), smp=True)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 9)]
    stats = BatchStats()
    sims = jax_sims(fg, systems, policy, min_lockstep=2, stats=stats)
    assert stats.heap_lanes == stats.lockstep_lanes == len(systems)
    for sim, system in zip(sims, systems):
        assert sims_equivalent(sim, simulate_fast(fg, system, policy), 0.0)
