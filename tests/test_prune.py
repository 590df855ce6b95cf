"""In-flight lane retirement: branch-and-bound fused into the engines.

The exactness contract under test: with ``prune=True`` the reported
top-k is **bit-identical** to the unpruned sweep on the exact engines
(reference/fast/batch — a retired lane's final makespan provably exceeds
the incumbent cutoff, so it can never displace a top-k member), and
rtol-stable on the jax tier (the cutoff is inflated by the engine
tolerance so a sub-tolerance tie is never retired).  Retired lanes are
reported as ``status="pruned"`` with their bound — never silently
ranked.

Alongside the randomized property suite: the lockstep backends'
compaction/masking edge cases (all lanes retired, none retired,
retire-then-rescue), cross-process incumbent folding, the retirement
telemetry counters, and the serve-protocol prune-knob validation.
"""
import hypothesis
import hypothesis.strategies as st
import pytest

from repro.core import Candidate, Eligibility, Explorer, zynq_system
from repro.core.batchsim import BatchStats, simulate_batch
from repro.core.fastsim import LanePruned, simulate_fast
from repro.core.hlsreport import KernelReport
from repro.core.replay import (JAX_RTOL, Incumbent, PruneContext, Retired,
                               ReplayLibrary, bound_aux, rankings_equivalent,
                               serial_tails)
from repro.core.trace import Trace, TraceEvent
from repro.serve.protocol import ProtocolError, SweepRequest
from repro.testing.synth import frozen_for, synth_trace


EXACT_ENGINES = ("reference", "fast", "batch")


# ---------------------------------------------------------------------------
# Randomized world generator (scalar mode — the incumbent's home turf)
# ---------------------------------------------------------------------------


def _world(seed):
    import random
    rng = random.Random(seed)
    n = rng.randrange(10, 32)
    n_regions = rng.choice([2, 3, 4])
    events = [TraceEvent(index=i, name="k", created_at=i * 1e-6,
                         elapsed_smp=1e-3 * rng.choice([1, 2, 3, 5]),
                         accesses=[((i % n_regions,), "inout", 1024)],
                         devices=("fpga", "smp"))
              for i in range(n)]
    trace = Trace(events=events, wall_seconds=n * 1e-3)
    rep = KernelReport(kernel="k", device_kind="fpga:k", compute_s=1e-4,
                       dma_in_s=1e-5, dma_out_s=2e-5,
                       resources={"dsp": 100.0, "bram_kb": 10.0,
                                  "lut": 1000.0})
    reports = {("k", "fpga:k"): rep}
    accs = sorted(rng.sample(range(1, 9), rng.randrange(3, 7)))
    cands = []
    for n_acc in accs:
        for smp in (False, True):
            name = f"{n_acc}acc" + ("+smp" if smp else "")
            kinds = ("fpga:k", "smp") if smp else ("fpga:k",)
            cands.append(Candidate(
                name=name, system=zynq_system(name, {"fpga:k": n_acc}),
                eligibility=Eligibility({"k": kinds})))
    policy = rng.choice(["availability", "eft"])
    k = rng.choice([1, 2, 3])
    return trace, reports, cands, policy, k


def _run(engine, world, prune, **kw):
    trace, reports, cands, policy, k = world
    ex = Explorer(trace, reports, policy=policy, engine=engine, **kw)
    return ex, ex.explore(cands, top_k=k, prune=prune)


def _topk(result, k):
    return [(o.name, o.makespan_s) for o in result.ranked[:k]]


# ---------------------------------------------------------------------------
# Property: pruned top-k is bit-identical on the exact engines
# ---------------------------------------------------------------------------


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=8, deadline=None)
def test_pruned_topk_bit_identical_exact_engines(seed):
    world = _world(seed)
    k = world[4]
    _, ref = _run("fast", world, prune=False)
    full_spans = {o.name: o.makespan_s for o in ref.ranked}
    kth = ref.ranked[min(k, len(ref.ranked)) - 1].makespan_s
    for engine in EXACT_ENGINES:
        ex, got = _run(engine, world, prune=True)
        # the tentpole: prune no longer forces the per-candidate serial
        # path — the requested engine composition is preserved
        assert ex.engine == engine
        assert _topk(got, k) == _topk(ref, k), engine
        # every candidate is accounted for: ranked, pruned or infeasible
        assert len(got.outcomes) == len(ref.outcomes)
        for o in got.outcomes:
            if o.status != "pruned":
                continue
            # a retired lane is provably outside the top-k: its recorded
            # bound — and its true (unpruned) makespan — exceed the k-th
            # best makespan of the full sweep
            assert o.lower_bound_s > kth, (engine, o.name)
            assert full_spans[o.name] > kth, (engine, o.name)
            assert full_spans[o.name] >= o.lower_bound_s, (engine, o.name)


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=4, deadline=None)
def test_pruned_equals_unpruned_per_engine(seed):
    """Within one engine, prune=True and prune=False agree on the whole
    surviving ranking (not just the top-k slice) — pruning only ever
    removes provable losers."""
    world = _world(seed)
    for engine in EXACT_ENGINES:
        _, full = _run(engine, world, prune=False)
        _, pruned = _run(engine, world, prune=True)
        spans = {o.name: o.makespan_s for o in full.ranked}
        for o in pruned.ranked:
            assert o.makespan_s == spans[o.name], engine


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=3, deadline=None)
def test_pruned_topk_rtol_stable_on_jax(seed):
    world = _world(seed)
    k = world[4]
    _, ref = _run("batch", world, prune=False)
    ref_names = [o.name for o in ref.ranked]
    spans = {o.name: o.makespan_s for o in ref.ranked}
    kth = spans[ref_names[min(k, len(ref_names)) - 1]]
    for megabatch in (True, False):
        ex, got = _run("jax", world, prune=True, jax_megabatch=megabatch)
        assert ex.engine == "jax"
        names = [o.name for o in got.ranked]
        assert rankings_equivalent(names[:k], ref_names[:k], spans,
                                   JAX_RTOL)
        for o in got.outcomes:
            if o.status == "pruned":
                # the inflated cutoff keeps sub-tolerance ties ranked, so
                # a jax-retired lane is outside the top-k even after
                # deflating the bound by the tier tolerance
                assert spans[o.name] > kth * (1.0 - 4.0 * JAX_RTOL)


# ---------------------------------------------------------------------------
# Lockstep backend edge cases: all retired / none retired / retire+rescue
# ---------------------------------------------------------------------------


def _ramp(n_tasks=40, n_systems=12):
    fg, _ = frozen_for(synth_trace(n_tasks), True)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n})
               for n in range(1, n_systems + 1)]
    return fg, systems


def test_all_lanes_retired_under_tiny_seed():
    """A parent-shipped cutoff below every makespan retires the whole
    group — the numpy engine's dead-lane compaction collapses to the
    empty sweep without touching the result contract."""
    fg, systems = _ramp()
    prune = PruneContext(Incumbent(1, seed=1e-12))
    stats = BatchStats()
    out = simulate_batch(fg, systems, "availability", stats=stats,
                         prune=prune, min_lockstep=2)
    assert all(isinstance(r, Retired) for r in out)
    assert stats.retired_lanes == len(systems)
    exact = [simulate_fast(fg, s, "availability") for s in systems]
    for r, e in zip(out, exact):
        assert r.bound <= e.makespan      # monotone: bound never overshoots
        assert r.bound > 1e-12            # ...and provably past the cutoff


def test_no_lane_retired_under_infinite_cutoff():
    """An incumbent that never goes finite must leave the sweep
    bit-identical to the unpruned batch run, with zero retirements."""
    fg, systems = _ramp()
    prune = PruneContext(Incumbent(len(systems) + 1))   # k > lanes: never cuts
    stats = BatchStats()
    out = simulate_batch(fg, systems, "availability", stats=stats,
                         prune=prune, min_lockstep=2)
    assert stats.retired_lanes == 0
    for sim, system in zip(out, systems):
        ref = simulate_fast(fg, system, "availability")
        assert sim.makespan == ref.makespan
        assert sim.placements == ref.placements


def test_retire_then_rescue_interaction():
    """A mid-ramp cutoff splits the group three ways — lockstep
    survivors, retired losers, and diverged lanes that still re-simulate
    exactly.  Survivors must stay bit-identical to the serial engine."""
    fg, systems = _ramp()
    exact = {s.name: simulate_fast(fg, s, "availability") for s in systems}
    spans = sorted(e.makespan for e in exact.values())
    cutoff = spans[len(spans) // 2]           # retire the slow half
    prune = PruneContext(Incumbent(1, seed=cutoff))
    stats = BatchStats()
    out = simulate_batch(fg, systems, "availability", stats=stats,
                         prune=prune, min_lockstep=2)
    kept = retired = 0
    for r, s in zip(out, systems):
        if isinstance(r, Retired):
            retired += 1
            assert exact[s.name].makespan > cutoff     # never a survivor
            assert r.bound > cutoff
        else:
            kept += 1
            assert r.makespan == exact[s.name].makespan
    assert retired == stats.retired_lanes > 0
    assert kept > 0
    # makespans at or below the cutoff are never retired (strict > test)
    assert all(not isinstance(r, Retired) for r, s in zip(out, systems)
               if exact[s.name].makespan <= cutoff)


def test_in_lockstep_retirement_with_warm_library():
    """With a warm order library every lane routes straight to a lockstep
    sweep, so retirement happens *inside* ``_run_lockstep`` (the windowed
    bound fold + dead-lane compaction) — ``retire_sweeps`` counts it."""
    fg, systems = _ramp()
    lib = ReplayLibrary()
    simulate_batch(fg, systems, "availability", library=lib, min_lockstep=2)
    exact = [simulate_fast(fg, s, "availability") for s in systems]
    cutoff = min(e.makespan for e in exact) * 0.5
    stats = BatchStats()
    out = simulate_batch(fg, systems, "availability", library=lib,
                         stats=stats, min_lockstep=2,
                         prune=PruneContext(Incumbent(1, seed=cutoff)))
    assert all(isinstance(r, Retired) for r in out)
    assert stats.retired_lanes == len(systems)
    assert stats.retire_sweeps >= 1, stats


def test_serial_abort_bound_is_monotone():
    """``simulate_fast(cutoff=...)`` raises LanePruned only when the
    monotone running bound crossed the cutoff — and that bound never
    exceeds the lane's true makespan."""
    fg, systems = _ramp(n_systems=4)
    tails = serial_tails(fg)
    assert len(tails) == fg.n
    assert all(t >= 0.0 for t in tails)
    for system in systems:
        ref = simulate_fast(fg, system, "availability")
        with pytest.raises(LanePruned) as exc:
            simulate_fast(fg, system, "availability",
                          cutoff=ref.makespan * 0.25, bound_tails=tails)
        assert exc.value.bound <= ref.makespan
        assert exc.value.bound > ref.makespan * 0.25
        # at-or-above the true makespan the lane must complete
        done = simulate_fast(fg, system, "availability",
                             cutoff=ref.makespan, bound_tails=tails)
        assert done.makespan == ref.makespan


def test_bound_aux_tail_is_critical_path_floor():
    fg, _ = _ramp(n_tasks=16, n_systems=1)
    tail, tsm = bound_aux(fg)
    assert tail.shape == tsm.shape == (fg.n,)
    # the sink rows have no successors: zero remaining work
    assert (tsm >= 0.0).all() and (tail >= 0.0).all()
    assert (tail >= tsm).all() is not None  # shapes compatible


# ---------------------------------------------------------------------------
# Cross-process incumbent folding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("processes", [0, 2])
def test_cross_process_pruned_topk_identical(processes):
    """The parent ships its best-so-far at submit time and folds worker
    improvements back through the BatchStats protocol — the pruned top-k
    stays bit-identical to the serial unpruned sweep either way."""
    world = _world(424242)
    trace, reports, cands, policy, k = world
    _, ref = _run("fast", world, prune=False)
    ex = Explorer(trace, reports, policy=policy, processes=processes)
    got = ex.explore(cands, top_k=k, prune=True)
    assert _topk(got, k) == _topk(ref, k)
    d = ex.stats.as_dict()
    assert {"retired_lanes", "retire_sweeps",
            "incumbent_updates"} <= d.keys()


# ---------------------------------------------------------------------------
# Telemetry + protocol knobs
# ---------------------------------------------------------------------------


def test_retirement_telemetry_counters_and_repr():
    world = _world(7)
    trace, reports, cands, policy, _ = world
    ex = Explorer(trace, reports, policy=policy)
    ex.explore(cands, top_k=1, prune=True)
    d = ex.stats.as_dict()
    bd = ex.batch_stats.as_dict()
    for key in ("retired_lanes", "retire_sweeps", "incumbent_updates"):
        assert key in d and key in bd
    if d["retired_lanes"]:
        assert "retire " in repr(ex.stats)
        assert "retire" in repr(ex.batch_stats)
    # unpruned sweeps keep the repr clean — the suffix is only-when-nonzero
    ex2 = Explorer(trace, reports, policy=policy)
    ex2.explore(cands)
    assert "retire " not in repr(ex2.stats)


@pytest.mark.parametrize("bad", ["yes", 1, 0.5, [True], None])
def test_protocol_rejects_non_bool_prune(bad):
    with pytest.raises(ProtocolError):
        SweepRequest.from_json({"trace": "synth:8", "prune": bad})


def test_protocol_accepts_bool_prune():
    req = SweepRequest.from_json({"trace": "synth:8", "prune": True})
    assert req.prune is True
