"""Cross-engine differential harness (tier-1).

One randomized generator (via hypothesis, or the deterministic
``repro.testing.minihypothesis`` stand-in that prints the falsifying
example when the real package is absent) drives the same candidate sets
through reference/fast/batch — and jax where available —
and asserts tier-correct equivalence of both the scalar rankings and the
Pareto frontiers:

* exact engines (tolerance 0): bit-identical outcome tables, rankings
  and frontier sets, including infeasible/budget-rejected statuses;
* jax (rtol tier): ``rankings_equivalent`` on the scalar ranking and
  ``frontiers_equivalent`` on the frontier, both judged against the
  exact engines' reference values.

This is the reusable oracle for future engine work: a new backend slots
into ``EXACT_ENGINES`` (or the jax-tier test) and inherits the whole
contract.
"""
import random

import hypothesis
import hypothesis.strategies as st

from repro.core.explore import Explorer
from repro.core.hwspec import SpecLibrary
from repro.core.replay import (JAX_RTOL, frontiers_equivalent,
                               rankings_equivalent)
from repro.core.trace import Trace, TraceEvent
from repro.testing.synth import (synth_candidates, synth_report,
                                 synth_reports)


EXACT_ENGINES = ("reference", "fast", "batch")


# ---------------------------------------------------------------------------
# Randomized world generator
# ---------------------------------------------------------------------------


def _world(seed, max_events=32, max_acc=7):
    """One random (trace, candidates, policy, PPA config) draw."""
    rng = random.Random(seed)
    n = rng.randrange(10, max_events)
    n_regions = rng.choice([2, 3, 4])
    events = [TraceEvent(index=i, name="k", created_at=i * 1e-6,
                         elapsed_smp=1e-3 * rng.choice([1, 2, 3, 5]),
                         accesses=[((i % n_regions,), "inout", 1024)],
                         devices=("fpga", "smp"))
              for i in range(n)]
    trace = Trace(events=events, wall_seconds=n * 1e-3)
    reports = synth_reports()
    accs = sorted(rng.sample(range(1, max_acc + 1),
                             rng.randrange(2, min(4, max_acc) + 1)))
    cands = synth_candidates(accs, synth_report())
    policy = rng.choice(["availability", "eft"])
    if rng.random() < 0.7:          # PPA mode most of the time
        objectives = rng.choice([["area_mm2", "energy_j"],
                                 ["energy_j"], list()]) or None
        # power is a *static* axis: the feasible set is engine-
        # independent, so budgeted draws stay comparable across tiers
        budgets = {"power_w": rng.choice([1.9, 2.1, 5.0])} \
            if rng.random() < 0.5 else None
        if objectives is None and budgets is None:
            objectives = ["area_mm2"]
    else:
        objectives = budgets = None
    return trace, reports, cands, policy, objectives, budgets


def _run(engine, world, prune=False, **kw):
    trace, reports, cands, policy, objectives, budgets = world
    ex = Explorer(trace, reports, policy=policy, engine=engine,
                  objectives=objectives, budgets=budgets, **kw)
    return ex, ex.explore(cands, top_k=3, prune=prune)


def _table(result):
    return [(o.name, o.status, o.makespan_s, o.rank, o.objectives)
            for o in result.outcomes]


# ---------------------------------------------------------------------------
# Exact engines: bit identity
# ---------------------------------------------------------------------------


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=8, deadline=None)
def test_exact_engines_bit_identical(seed):
    world = _world(seed)
    _, ref = _run("reference", world)
    for engine in ("fast", "batch"):
        ex, got = _run(engine, world)
        assert ex.engine == engine          # no silent demotion
        assert _table(got) == _table(ref), engine
        assert [o.name for o in got.frontier] == \
            [o.name for o in ref.frontier], engine
        assert got.dominated_count == ref.dominated_count


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=4, deadline=None)
def test_exact_engines_identical_under_energy_budget(seed):
    """Energy budgets reject *post-sim* — the rejection must still be
    bit-identical across the exact engines (same sims, same arithmetic),
    including the energy lower-bound pre-cut outcomes."""
    world = list(_world(seed))
    trace, reports = world[0], world[1]
    lib = SpecLibrary.from_reports(reports)
    # pick a cap between the sweep's min and max energy so both sides
    # of the cut are populated
    ex0, probe = _run("fast", (*world[:4], ["energy_j"], None), hwspec=lib)
    energies = sorted({o.objectives["energy_j"] for o in probe.ranked})
    if len(energies) < 2:
        return
    # cap below the max distinct energy: both sides of the cut populated
    cap = energies[-2]
    world[4], world[5] = ["area_mm2"], {"energy_j": cap}
    _, ref = _run("fast", tuple(world), hwspec=lib)
    _, got = _run("batch", tuple(world), hwspec=lib)
    assert _table(got) == _table(ref)
    assert [o.name for o in got.frontier] == [o.name for o in ref.frontier]
    statuses = {o.status for o in ref.outcomes}
    assert "infeasible" in statuses         # the cut actually fired


# ---------------------------------------------------------------------------
# Pruned column: branch-and-bound retirement preserves every contract
# ---------------------------------------------------------------------------


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=6, deadline=None)
def test_pruned_column_matches_unpruned(seed):
    """``prune=True`` composed with each exact engine: the top-k slice,
    the frontier and the infeasible set are bit-identical to the unpruned
    fast reference; candidates retired mid-sweep surface as ``pruned``
    with a bound that the unpruned sweep confirms exceeds the k-th best."""
    world = _world(seed)
    objectives, budgets = world[4], world[5]
    _, ref = _run("fast", world)
    ref_spans = {o.name: o.makespan_s for o in ref.ranked}
    kth = ref.ranked[min(3, len(ref.ranked)) - 1].makespan_s \
        if ref.ranked else float("inf")
    scalar = objectives is None and budgets is None
    for engine in EXACT_ENGINES:
        ex, got = _run(engine, world, prune=True)
        assert ex.engine == engine          # prune never demotes the engine
        assert [(o.name, o.makespan_s) for o in got.ranked[:3]] == \
            [(o.name, o.makespan_s) for o in ref.ranked[:3]], engine
        assert [o.name for o in got.frontier] == \
            [o.name for o in ref.frontier], engine
        assert sorted(got.infeasible) == sorted(ref.infeasible), engine
        for o in got.outcomes:
            if o.status == "pruned":
                assert scalar, engine   # multi-axis mode never retires here
                assert ref_spans[o.name] > kth, (engine, o.name)
        if not scalar:
            # multi-axis draws (objectives or a static power budget):
            # the scalar incumbent is off, so the sweep is untouched
            assert _table(got) == _table(ref), engine


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=2, deadline=None)
def test_pruned_column_rtol_stable_on_jax(seed):
    world = _world(seed, max_events=20, max_acc=4)
    _, ref = _run("batch", world)
    ref_names = [o.name for o in ref.ranked]
    ref_spans = {o.name: o.makespan_s for o in ref.ranked}
    ex, got = _run("jax", world, prune=True)
    assert ex.engine == "jax"
    names = [o.name for o in got.ranked]
    assert rankings_equivalent(names[:3], ref_names[:3], ref_spans,
                               JAX_RTOL)
    if ref.objectives is not None:
        ref_objs = {o.name: o.objectives for o in ref.ranked}
        assert frontiers_equivalent(
            [o.name for o in got.frontier],
            [o.name for o in ref.frontier],
            ref_objs, ref.objectives, JAX_RTOL)


# ---------------------------------------------------------------------------
# jax tier: ranking- and frontier-stability
# ---------------------------------------------------------------------------


@hypothesis.given(st.integers(0, 10_000))
@hypothesis.settings(max_examples=3, deadline=None)
def test_jax_tier_ranking_and_frontier_stable(seed):
    world = _world(seed, max_events=20, max_acc=4)
    _, ref = _run("batch", world)
    ref_names = [o.name for o in ref.ranked]
    ref_spans = {o.name: o.makespan_s for o in ref.ranked}
    ref_objs = {o.name: o.objectives for o in ref.ranked}
    axes = ref.objectives or ["makespan_s"]
    ex, got = _run("jax", world)
    assert ex.engine == "jax"
    # same candidates survived (power/area budgets are static, so
    # feasibility can never be tier-dependent here)
    assert sorted(o.name for o in got.ranked) == sorted(ref_names)
    assert rankings_equivalent([o.name for o in got.ranked],
                               ref_names, ref_spans, JAX_RTOL)
    if ref.objectives is not None:
        assert frontiers_equivalent(
            [o.name for o in got.frontier],
            [o.name for o in ref.frontier],
            ref_objs, axes, JAX_RTOL)
    # placements/discrete structure are exact even at the rtol tier:
    # area and peak power are spec arithmetic and must be identical
    for o in got.ranked:
        if o.objectives is not None:
            assert o.objectives["area_mm2"] == \
                ref_objs[o.name]["area_mm2"]
            assert o.objectives["power_w"] == \
                ref_objs[o.name]["power_w"]


# ---------------------------------------------------------------------------
# frontiers_equivalent unit contract
# ---------------------------------------------------------------------------

AXES = ["makespan_s", "area_mm2", "energy_j"]


def _objs(makespan, area, energy):
    return {"makespan_s": makespan, "area_mm2": area, "energy_j": energy}


def test_frontiers_equivalent_exact_tier_is_set_equality():
    ref_objs = {"a": _objs(1.0, 2.0, 3.0), "b": _objs(2.0, 1.0, 3.0)}
    assert frontiers_equivalent(["b", "a"], ["a", "b"], ref_objs, AXES, 0.0)
    assert not frontiers_equivalent(["a"], ["a", "b"], ref_objs, AXES, 0.0)
    # unknown names fail outright
    assert not frontiers_equivalent(["a", "z"], ["a"], ref_objs, AXES, 0.0)


def test_frontiers_equivalent_rtol_drop_legality():
    tol = 1e-6
    # y matches x on the exact axis and sits a sub-tolerance margin away
    # on the noisy axes -> dropping x is a legal rtol flip
    ref_objs = {"x": _objs(1.0, 2.0, 3.0),
                "y": _objs(1.0 + 1e-8, 2.0, 3.0 - 1e-8)}
    assert frontiers_equivalent(["y"], ["x", "y"], ref_objs, AXES, tol)
    # but a super-tolerance makespan gap cannot be perturbed away
    ref_far = {"x": _objs(1.0, 2.0, 3.0),
               "y": _objs(1.1, 2.0, 3.0)}
    assert not frontiers_equivalent(["y"], ["x", "y"], ref_far, AXES, tol)


def test_frontiers_equivalent_rtol_appear_legality():
    tol = 1e-6
    # x is dominated in the reference, but only across a noisy margin
    # within tolerance -> appearing is legal
    ref_objs = {"d": _objs(1.0, 2.0, 3.0),
                "x": _objs(1.0 + 1e-8, 2.0, 3.0)}
    assert frontiers_equivalent(["d", "x"], ["d"], ref_objs, AXES, tol)
    # dominated on an *exact* axis (area) with noisy axes far apart:
    # no rtol perturbation explains the appearance
    ref_exact = {"d": _objs(0.5, 1.0, 1.5),
                 "x": _objs(1.0, 2.0, 3.0)}
    assert not frontiers_equivalent(["d", "x"], ["d"], ref_exact, AXES,
                                    tol)
