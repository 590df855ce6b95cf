"""Exploration-engine tests: generators, caches, parallel determinism,
pruning safety, JSON round-trip, plus property-based regression tests for
the simulator/estimator invariants the engine relies on."""
import json

import hypothesis
import hypothesis.strategies as st
import pytest

from repro.core import (Candidate, DesignSpace, Eligibility, ExplorationResult,
                        Explorer, explore, hillclimb, lower_bound_seconds,
                        parallel_map, zynq_system)
from repro.core.augment import build_graph
from repro.core.hlsreport import KernelReport
from repro.core.simulator import simulate
from repro.core.taskgraph import Task, TaskGraph
from repro.core.trace import Trace, TraceEvent


# ---------------------------------------------------------------------------
# Synthetic trace / candidate helpers (no jax, milliseconds to build)
# ---------------------------------------------------------------------------


def synth_trace(n_tasks: int, n_regions: int = 4, kernel: str = "k",
                cost: float = 1e-3) -> Trace:
    """A chain-ish trace: task i inouts region (i % n_regions)."""
    events = [TraceEvent(index=i, name=kernel, created_at=i * 1e-6,
                         elapsed_smp=cost * (1 + (i % 3)),
                         accesses=[((i % n_regions,), "inout", 1024)],
                         devices=("fpga", "smp"))
              for i in range(n_tasks)]
    return Trace(events=events, wall_seconds=n_tasks * cost)


def synth_reports(kernel: str = "k", kind: str = "fpga:k",
                  compute_s: float = 1e-4, dsp: float = 100.0):
    rep = KernelReport(kernel=kernel, device_kind=kind, compute_s=compute_s,
                      dma_in_s=1e-5, dma_out_s=2e-5,
                      resources={"dsp": dsp, "bram_kb": 10.0, "lut": 1000.0})
    return {(kernel, kind): rep}, rep


def synth_candidates(rep, kind: str = "fpga:k", kernel: str = "k",
                     accs=(1, 2), smp_opts=(False, True)):
    out = []
    for n_acc in accs:
        for smp in smp_opts:
            name = f"{n_acc}acc" + ("+smp" if smp else "")
            kinds = (kind, "smp") if smp else (kind,)
            out.append(Candidate(
                name=name, system=zynq_system(name, {kind: n_acc}),
                eligibility=Eligibility({kernel: kinds}),
                fabric=[(rep, n_acc)]))
    return out


@pytest.fixture(scope="module")
def trace():
    return synth_trace(48)


@pytest.fixture(scope="module")
def reports_and_rep():
    return synth_reports()


# ---------------------------------------------------------------------------
# candidate generators
# ---------------------------------------------------------------------------


def test_grid_covers_space_in_order():
    space = DesignSpace({"a": (1, 2, 3), "b": ("x", "y")})
    pts = list(space.points())
    assert space.size == len(pts) == 6
    assert pts[0] == {"a": 1, "b": "x"}
    assert pts[-1] == {"a": 3, "b": "y"}
    assert pts == [space.point_at(i) for i in range(space.size)]


def test_sample_distinct_and_deterministic():
    space = DesignSpace({"a": tuple(range(10)), "b": tuple(range(10))})
    s1 = space.sample(25, seed=7)
    s2 = space.sample(25, seed=7)
    assert s1 == s2
    keys = [(p["a"], p["b"]) for p in s1]
    assert len(set(keys)) == 25
    assert space.sample(10_000)  # clamped to space.size, all distinct


def test_neighbors_step_one_axis():
    space = DesignSpace({"a": (1, 2, 3), "b": (False, True)})
    nbs = space.neighbors({"a": 2, "b": False})
    assert {(p["a"], p["b"]) for p in nbs} == {(1, False), (3, False),
                                              (2, True)}


def test_hillclimb_finds_convex_optimum():
    space = DesignSpace({"x": tuple(range(11)), "y": tuple(range(11))})
    evals = []

    def score(p):
        evals.append(1)
        return (p["x"] - 7) ** 2 + (p["y"] - 2) ** 2

    best, best_s, history = hillclimb(space, score, start={"x": 0, "y": 0})
    assert (best["x"], best["y"]) == (7, 2) and best_s == 0
    # memoised: every scored point is unique
    assert len(evals) == len(history) <= space.size


def test_parallel_map_preserves_order():
    items = list(range(20))
    assert parallel_map(lambda x: x * x, items, max_workers=4) == \
        [x * x for x in items]
    assert parallel_map(lambda x: x * x, items, max_workers=None) == \
        [x * x for x in items]


# ---------------------------------------------------------------------------
# caching
# ---------------------------------------------------------------------------


def test_cache_hit_miss_counters(trace, reports_and_rep):
    reports, rep = reports_and_rep
    ex = Explorer(trace, reports)
    cands = synth_candidates(rep)
    res = ex.explore(cands)
    # 4 candidates, 2 distinct eligibilities (±smp) -> 2 graph builds;
    # the 1acc/2acc pairs share their graph
    assert ex.stats.graph_misses == 2 and ex.stats.graph_hits == 2
    assert ex.stats.eval_misses == 4 and ex.stats.eval_hits == 0
    shared = [o for o in res.outcomes if o.cached_graph]
    assert len(shared) == 2

    res2 = ex.explore(cands)
    assert ex.stats.graph_misses == 2 and ex.stats.eval_misses == 4
    assert ex.stats.eval_hits == 4          # whole simulations reused
    # each result accounts for its own batch, not the Explorer's lifetime
    # (disk counters stay zero: no cache_dir configured)
    lanes = {"diverged_lanes": 0, "rescued_lanes": 0,
             "serial_fallback_lanes": 0}
    faults = {"worker_retries": 0, "pool_respawns": 0, "chunk_timeouts": 0,
              "quarantined": 0, "engine_demotions": 0,
              "cache_quarantined": 0, "retired_lanes": 0,
              "retire_sweeps": 0, "incumbent_updates": 0}
    assert res.cache == {"graph_hits": 2, "graph_misses": 2,
                         "eval_hits": 0, "eval_misses": 4,
                         "disk_hits": 0, "disk_misses": 0, **lanes, **faults}
    assert res2.cache == {"graph_hits": 4, "graph_misses": 0,
                          "eval_hits": 4, "eval_misses": 0,
                          "disk_hits": 0, "disk_misses": 0, **lanes, **faults}
    assert [(o.name, o.makespan_s) for o in res2.ranked] == \
        [(o.name, o.makespan_s) for o in res.ranked]
    assert all(o.cached_eval for o in res2.outcomes)


def test_cache_does_not_change_results(trace, reports_and_rep):
    reports, rep = reports_and_rep
    cands = synth_candidates(rep)
    r_cached = explore(trace, cands, reports, cache=True)
    r_plain = explore(trace, cands, reports, cache=False)
    assert [(o.name, o.makespan_s, o.critical_path_s) for o in r_cached.ranked] \
        == [(o.name, o.makespan_s, o.critical_path_s) for o in r_plain.ranked]


# ---------------------------------------------------------------------------
# parallel evaluation: deterministic, equivalent to serial
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workers", [2, 3, 8])
def test_parallel_equals_serial(trace, reports_and_rep, workers):
    reports, rep = reports_and_rep
    cands = synth_candidates(rep, accs=(1, 2, 3))
    serial = explore(trace, cands, reports, max_workers=1)
    par = explore(trace, cands, reports, max_workers=workers)
    # same ranking AND bit-identical makespans
    assert [o.name for o in par.ranked] == [o.name for o in serial.ranked]
    assert [o.makespan_s for o in par.ranked] == \
        [o.makespan_s for o in serial.ranked]
    assert par.n_workers == min(workers, len(cands))


# ---------------------------------------------------------------------------
# pruning
# ---------------------------------------------------------------------------


def test_infeasible_rejected_before_any_build(trace):
    reports, rep = synth_reports(dsp=500.0)          # 2 fit, 3 do not
    cands = synth_candidates(rep, accs=(1, 3), smp_opts=(False,))
    res = explore(trace, cands, reports)
    assert res.infeasible == ["3acc"]
    assert [o.name for o in res.ranked] == ["1acc"]


def test_pruning_never_discards_true_optimum(trace, reports_and_rep):
    """Hand-checked set: the SMP-only candidate's critical path (a 12-task
    serial chain at SMP speed) is far above the accelerator candidates'
    makespans, so the cut fires — and the surviving ranking must still open
    with the exhaustive optimum."""
    reports, rep = reports_and_rep
    # order matters: a good candidate first gives the cut teeth
    cands = synth_candidates(rep, accs=(2, 1), smp_opts=(False, True))
    cands.append(Candidate(name="smponly",
                           system=zynq_system("smponly", {}),
                           eligibility=Eligibility({"k": ("smp",)})))
    full = explore(trace, cands, reports, prune=False)
    pruned = explore(trace, cands, reports, prune=True, top_k=1)
    assert pruned.best_name == full.best_name
    assert pruned.best.makespan_s == full.best.makespan_s
    # everything pruned was genuinely worse than the found optimum
    full_times = {o.name: o.makespan_s for o in full.ranked}
    for o in pruned.outcomes:
        if o.status == "pruned":
            assert o.lower_bound_s > pruned.best.makespan_s
            assert full_times[o.name] > pruned.best.makespan_s
    # and with slow-SMP candidates the cut actually fires
    assert pruned.pruned, "expected at least one pruned candidate"


def test_pruning_keeps_full_topk(trace, reports_and_rep):
    reports, rep = reports_and_rep
    cands = synth_candidates(rep, accs=(2, 1, 3))
    full = explore(trace, cands, reports, prune=False)
    for k in (1, 2, 3):
        res = explore(trace, cands, reports, prune=True, top_k=k)
        assert [o.name for o in res.top(k)] == \
            [o.name for o in full.ranked[:k]]


# ---------------------------------------------------------------------------
# results: ranking, JSON round-trip, seed API compatibility
# ---------------------------------------------------------------------------


def test_result_ranks_and_top_k(trace, reports_and_rep):
    reports, rep = reports_and_rep
    res = explore(trace, synth_candidates(rep), reports, top_k=2)
    ranked = res.ranked
    assert [o.rank for o in ranked] == list(range(len(ranked)))
    assert len(res.top(2)) == 2
    assert ranked[0].makespan_s <= ranked[-1].makespan_s
    assert res.best.candidate == res.best_name == ranked[0].name


def test_json_roundtrip(trace, reports_and_rep):
    reports, rep = reports_and_rep
    res = explore(trace, synth_candidates(rep, accs=(1, 2, 3)), reports,
                  prune=True, top_k=2)
    back = ExplorationResult.from_json(res.to_json())
    assert [vars(o) for o in back.outcomes] == [vars(o) for o in res.outcomes]
    assert back.best_name == res.best_name
    assert back.pruned == res.pruned and back.infeasible == res.infeasible
    assert back.cache == res.cache and back.top_k == res.top_k
    # offline re-ranking of a stored sweep works without live estimates
    assert back.speedups() == res.speedups()
    assert back.speedups()[back.best_name] == max(back.speedups().values())
    # second round-trip is the identity
    assert back.to_json() == ExplorationResult.from_json(back.to_json()).to_json()
    with pytest.raises(ValueError):
        ExplorationResult.from_json('{"version": 1}')


def test_json_loads_documents_with_the_dropped_time_share(trace,
                                                         reports_and_rep):
    """Outcomes no longer carry ``analysis_seconds`` (a sweep's wall time
    split evenly over its candidates); a version-2 document written while
    they did still loads, and the key is dropped."""
    reports, rep = reports_and_rep
    res = explore(trace, synth_candidates(rep, accs=(1, 2)), reports)
    doc = json.loads(res.to_json())
    assert all("analysis_seconds" not in o for o in doc["outcomes"])
    for o in doc["outcomes"]:
        o["analysis_seconds"] = 0.0125
    back = ExplorationResult.from_json(json.dumps(doc))
    assert [vars(o) for o in back.outcomes] == [vars(o) for o in res.outcomes]
    assert back.to_json() == res.to_json()


def test_json_roundtrip_ppa_fields(trace, reports_and_rep):
    """The PPA additions (objectives/budgets on the result, per-outcome
    objective values + component breakdowns, the derived frontier) and
    the existing ``failed`` list all survive to_json/from_json — the
    document the CLI prints and sweepd returns is a faithful store."""
    import dataclasses as dc
    reports, rep = reports_and_rep
    res = explore(trace, synth_candidates(rep, accs=(1, 2, 3)), reports,
                  top_k=2, objectives=["area_mm2", "energy_j"],
                  budgets={"power_w": 5.0})
    assert res.objectives == ["makespan_s", "area_mm2", "power_w",
                              "energy_j"]
    assert res.budgets == {"power_w": 5.0}
    # synthesize a quarantined candidate so the failed list is non-empty
    res.outcomes[-1] = dc.replace(res.outcomes[-1], status="failed",
                                  error="RuntimeError('boom')", rank=None)
    back = ExplorationResult.from_json(res.to_json())
    assert back.objectives == res.objectives
    assert back.budgets == res.budgets
    assert [vars(o) for o in back.outcomes] == \
        [vars(o) for o in res.outcomes]
    assert [o.name for o in back.frontier] == [o.name for o in res.frontier]
    assert back.dominated_count == res.dominated_count
    for o in back.ranked:
        assert set(o.objectives) == {"makespan_s", "area_mm2", "power_w",
                                     "energy_j"}
        assert set(o.ppa["components"]) >= {"base"}
    assert [(o.name, o.error) for o in back.failed] == \
        [(o.name, o.error) for o in res.failed] and back.failed
    assert back.to_json() == \
        ExplorationResult.from_json(back.to_json()).to_json()
    # scalar sweeps keep the pre-PPA document shape: no phantom keys
    scalar = explore(trace, synth_candidates(rep, accs=(1,)), reports)
    doc = json.loads(scalar.to_json())
    assert "objectives" not in doc and "budgets" not in doc


def test_seed_explore_api_surface(trace, reports_and_rep):
    """The seed call shape keeps working: positional args, .table of
    PerfEstimate, .infeasible, .best, .wall_seconds, .speedups()."""
    reports, rep = reports_and_rep
    res = explore(trace, synth_candidates(rep), reports, "availability", 1.0)
    assert res.best is not None and res.best.makespan_s > 0
    assert {e.candidate for e in res.table} == \
        {"1acc", "2acc", "1acc+smp", "2acc+smp"}
    assert res.wall_seconds > 0 and res.infeasible == []
    sp = res.speedups()
    assert sp[res.best.candidate] == max(sp.values())
    lines = res.report_lines()
    assert any("cache:" in ln for ln in lines)


# ---------------------------------------------------------------------------
# property-based regression tests for the invariants the engine relies on
# ---------------------------------------------------------------------------


@st.composite
def random_trace(draw):
    n = draw(st.integers(4, 24))
    n_regions = draw(st.integers(1, 5))
    events = [TraceEvent(index=i, name="k", created_at=i * 1e-6,
                         elapsed_smp=draw(st.floats(1e-4, 5e-3)),
                         accesses=[((i % n_regions,), "inout", 512)],
                         devices=("fpga", "smp"))
              for i in range(n)]
    return Trace(events=events, wall_seconds=1.0)


@hypothesis.given(random_trace(), st.integers(1, 3), st.booleans())
@hypothesis.settings(deadline=None, max_examples=25)
def test_makespan_at_least_lower_bound(tr, n_acc, smp):
    """The pruning cut is only safe if the bound never exceeds the
    simulated makespan — including when conditional DMA tasks collapse."""
    reports, rep = synth_reports()
    kinds = ("fpga:k", "smp") if smp else ("fpga:k",)
    cand = Candidate(name="c", system=zynq_system("c", {"fpga:k": n_acc}),
                     eligibility=Eligibility({"k": kinds}),
                     fabric=[(rep, n_acc)])
    graph = build_graph(tr, cand.system, reports, cand.eligibility,
                        smp_cost="mean")
    lb = lower_bound_seconds(graph)
    for policy in ("availability", "eft"):
        sim = simulate(graph, cand.system, policy=policy)
        assert sim.makespan >= lb - 1e-12


@hypothesis.given(random_trace(), st.integers(2, 6))
@hypothesis.settings(deadline=None, max_examples=15)
def test_explore_deterministic_across_worker_counts(tr, workers):
    reports, rep = synth_reports()
    cands = synth_candidates(rep, accs=(1, 2))
    a = explore(tr, cands, reports, max_workers=1)
    b = explore(tr, cands, reports, max_workers=workers)
    assert [(o.name, o.makespan_s, o.rank) for o in a.ranked] == \
        [(o.name, o.makespan_s, o.rank) for o in b.ranked]


@hypothesis.given(st.lists(st.floats(1e-4, 5e-3), min_size=1, max_size=24),
                  st.integers(1, 3))
@hypothesis.settings(deadline=None, max_examples=25)
def test_more_accelerator_slots_never_hurt_independent_tasks(costs, slots):
    """Monotonicity in accelerator count, for independent accelerator-only
    tasks (for dependent graphs any list scheduler has Graham anomalies —
    the estimator models them, it does not hide them)."""
    from repro.core.devices import DevicePool, SystemConfig

    def run(n):
        g = TaskGraph()
        for i, c in enumerate(costs):
            g.add_task(Task(uid=g.new_uid(), name=f"t{i}",
                            devices=("fpga:k",), costs={"fpga:k": c},
                            creation_index=i), infer_deps=False)
        sys_n = SystemConfig(name=f"{n}acc",
                             pools=[DevicePool("acc", ("fpga:k",), n)])
        return simulate(g, sys_n).makespan

    assert run(slots + 1) <= run(slots) + 1e-12


def test_adding_accelerator_slot_helps_synthetic_codesign(trace,
                                                          reports_and_rep):
    """End-to-end flavour of the same invariant: on the synthetic trace the
    2-slot candidate must beat the 1-slot candidate (hand-checked; this is
    the paper's 'more accels help — until the SMP grabs work' story)."""
    reports, rep = reports_and_rep
    res = explore(trace, synth_candidates(rep, smp_opts=(False,)), reports)
    times = {o.name: o.makespan_s for o in res.ranked}
    assert times["2acc"] < times["1acc"]
