"""The multi-graph megabatch and the compile cache.

Two mechanisms set the jax engine's cold-start economics: one compiled
heap scan serving every graph family of a sweep (``simulate_jax_many`` /
``replay.simulate_many``, the engine's only path), and a persistent XLA
compile cache (``xlacache.CompileCache``, DiskCache ``xla`` namespace).
This file pins their contracts — megabatch results equal ``simulate_fast``
bit for bit, launch shapes that drift inside their buckets compile once,
and a warm store serves a fresh process with zero compiles — plus two
satellite bugfixes: ``_bucket`` can never exceed its cap, and process
pools never fork a jax-loaded parent.
"""
import collections
import json
import pickle
import subprocess
import sys

import hypothesis
import hypothesis.strategies as st
import numpy as np
import pytest

from repro.core import Explorer, zynq_system
from repro.core.batchsim import simulate_batch
from repro.core.devices import DevicePool, SharedResource, SystemConfig
from repro.core.diskcache import DiskCache
from repro.core.explore import _pool_mp_context
from repro.core.fastsim import FrozenGraph, simulate_fast
from repro.core.jaxsim import _bucket, simulate_jax_many
from repro.core.replay import (BatchStats, JAX_RTOL, ReplayLibrary,
                               rankings_equivalent, sims_equivalent)
from repro.core.taskgraph import Task, TaskGraph
from repro.core.xlacache import CompileCache
from repro.testing.synth import (frozen_for, synth_candidates, synth_report,
                                 synth_reports, synth_trace)


# ---------------------------------------------------------------------------
# _bucket: the chunk-cap bugfix (pure, no jax needed)
# ---------------------------------------------------------------------------


@hypothesis.given(st.integers(1, 5000), st.integers(1, 4096))
@hypothesis.settings(deadline=None, max_examples=200)
def test_bucket_respects_cap_and_stays_power_of_two(n, cap):
    """The documented contract: a power of two, never above the cap, and
    wide enough for ``n`` whenever the rounded-down cap allows it."""
    b = _bucket(n, cap)
    assert 1 <= b <= cap
    assert b & (b - 1) == 0, f"_bucket({n}, {cap}) = {b} not a power of two"
    cap_p = 1
    while cap_p * 2 <= cap:
        cap_p *= 2
    assert b <= cap_p, "caps round DOWN to a power of two"
    if n <= cap_p:
        assert b >= n, f"_bucket({n}, {cap}) = {b} cannot hold {n} lanes"


def test_bucket_non_power_of_two_cap_regression():
    """The ISSUE-6 shape: a user cap of 48 must never compile wider than
    48 (and never a non-power-of-two width like 48 itself)."""
    assert _bucket(40, 48) == 32
    for n in range(1, 200):
        b = _bucket(n, 48)
        assert b <= 48 and b & (b - 1) == 0


def test_non_power_of_two_chunk_is_invariant():
    """A non-power-of-two ``chunk`` is a cap, not a width: results are
    identical to any other chunking (the cap rounds down internally)."""
    fg, _ = frozen_for(synth_trace(20), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 13)]
    (base,) = simulate_jax_many([(fg, systems)], "availability",
                                min_lockstep=2)
    for chunk in (5, 48, 100):
        (got,) = simulate_jax_many([(fg, systems)], "availability",
                                   min_lockstep=2, chunk=chunk)
        assert [s.makespan for s in got] == [s.makespan for s in base]
        assert [s.placements for s in got] == [s.placements for s in base]


# ---------------------------------------------------------------------------
# megabatch vs the exact engine: randomized equivalence
# ---------------------------------------------------------------------------


def _two_pool_dag(n):
    """A bare DAG over two device kinds — pool shapes a synth trace never
    produces (no smp, no DMA, heterogeneous pools)."""
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
    return FrozenGraph.freeze(g)


def _mixed_families(seed):
    """Heterogeneous (graph, systems) families: different task counts,
    ±smp (conditional DMA on and off), different pool templates and slot
    counts — everything the task-axis padding has to absorb."""
    fg1, _ = frozen_for(synth_trace(8 + seed % 13), smp=True)
    fg2, _ = frozen_for(synth_trace(6 + (seed // 3) % 17), smp=False)
    fg3 = _two_pool_dag(5 + seed % 7)
    return [
        (fg1, [zynq_system(f"a{i}", {"fpga:k": 1 + (i + seed) % 4})
               for i in range(9)]),
        (fg2, [zynq_system(f"b{i}", {"fpga:k": 1 + i % 3})
               for i in range(8)]),
        (fg3, [SystemConfig(name=f"c{i}-{j}",
                            pools=[DevicePool("pa", ("a",), i),
                                   DevicePool("pb", ("b",), j)],
                            shared=[SharedResource("x", 1)])
               for i in range(1, 3) for j in range(1, 4)]),
    ]


@hypothesis.given(st.integers(0, 10 ** 6),
                  st.sampled_from(["availability", "eft"]))
@hypothesis.settings(deadline=None, max_examples=4)
def test_megabatch_matches_per_graph_tier(seed, policy):
    """One megabatch call over heterogeneous families equals
    ``simulate_fast`` bit for bit (tolerance 0, inside the jax tier)
    across policies, conditional DMA on/off, and heterogeneous pool
    templates/slot counts."""
    items = _mixed_families(seed)
    res = simulate_jax_many(items, policy, min_lockstep=2)
    for (fg, systems), sims in zip(items, res):
        assert len(sims) == len(systems)
        for system, sim in zip(systems, sims):
            ref = simulate_fast(fg, system, policy)
            assert sim.system == system.name and sim.schedule == []
            assert sims_equivalent(sim, ref, 0.0), \
                (policy, system.name, sim.makespan, ref.makespan)
            assert sim.placements == ref.placements


def test_megabatch_divergent_lanes_fall_back_exactly():
    """A slot-count ramp whose lanes diverge from any replayed order (the
    batch engine's replay shows it) completes in the megabatch's heap
    scan, every lane bit-identical to the batch engine, with no discovery
    and no divergence; the per-lane accounting covers every lane exactly
    once."""
    fg1, _ = frozen_for(synth_trace(40), smp=True)
    fg2, _ = frozen_for(synth_trace(24), smp=False)
    items = [(fg1, [zynq_system(f"a{n}", {"fpga:k": n})
                    for n in range(1, 25)]),
             (fg2, [zynq_system(f"b{n}", {"fpga:k": n})
                    for n in range(1, 13)])]
    replayed = BatchStats()
    for fg, systems in items:
        simulate_batch(fg, systems, "availability", min_lockstep=2,
                       stats=replayed)
    assert replayed.diverged_lanes > 0, "ramp should diverge on replay"
    stats = BatchStats()
    res = simulate_jax_many(items, "availability", min_lockstep=2,
                            stats=stats)
    n_lanes = sum(len(systems) for _, systems in items)
    assert stats.heap_lanes == stats.lockstep_lanes == n_lanes
    assert stats.reference_lanes == stats.diverged_lanes == 0
    assert stats.rescued_lanes == stats.order_pinned_lanes == 0
    assert stats.serial_fallback_lanes == stats.small_group_lanes == 0
    assert stats.scan_lane_steps == 0
    for (fg, systems), sims in zip(items, res):
        ref = simulate_batch(fg, systems, "availability", min_lockstep=2)
        for system, sim, want in zip(systems, sims, ref):
            assert sims_equivalent(sim, want, 0.0), system.name


def test_megabatch_warm_library_routes_everything():
    """A library warmed by the batch engine steers no megabatch lane: the
    megabatch sweep on it routes every lane to the heap scan, with no
    order hit and no discovery, leaves the library as it found it, and
    ranks as the batch engine does."""
    reports, rep = synth_reports(), synth_report()
    tr = synth_trace(24)
    cands = synth_candidates(range(1, 7), rep)
    lib = ReplayLibrary()
    Explorer(tr, reports, engine="batch", order_library=lib).explore(cands)
    warmed = lib.counts()
    assert warmed["orders"] > 0
    ex = Explorer(tr, reports, engine="jax", order_library=lib)
    got = ex.explore(cands)
    st = ex.batch_stats
    assert lib.counts() == warmed
    assert st.heap_lanes == st.lockstep_lanes == len(cands)
    assert st.order_hits == st.order_pinned_lanes == 0
    assert st.reference_lanes == st.diverged_lanes == 0
    batch = Explorer(tr, reports, engine="batch").explore(cands)
    assert [(o.name, o.makespan_s) for o in got.ranked] == \
        [(o.name, o.makespan_s) for o in batch.ranked]


def test_megabatch_rejects_bad_arguments():
    fg, _ = frozen_for(synth_trace(6), smp=False)
    items = [(fg, [zynq_system("s", {"fpga:k": 1})])]
    with pytest.raises(ValueError, match="policy"):
        simulate_jax_many(items, "heft")
    with pytest.raises(ValueError, match="chunk"):
        simulate_jax_many(items, "availability", chunk=0)


# ---------------------------------------------------------------------------
# the persistent compile cache
# ---------------------------------------------------------------------------


def test_compile_cache_memory_tier_dedups_repeat_shapes():
    """Same shapes, same signature: the second sweep is a memory hit, not
    a recompile."""
    fg, _ = frozen_for(synth_trace(10), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 9)]
    cc = CompileCache()                                     # mem-only
    simulate_jax_many([(fg, systems)], "availability", min_lockstep=2,
                      compile_cache=cc)
    compiles = cc.as_dict()["compiles"]
    assert compiles >= 1
    simulate_jax_many([(fg, systems)], "availability", min_lockstep=2,
                      compile_cache=cc)
    got = cc.as_dict()
    assert got["compiles"] == compiles, "repeat shapes must not recompile"
    assert got["mem_hits"] >= 1


def _drift_sweeps(axis):
    """Two sweeps whose heap-bound lanes (``axis="lanes"``: 7 then 12) or
    cohorts (``axis="cohorts"``: 1 then 3 graphs) differ, while rows,
    slots, lanes and cohorts stay inside one bucket each (under
    ``HEAP_ROW_ALIGN`` rows, at most 8 slots, at most ``HEAP_MIN_LANES``
    lanes, at most ``HEAP_MIN_COHORTS`` cohorts)."""
    def ramp(tag, counts):
        return [zynq_system(f"{tag}{i}-{n}", {"fpga:k": n})
                for i, n in enumerate(counts)]
    fgs = [frozen_for(synth_trace(n), smp=True)[0] for n in (12, 9, 15)]
    if axis == "lanes":
        return ([(fgs[0], ramp("a", range(1, 8)))],
                [(fgs[0], ramp("b", [1 + i % 8 for i in range(12)]))])
    return ([(fgs[0], ramp("a", range(1, 8)))],
            [(fg, ramp(f"c{g}", range(1, 9))) for g, fg in enumerate(fgs)])


@pytest.mark.parametrize("axis", ["lanes", "cohorts"])
def test_compile_cache_serves_drift_inside_a_bucket(axis):
    """A sweep whose count of heap lanes or cohorts drifts with its costs
    meets the executable its warm-up compiled: one compile for both
    sweeps, and both bit-identical to ``simulate_fast``."""
    cc = CompileCache()                                     # mem-only
    for items in _drift_sweeps(axis):
        stats = BatchStats()
        res = simulate_jax_many(items, "availability", compile_cache=cc,
                                stats=stats)
        assert stats.heap_lanes == sum(len(s) for _, s in items)
        for (fg, systems), sims in zip(items, res):
            for system, sim in zip(systems, sims):
                ref = simulate_fast(fg, system, "availability")
                assert sims_equivalent(sim, ref, 0.0), system.name
    got = cc.as_dict()
    assert got["compiles"] == 1 and got["mem_hits"] >= 1, got


def test_compile_cache_rejects_corrupt_disk_payloads(tmp_path):
    """A garbled disk entry degrades to a fresh compile (counted in
    ``failures`` when deserialization rejects it), never a crash."""
    disk = DiskCache(str(tmp_path))
    cc = CompileCache(disk)
    sig = ("probe", 1)
    disk.put(cc._key_text(sig), ("xla-exec", 1, b"not an executable",
                                 None, None))
    assert cc.get(sig) is None
    assert cc.as_dict()["failures"] == 1
    disk.put(cc._key_text(sig), {"wrong": "shape"})     # wrong wire format
    assert cc.get(sig) is None                          # plain miss


def test_compile_cache_cross_process_warm_start(tmp_path):
    """The headline property: a fresh *process* with a warm store runs the
    sweep with zero XLA compiles — the executable deserializes from the
    DiskCache ``xla`` namespace (disk_hits >= 1)."""
    store = str(tmp_path / "store")
    items = _mixed_families(1)
    cc = CompileCache(DiskCache(store))
    # the heap launch's shapes follow from the sweep's groups alone, so
    # one run compiles everything a fresh process will need
    simulate_jax_many(items, "availability", min_lockstep=2,
                      compile_cache=cc)
    payload = str(tmp_path / "families.pkl")
    with open(payload, "wb") as f:
        pickle.dump(items, f)

    script = """
import json, pickle, sys
from repro.core.diskcache import DiskCache
from repro.core.jaxsim import simulate_jax_many
from repro.core.xlacache import CompileCache

with open(sys.argv[1], "rb") as f:
    items = pickle.load(f)
cc = CompileCache(DiskCache(sys.argv[2]))
res = simulate_jax_many(items, "availability", min_lockstep=2,
                        compile_cache=cc)
print(json.dumps({"cc": cc.as_dict(),
                  "spans": [[s.makespan for s in fam] for fam in res]}))
"""
    out = subprocess.run(
        [sys.executable, "-c", script, payload, store],
        capture_output=True, text=True, timeout=300,
        env=_src_env())
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["cc"]["compiles"] == 0, got["cc"]
    assert got["cc"]["disk_hits"] >= 1, got["cc"]
    for (fg, systems), spans in zip(items, got["spans"]):
        for system, span in zip(systems, spans):
            ref = simulate_fast(fg, system, "availability").makespan
            assert abs(span - ref) <= JAX_RTOL * max(abs(span), abs(ref))


def _src_env():
    """An explicit environment for a child interpreter: the source tree and
    this process's jax backend, plus the few process basics — nothing else
    leaks in (an inherited ``XLA_FLAGS`` that some other module set would
    change the child's device count, and with it every compile key)."""
    import os
    import jax
    import repro
    env = {k: os.environ[k] for k in ("PATH", "HOME", "TMPDIR", "LANG")
           if k in os.environ}
    env["PYTHONPATH"] = os.path.dirname(list(repro.__path__)[0])
    env["JAX_PLATFORMS"] = jax.default_backend()
    return env


# ---------------------------------------------------------------------------
# scan counters (BatchStats.heap_lane_steps / heap_h2d_bytes)
# ---------------------------------------------------------------------------


def _ramp_families():
    """Two families with slot-count ramps, whose lanes diverge from any
    one replayed order."""
    fg1, _ = frozen_for(synth_trace(24), smp=True)
    fg2, _ = frozen_for(synth_trace(14), smp=False)
    return [(fg1, [zynq_system(f"a{n}", {"fpga:k": n})
                   for n in range(1, 13)]),
            (fg2, [zynq_system(f"b{n}", {"fpga:k": n})
                   for n in range(1, 9)])]


def _record_scans(monkeypatch):
    """Record, beside the counters, what the scan really did: the
    ``(rows, lanes)`` of every launch, the bytes of every ``jnp.asarray``
    upload and of each launch's host-made arguments."""
    import jax.numpy as jnp
    from repro.core import jaxsim
    rec = {"uploads": [], "heap_launches": [], "heap_args": []}
    real_put, real_heap = jnp.asarray, jaxsim._load_heap_runner

    def load_heap(cc, args):
        rec["heap_launches"].append((args[0]["npred"].shape[0],
                                     args[1].shape[2]))
        rec["heap_args"].append(sum(np.asarray(a).nbytes for a in args[1:]))
        return real_heap(cc, args)

    def put(a, *rest, **kw):
        rec["uploads"].append(np.asarray(a).nbytes)
        return real_put(a, *rest, **kw)

    monkeypatch.setattr(jaxsim, "_DEV_XS_CACHE",
                        collections.OrderedDict())
    monkeypatch.setattr(jaxsim, "_load_heap_runner", load_heap)
    monkeypatch.setattr(jnp, "asarray", put)
    return rec


def test_scan_counters_match_the_launches_and_uploads(monkeypatch):
    """Nothing writes ``scan_lane_steps``, ``scan_kept_lane_steps`` and
    ``h2d_bytes``, so they stay 0; ``heap_lane_steps`` is Σ B × rows over
    the heap scan's launches and ``heap_h2d_bytes`` the bytes actually
    handed to ``jnp.asarray``.  A warm repeat (the stacked graph tables a
    device-cache hit) uploads only each launch's own arguments, over the
    same launches."""
    items = _ramp_families()
    rec = _record_scans(monkeypatch)
    cold = BatchStats()
    simulate_jax_many(items, "availability", min_lockstep=2, stats=cold)
    assert (cold.scan_lane_steps, cold.scan_kept_lane_steps,
            cold.h2d_bytes) == (0, 0, 0)
    heap_launches = list(rec["heap_launches"])
    assert heap_launches, "every lane must reach the heap scan"
    assert cold.heap_lanes == sum(len(s) for _, s in items)
    assert cold.heap_lane_steps == sum(r * b for r, b in heap_launches)
    assert cold.heap_h2d_bytes == sum(rec["uploads"])
    assert cold.heap_h2d_bytes > sum(rec["heap_args"])

    for k in rec:
        rec[k].clear()
    warm = BatchStats()
    simulate_jax_many(items, "availability", min_lockstep=2, stats=warm)
    assert rec["heap_launches"] == heap_launches
    assert warm.heap_h2d_bytes == sum(rec["heap_args"]) \
        == sum(rec["uploads"])
    assert warm.heap_lane_steps == cold.heap_lane_steps
    assert warm.h2d_bytes == warm.scan_lane_steps == 0


def test_span_needs_no_jax_and_marks_the_trace_with_it():
    """Without jax loaded a span is one shared no-op and imports nothing;
    with jax loaded it is a profiler annotation."""
    script = """
import sys
from repro.core.spans import span
with span("explore.sweep"):
    pass
assert span("a") is span("b") and "jax" not in sys.modules
import jax
assert isinstance(span("a"), jax.profiler.TraceAnnotation)
print("SPANS-OK")
"""
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=120, env=_src_env())
    assert out.returncode == 0, out.stderr
    assert "SPANS-OK" in out.stdout


def test_scan_counters_on_the_per_graph_path_and_the_batch_engine():
    """The numpy batch engine launches no scan and leaves every scan
    counter at 0."""
    fg, systems = _ramp_families()[1]
    stats = BatchStats()
    simulate_batch(fg, systems, "availability", min_lockstep=2, stats=stats)
    assert stats.lockstep_lanes > 0
    assert (stats.scan_lane_steps, stats.scan_kept_lane_steps,
            stats.h2d_bytes, stats.heap_lane_steps,
            stats.heap_h2d_bytes) == (0, 0, 0, 0, 0)


# ---------------------------------------------------------------------------
# explorer integration
# ---------------------------------------------------------------------------


def test_explorer_megabatch_matches_batch_rankings(tmp_path):
    """`engine="jax"` runs the megabatch path; rankings must stay
    equivalent to the exact batch engine under the documented tie-break,
    with the compile cache wired through ``cache_dir`` (and none on the
    batch engine)."""
    reports, rep = synth_reports(), synth_report()
    tr = synth_trace(24)
    cands = synth_candidates(range(1, 7), rep)
    ex = Explorer(tr, reports, engine="jax",
                  cache_dir=str(tmp_path / "store"))
    jaxr = ex.explore(cands)
    assert ex.compile_cache is not None
    assert ex.compile_cache.as_dict()["compiles"] >= 1
    batch = Explorer(tr, reports, engine="batch").explore(cands)
    spans = {o.name: o.makespan_s for o in batch.ranked}
    assert rankings_equivalent([o.name for o in jaxr.ranked],
                               [o.name for o in batch.ranked], spans,
                               JAX_RTOL)
    assert Explorer(tr, reports, engine="batch").compile_cache is None


# ---------------------------------------------------------------------------
# the fork-after-jax pool hazard
# ---------------------------------------------------------------------------


def test_pool_context_avoids_fork_once_jax_loaded(monkeypatch):
    """The start method is decided per acquisition: fork only while jax
    has never been imported, forkserver/spawn after."""
    monkeypatch.setitem(sys.modules, "jax", sys.modules.get("jax") or True)
    assert _pool_mp_context().get_start_method() in ("forkserver", "spawn")
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    monkeypatch.delitem(sys.modules, "jaxlib", raising=False)
    assert _pool_mp_context().get_start_method() == "fork"


def test_process_pool_after_jax_is_runtimewarning_clean(tmp_path):
    """Regression for the ISSUE-6 hazard: a process-pool sweep in a
    jax-loaded parent under ``-W error::RuntimeWarning`` — the exact
    warning the old fork-start pools tripped (`os.fork() was called ...
    JAX is multithreaded`) is now an error, and the sweep must survive it
    with correct results."""
    script = """
import jax                                  # load the threaded runtime FIRST
from repro.core.explore import Explorer
from repro.testing.synth import synth_candidates, synth_report, synth_reports, synth_trace

reports, rep = synth_reports(), synth_report()
ex = Explorer(synth_trace(12), reports, engine="batch", processes=2)
res = ex.explore(synth_candidates(range(1, 5), rep))
assert len(res.ranked) == 8, res.ranked
ref = Explorer(synth_trace(12), reports, engine="fast").explore(
    synth_candidates(range(1, 5), rep))
assert [(o.name, o.makespan_s) for o in res.ranked] == \
    [(o.name, o.makespan_s) for o in ref.ranked]
print("POOL-CLEAN")
"""
    out = subprocess.run(
        [sys.executable, "-W", "error::RuntimeWarning", "-c", script],
        capture_output=True, text=True, timeout=300, env=_src_env())
    assert out.returncode == 0, out.stderr
    assert "POOL-CLEAN" in out.stdout
