"""Failure-matrix tests for the fault-tolerant sweep machinery.

Every failure mode docs/architecture.md's "Failure model" section claims
to handle is driven here through the deterministic injector
(:mod:`repro.testing.faults`): worker crashes and poisoned candidates,
stuck/slow chunks and deadlines, engine degradation down the fallback
chain, disk-cache corruption and quarantine, and the CLI's one-line
operational error contract.
"""
import json
import os
import pickle
import subprocess
import sys
import time

import pytest

from repro.core.diskcache import DiskCache
from repro.core.explore import CacheStats, ExplorationResult, Explorer
from repro.core.replay import ENGINE_FALLBACK
from repro.testing import faults
from repro.testing.synth import synth_reports, synth_trace, synth_candidates


def ranking(res):
    return [(o.name, o.makespan_s) for o in res.ranked]


@pytest.fixture()
def world():
    trace = synth_trace(24)
    reports = synth_reports()
    return trace, reports


def baseline_ranking(world, accs):
    """The fault-free batch ranking for the same candidate ramp (computed
    with no plan active, so it never consumes an injection)."""
    trace, reports = world
    ex = Explorer(trace, reports, engine="batch")
    return ranking(ex.explore(synth_candidates(accs)))


# ---------------------------------------------------------------------------
# the injector itself
# ---------------------------------------------------------------------------


def test_spec_parse_errors():
    with pytest.raises(ValueError, match="want site:occ"):
        faults.FaultInjector("kill_worker")
    with pytest.raises(ValueError, match="unknown fault site"):
        faults.FaultInjector("eat_homework:1")
    with pytest.raises(ValueError, match="occurrence"):
        faults.FaultInjector("kill_worker:0")


def test_fire_is_deterministic_and_one_shot(tmp_path):
    inj = faults.FaultInjector("kill_worker:3", state_dir=str(tmp_path))
    assert [bool(inj.fire("kill_worker")) for _ in range(5)] == \
        [False, False, True, False, False]
    assert inj.fired("kill_worker") == 1
    # a second process sharing the state dir AND the run token (a pool
    # worker) can never claim it again
    inj2 = faults.FaultInjector("kill_worker:3", state_dir=str(tmp_path),
                                run_token=inj.run_token)
    assert [bool(inj2.fire("kill_worker")) for _ in range(5)] == [False] * 5
    assert inj2.fired("kill_worker") == 1


def test_fresh_activation_sweeps_stale_markers(tmp_path):
    # run 1 claims its rule in a shared, reused state dir...
    inj = faults.FaultInjector("kill_worker:1", state_dir=str(tmp_path))
    assert bool(inj.fire("kill_worker"))
    assert inj.fired("kill_worker") == 1
    legacy = tmp_path / "kill_worker.0.fired"      # pre-token marker name
    legacy.write_bytes(b"")
    # ...run 2 (a fresh install, new token) must not be shadowed by run
    # 1's markers: they are swept and the rule fires again
    inj2 = faults.FaultInjector("kill_worker:1", state_dir=str(tmp_path))
    assert not legacy.exists()
    assert inj2.fired("kill_worker") == 0
    assert bool(inj2.fire("kill_worker"))
    assert inj2.fired("kill_worker") == 1
    # workers inheriting the token never sweep their parent's claims
    worker = faults.FaultInjector("kill_worker:1", state_dir=str(tmp_path),
                                  run_token=inj2.run_token)
    assert worker.fired("kill_worker") == 1
    assert [bool(worker.fire("kill_worker"))] == [False]


def test_star_rules_fire_every_time_and_match_filters():
    inj = faults.FaultInjector("kill_candidate:*:3acc")
    assert inj.fire("kill_candidate", "1acc") is None
    assert inj.fire("kill_candidate", "3acc") == "3acc"
    assert inj.fire("kill_candidate", "3acc+smp") == "3acc"
    assert inj.fire("kill_candidate", "3acc") == "3acc"


def test_install_restores_previous_plan_and_env():
    os.environ.pop(faults.ENV_SPEC, None)
    with faults.install("delay_chunk:1:0.01") as inj:
        assert faults.active() is inj
        assert os.environ[faults.ENV_SPEC] == "delay_chunk:1:0.01"
        assert os.environ[faults.ENV_TOKEN] == inj.run_token
        assert faults.token() == \
            f"{inj.spec}@{inj.state_dir}@{inj.run_token}"
        assert faults.current() == (inj.spec, inj.state_dir, inj.run_token)
    assert faults.active() is None
    assert faults.ENV_SPEC not in os.environ
    assert faults.ENV_TOKEN not in os.environ
    assert not os.path.isdir(inj.state_dir)


def test_sleep_if_injected_returns_delay():
    with faults.install("delay_chunk:1:0.02"):
        t0 = time.perf_counter()
        assert faults.sleep_if_injected("delay_chunk") == 0.02
        assert time.perf_counter() - t0 >= 0.02
        assert faults.sleep_if_injected("delay_chunk") == 0.0


# ---------------------------------------------------------------------------
# worker-crash recovery (process pool)
# ---------------------------------------------------------------------------


def test_worker_crash_recovers_bit_identical(world):
    trace, reports = world
    cands = synth_candidates(range(1, 4))
    clean = baseline_ranking(world, range(1, 4))
    with faults.install("kill_worker:1"):
        ex = Explorer(trace, reports, engine="batch", processes=2)
        res = ex.explore(cands)
    assert ranking(res) == clean
    assert not res.failed
    assert ex.stats.pool_respawns >= 1
    assert ex.stats.worker_retries >= 1
    assert ex.stats.quarantined == 0


def test_poisoned_candidate_quarantined_others_survive(world):
    trace, reports = world
    cands = synth_candidates(range(1, 4))
    clean = baseline_ranking(world, range(1, 4))
    # "*" = fires on EVERY worker that ever touches 2acc+smp: the chunk
    # retries, exhausts max_retries, and in-parent isolation quarantines
    # exactly the poisoned candidate — innocents keep exact results
    with faults.install("kill_candidate:*:2acc+smp"):
        ex = Explorer(trace, reports, engine="batch", processes=2,
                      max_retries=1)
        res = ex.explore(cands)
    assert [o.name for o in res.failed] == ["2acc+smp"]
    assert "2acc+smp" in res.failed[0].error
    assert ranking(res) == [r for r in clean if r[0] != "2acc+smp"]
    assert ex.stats.quarantined == 1
    assert ex.stats.pool_respawns >= 1


def test_in_worker_exception_demotes_and_recovers(world):
    trace, reports = world
    # >= MIN_LOCKSTEP lanes per eligibility family, else the small-group
    # path sidesteps the lockstep engine and the fault never fires
    cands = synth_candidates(range(1, 8))
    clean = baseline_ranking(world, range(1, 8))
    with faults.install("fail_lockstep:1"):
        ex = Explorer(trace, reports, engine="batch", processes=2)
        with pytest.warns(UserWarning, match="degraded to 'fast'"):
            res = ex.explore(cands)
    assert ranking(res) == clean
    assert not res.failed
    assert ex.engine == "fast" and ex.stats.engine_demotions == 1


# ---------------------------------------------------------------------------
# deadlines: per-candidate timeouts and the sweep deadline
# ---------------------------------------------------------------------------


def test_timed_out_chunk_retries_serially(world):
    trace, reports = world
    cands = synth_candidates(range(1, 4))
    clean = baseline_ranking(world, range(1, 4))
    # one worker chunk stalls for 2s; its unit budget is 0.3s x chunk
    # width, so the future times out and every candidate of the chunk is
    # re-run in-parent (where the one-shot delay has already been claimed;
    # the timeout leaves a wide margin so the serial retries never trip
    # the post-hoc elapsed check on a loaded machine)
    with faults.install("delay_chunk:1:2.0"):
        ex = Explorer(trace, reports, engine="batch", processes=2,
                      candidate_timeout=0.3)
        res = ex.explore(cands)
    assert ranking(res) == clean
    assert not res.failed
    assert ex.stats.chunk_timeouts >= 1


def test_always_slow_candidates_quarantined(world):
    trace, reports = world
    cands = synth_candidates(range(1, 2))          # 2 candidates, 2 graphs
    # "*": the delay fires in the worker AND again during the serial
    # retry, so the post-hoc elapsed check quarantines every candidate.
    # The second chunk finishes in its worker while the parent retries the
    # first: it arrives done but over budget, and still counts as timed out
    with faults.install("delay_chunk:*:0.3"):
        ex = Explorer(trace, reports, engine="batch", processes=2,
                      candidate_timeout=0.05)
        res = ex.explore(cands)
    assert sorted(o.name for o in res.failed) == ["1acc", "1acc+smp"]
    assert not res.ranked
    assert ex.stats.chunk_timeouts == 2
    assert ex.stats.quarantined == 2


def test_sweep_deadline_quarantines_remainder(world):
    trace, reports = world
    cands = synth_candidates(range(1, 4))
    ex = Explorer(trace, reports, engine="batch", sweep_deadline=1e-4)
    res = ex.explore(cands)
    assert len(res.failed) == len(cands)
    assert all("deadline" in o.error for o in res.failed)
    # the deadline is per explore() call: a fresh call gets a fresh budget
    ex.sweep_deadline = None
    assert ranking(ex.explore(cands))


def test_deadline_on_serial_per_candidate_path(world):
    trace, reports = world
    cands = synth_candidates(range(1, 3))
    ex = Explorer(trace, reports, engine="fast", sweep_deadline=1e-9)
    res = ex.explore(cands)
    assert len(res.failed) == len(cands)


def test_timeout_validation():
    trace, reports = synth_trace(4), synth_reports()
    with pytest.raises(ValueError, match="candidate_timeout"):
        Explorer(trace, reports, candidate_timeout=0)
    with pytest.raises(ValueError, match="sweep_deadline"):
        Explorer(trace, reports, sweep_deadline=-1)
    with pytest.raises(ValueError, match="max_retries"):
        Explorer(trace, reports, max_retries=-1)


# ---------------------------------------------------------------------------
# engine degradation down the fallback chain
# ---------------------------------------------------------------------------


def test_fallback_chain_is_declared_and_terminates():
    assert ENGINE_FALLBACK == {"jax": "batch", "batch": "fast",
                               "fast": "reference", "reference": None}


def test_lockstep_fault_demotes_batch_to_fast(world):
    trace, reports = world
    cands = synth_candidates(range(1, 8))      # >= MIN_LOCKSTEP lanes/family
    clean = baseline_ranking(world, range(1, 8))
    with faults.install("fail_lockstep:1"):
        ex = Explorer(trace, reports, engine="batch")
        with pytest.warns(UserWarning, match="degraded to 'fast'"):
            res = ex.explore(cands)
    assert ranking(res) == clean
    assert ex.engine == "fast" and ex.stats.engine_demotions == 1


def test_fast_fault_demotes_to_reference(world, monkeypatch):
    # repro.core re-exports the explore() function, shadowing the submodule
    # attribute -- resolve the module object itself
    explore_mod = sys.modules["repro.core.explore"]
    trace, reports = world
    cands = synth_candidates(range(1, 8))      # >= MIN_LOCKSTEP lanes/family
    clean = baseline_ranking(world, range(1, 8))

    def broken_fast(*a, **kw):
        raise RuntimeError("pallas kernel went sideways")

    monkeypatch.setattr(explore_mod, "simulate_fast", broken_fast)
    # batch faults on every call -> fast -> fast faults too -> reference
    with faults.install("fail_lockstep:*"):
        ex = Explorer(trace, reports, engine="batch")
        with pytest.warns(UserWarning):
            res = ex.explore(cands)
    assert ex.engine == "reference" and ex.stats.engine_demotions == 2
    assert not res.failed
    # reference results are exact: the demoted sweep ranks identically
    assert ranking(res) == clean


@pytest.mark.parametrize("error", [ImportError, AttributeError])
def test_broken_jax_raises_and_never_demotes(world, monkeypatch, error):
    # jax is a declared dependency: a failed import or a removed API is a
    # bug, so it raises -- at construction, and also when it first shows
    # mid-sweep -- instead of demoting to batch behind exit status 0
    from repro.core import jaxsim
    trace, reports = world
    ex = Explorer(trace, reports, engine="jax")

    def broken():
        raise error("jax lost an API the engine calls")

    monkeypatch.setattr(jaxsim, "_jax", broken)
    with pytest.raises(error):
        Explorer(trace, reports, engine="jax")
    with pytest.raises(error):
        ex.explore(synth_candidates(range(1, 8)))   # >= MIN_LOCKSTEP lanes
    assert ex.engine == "jax" and ex.stats.engine_demotions == 0


def test_compile_fault_demotes_jax_to_batch(world):
    trace, reports = world
    cands = synth_candidates(range(1, 8))      # >= MIN_LOCKSTEP lanes/family
    clean = baseline_ranking(world, range(1, 8))
    with faults.install("fail_compile:1"):
        ex = Explorer(trace, reports, engine="jax")
        with pytest.warns(UserWarning, match="degraded to 'batch'"):
            res = ex.explore(cands)
    assert ex.engine == "batch" and ex.stats.engine_demotions == 1
    # the demoted tiers are exact: bit-identical to the clean batch sweep
    assert ranking(res) == clean


# ---------------------------------------------------------------------------
# disk-cache corruption: quarantine + crash-atomic writes
# ---------------------------------------------------------------------------


def test_corrupt_entry_quarantined_once(tmp_path):
    dc = DiskCache(str(tmp_path))
    with faults.install("corrupt_cache:1"):
        dc.put("key-a", {"v": 1})          # lands corrupted on disk
    assert dc.get("key-a") is None
    assert dc.quarantined == 1
    qdir = os.path.join(str(tmp_path), "quarantine")
    assert len(os.listdir(qdir)) == 1
    assert dc.get("key-a") is None         # no re-read, no double count
    assert dc.quarantined == 1
    dc.put("key-a", {"v": 2})              # next put recreates cleanly
    assert dc.get("key-a") == {"v": 2}
    assert dc.quarantined == 1


def test_quarantine_dir_never_served_as_entry(tmp_path):
    dc = DiskCache(str(tmp_path))
    with faults.install("corrupt_cache:1"):
        dc.put("key-a", 1)
    dc.get("key-a")
    dc.put("key-b", 2)
    assert all(name.endswith(".pkl") for name in dc.entries())
    assert len(list(dc.entries())) == 1    # the quarantine dir is skipped
    dc2 = DiskCache(str(tmp_path))         # reopening survives quarantine/
    assert dc2.get("key-b") == 2


def test_truncated_and_bitrotted_entries_quarantine(tmp_path):
    dc = DiskCache(str(tmp_path))
    dc.put("k", list(range(50)))
    path = dc._path("k")
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[: len(blob) // 2])      # torn write
    assert dc.get("k") is None and dc.quarantined == 1
    dc.put("k", "fresh")
    flipped = bytearray(open(path, "rb").read())
    flipped[-1] ^= 0xFF
    open(path, "wb").write(bytes(flipped))              # bit rot
    assert dc.get("k") is None and dc.quarantined == 2


def test_tmp_orphans_swept_by_age(tmp_path):
    old = tmp_path / "dead-writer.tmp"
    young = tmp_path / "live-writer.tmp"
    old.write_bytes(b"x")
    young.write_bytes(b"y")
    past = time.time() - 7200
    os.utime(old, (past, past))
    DiskCache(str(tmp_path))
    assert not old.exists()
    assert young.exists()                  # may belong to a live writer


def test_explorer_folds_cache_quarantine(world, tmp_path):
    trace, reports = world
    cands = synth_candidates(range(1, 3))
    ex1 = Explorer(trace, reports, engine="batch", cache_dir=str(tmp_path))
    r1 = ex1.explore(cands)
    entries = [f for f in os.listdir(str(tmp_path)) if f.endswith(".pkl")]
    assert entries
    for name in entries:                   # rot every stored entry
        p = os.path.join(str(tmp_path), name)
        open(p, "wb").write(b"garbage" * 10)
    ex2 = Explorer(trace, reports, engine="batch", cache_dir=str(tmp_path))
    r2 = ex2.explore(cands)
    assert ranking(r2) == ranking(r1)      # recomputed, never wrong
    assert ex2.stats.cache_quarantined >= 1
    assert r2.cache["cache_quarantined"] >= 1


# ---------------------------------------------------------------------------
# telemetry surfaces
# ---------------------------------------------------------------------------


def test_cachestats_repr_hides_clean_fault_counters():
    s = CacheStats()
    assert "faults" not in repr(s)
    s.quarantined = 2
    s.engine_demotions = 1
    assert "faults 0rt/0rs/0to/2q/1d/0cq" in repr(s)


def test_failed_outcomes_in_report_and_json(world):
    trace, reports = world
    ex = Explorer(trace, reports, engine="batch", sweep_deadline=1e-4)
    res = ex.explore(synth_candidates(range(1, 2)))
    lines = "\n".join(res.report_lines())
    assert "quarantined:" in lines
    assert "faults:" in lines
    back = ExplorationResult.from_json(res.to_json())
    assert [(o.name, o.error) for o in back.failed] == \
        [(o.name, o.error) for o in res.failed]


# ---------------------------------------------------------------------------
# CLI: one-line operational errors, chaos-run counters
# ---------------------------------------------------------------------------


def _run_cli(args, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop(faults.ENV_SPEC, None)
    env.pop(faults.ENV_STATE, None)
    env.pop(faults.ENV_TOKEN, None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "repro.explore", *args],
        capture_output=True, text=True, env=env, timeout=180)


def test_cli_missing_trace_is_one_line_error(tmp_path):
    p = _run_cli([str(tmp_path / "nope.jsonl"), "--reports",
                  str(tmp_path / "nope.json")])
    assert p.returncode == 2
    assert p.stderr.startswith("error:")
    assert "Traceback" not in p.stderr


def test_cli_corrupt_trace_is_one_line_error(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text("this is { not json\n")
    rep = tmp_path / "reports.json"
    rep.write_text(json.dumps([{
        "kernel": "k", "device_kind": "fpga:k", "compute_s": 1e-4,
        "dma_in_s": 1e-5, "dma_out_s": 2e-5, "resources": {"dsp": 1.0}}]))
    p = _run_cli([str(bad), "--reports", str(rep)])
    assert p.returncode == 2
    assert p.stderr.startswith("error:")
    assert "Traceback" not in p.stderr


def test_cli_unknown_engine_rejected():
    p = _run_cli(["synth:8", "--engine", "warp"])
    assert p.returncode == 2
    assert "invalid choice" in p.stderr
    assert "Traceback" not in p.stderr


def test_cli_chaos_run_reports_fault_counters(tmp_path):
    state = tmp_path / "fault-state"
    state.mkdir()
    p = _run_cli(["synth:24", "--accs", "1-3", "--processes", "2",
                  "--candidate-timeout", "30"],
                 env_extra={faults.ENV_SPEC: "kill_worker:1",
                            faults.ENV_STATE: str(state)})
    assert p.returncode == 0, p.stderr
    doc = json.loads(p.stdout)
    assert doc["best"] is not None
    assert doc["failed"] == []
    assert doc["faults"]["pool_respawns"] >= 1
    assert doc["engine_final"] == "batch"


def test_cli_quarantine_summary_on_stderr(tmp_path):
    state = tmp_path / "fault-state"
    state.mkdir()
    p = _run_cli(["synth:24", "--accs", "1-3", "--processes", "2",
                  "--max-retries", "0"],
                 env_extra={faults.ENV_SPEC: "kill_candidate:*:2acc+smp",
                            faults.ENV_STATE: str(state)})
    assert p.returncode == 0, p.stderr
    doc = json.loads(p.stdout)
    assert [f["name"] for f in doc["failed"]] == ["2acc+smp"]
    assert "quarantined 1 candidate(s):" in p.stderr
