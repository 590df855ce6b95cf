"""The comparison that decides ``correct``, at test sizes on the CPU.

A sound run reads 0; the control (the reference computed in binary32, put
in the program's place) and an answer altered where the program produces
it both come out not correct.
"""
import json

import numpy as np
import pytest

import run
from harness import check, program, reference, spec
from harness.traffic import Traffic

CELLS = ("chol768-dgemm8.sweep", "chol512-fig9.sweep")


def drive(bench, cell, capsys, seed=7, seconds=0.2):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0"], require_device=False,
                  bench=bench)
    out, err = capsys.readouterr()
    assert rc == 0, err
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small_bench, cell, capsys):
    result, err = drive(small_bench, cell, capsys)
    assert result["correct"] is True
    assert list(result)[-1] == "check"
    assert result["check"] == {"max_gap": {"value": 0.0, "limit": 0.0}}
    assert err.strip().splitlines()[-1] == "max_gap 0.0 limit 0.0"
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"cand_per_s", "setup_s"}


def test_off_tpu_the_run_refuses(small_bench, capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"], bench=small_bench)
    out, err = capsys.readouterr()
    assert rc == 2 and out == "" and "not a TPU" in err


def _bump(sim):
    sim.makespan = float(np.nextafter(sim.makespan, np.inf))
    return sim


def test_scan_answer_altered_is_caught(small_bench, capsys, monkeypatch):
    """Every lane that the device scan completes comes back one ulp late."""
    import repro.core.jaxsim as jaxsim
    orig = jaxsim.lane_results

    def altered(*a, **kw):
        return {pos: _bump(sim) for pos, sim in orig(*a, **kw).items()}

    monkeypatch.setattr(jaxsim, "lane_results", altered)
    result, err = drive(small_bench, CELLS[0], capsys)
    assert result["correct"] is False
    assert result["check"]["max_gap"]["value"] > 0.0
    assert "largest gap: none" not in err


def test_fallback_answer_altered_is_caught(small_bench, capsys,
                                           monkeypatch):
    """Every lane that the host's exact path completes comes back one ulp
    late."""
    import repro.core.replay as replay
    orig = replay.simulate_fast

    def altered(*a, **kw):
        return _bump(orig(*a, **kw))

    monkeypatch.setattr(replay, "simulate_fast", altered)
    result, _ = drive(small_bench, CELLS[1], capsys)
    assert result["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_binary32_control_fails(small_bench, cell):
    w = spec.workload(small_bench, cell)
    problem = spec.problem(spec.config(small_bench, w["config"]))
    gaps = check.control(problem, Traffic(spec.traffic(w["traffic"]), 11), 2)
    assert not check.correct(gaps)
    assert gaps.worst() > 0.0


def test_reference_matches_the_program_reference_engine(small_bench):
    """The independent reference agrees bit for bit with the program's own
    object engine on every candidate of a test-sized grid."""
    from repro.core.explore import Explorer
    w = spec.workload(small_bench, CELLS[1])
    problem = spec.problem(spec.config(small_bench, w["config"]))
    reps = problem.scaled_reports(1.37, ("compute_s", "dma_in_s",
                                         "dma_out_s"))
    inp = program.inputs(problem)
    res = Explorer(inp.trace, program.reports_map(reps),
                   smp_seconds_fn=inp.smp_seconds_fn, budget=problem.budget,
                   engine="reference").explore(inp.cands)
    ref = reference.Reference(problem, reps)
    n = 0
    for c in problem.cands:
        if not problem.feasible(c):
            continue
        r = ref.run(c)
        sim = res.estimates[c.name].sim
        assert (r.makespan, r.busy) == (sim.makespan, sim.busy), c.name
        assert r.placements == sim.placements, c.name
        n += 1
    assert n > 10


def test_sample_is_drawn_from_the_seed():
    ranked = [f"c{i}" for i in range(40)]
    rule = {"top": 2, "cover": 4, "random": 6}
    a = check.sample(5, 7, ranked, ranked, rule)
    assert a == check.sample(5, 7, ranked, ranked, rule)
    assert a[:2] == ["c0", "c1"] and len(set(a)) == len(a) <= 8
    assert a != check.sample(6, 7, ranked, ranked, rule)


def test_first_sweeps_cover_every_candidate():
    ranked = [f"c{i}" for i in range(41)]
    rule = {"top": 2, "cover": 4, "random": 6}
    shares = [check.sample(9, i, ranked, ranked, rule)[2:]
              for i in range(4)]
    seen = [n for s in shares for n in s]
    assert len(set(seen)) == len(seen) >= 41 - 2 * 4
    assert set(seen) | {"c0", "c1"} == set(ranked)


def test_whole_ranking_out_of_order_is_caught(small_bench, capsys,
                                              monkeypatch):
    """The sweep's ranking swaps two candidates that the sample need not
    read: the makespans are right, the order is not."""
    orig = program.sweep

    def swapped(*a, **kw):
        sw = orig(*a, **kw)
        sw.ranked[-2], sw.ranked[-1] = sw.ranked[-1], sw.ranked[-2]
        return sw

    monkeypatch.setattr(program, "sweep", swapped)
    result, err = drive(small_bench, CELLS[0], capsys)
    assert result["correct"] is False
    assert "(whole)" in err
