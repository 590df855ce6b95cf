"""The matmul configuration, its builder, the reference on it, and the
heap scan's metric reader, at test sizes on the CPU."""
import copy
import json
import os
import types

import pytest

import run
from harness import check, program, reference, spec
from harness.traffic import Traffic

ROOT = spec.ROOT


def test_builder_writes_out_the_paper_size():
    """Fig. 1's loop nest at n=1024, bs=128: 8^3 = 512 block updates, each
    reading A[i][k] and B[k][j] and updating C[i][j]; 1-64 accelerators
    +-SMP is 128 candidates, none of them filtered."""
    cfg = spec.config(spec.benchmark(), "matmul1024-ramp")
    problem = spec.problem(cfg)
    assert len(problem.events) == 512 and len(problem.cands) == 128
    ev = problem.events[1]                       # k=0, i=0, j=1
    assert [(key, d) for key, d, _ in ev.accesses] == \
        [(("A", 0, 0), "in"), (("B", 0, 1), "in"), (("C", 0, 1), "inout")]
    assert all(nbytes == 65536 for _, _, nbytes in ev.accesses)
    assert all(problem.feasible(c) for c in problem.cands)
    (rep,) = problem.reports.values()
    assert dict(rep.resources) == {"dsp": 640.0, "bram_kb": 192.0,
                                   "lut": 106900.0}


def _small():
    with open(os.path.join(ROOT, "bench", "tests", "data",
                           "mm256-ramp.json")) as f:
        return json.load(f)


def test_reference_agrees_with_the_program():
    """Every candidate of a small matmul sweep: makespan, busy times and
    placements of the reference equal the program's, and the program ran
    lanes in the heap scan."""
    from repro.core.explore import Explorer
    problem = spec.problem(_small())
    inp = program.inputs(problem)
    ex = Explorer(inp.trace, program.reports_map(problem.reports),
                  policy=problem.policy, smp_seconds_fn=inp.smp_seconds_fn,
                  budget=problem.budget, engine="jax")
    res = ex.explore(inp.cands)
    assert ex.batch_stats.heap_lanes > 0
    ref = reference.Reference(problem, problem.reports)
    for cand in problem.cands:
        want = ref.run(cand)
        got = res.estimates[cand.name].sim
        assert got.makespan == want.makespan, cand.name
        assert got.busy == want.busy, cand.name
        assert got.placements == want.placements, cand.name


def test_small_run_is_correct(capsys):
    """The whole run at test size: every sweep checked against the
    reference, ``max_gap`` 0."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = copy.deepcopy(json.load(f))
    bench["configs"] = [{"name": "mm256-ramp",
                         "file": "bench/tests/data/mm256-ramp.json"}]
    bench["workloads"] = [{"name": "mm256-ramp.sweep",
                           "config": "mm256-ramp", "traffic": "oneshot",
                           "chips": 1}]
    rc = run.main(["--workload", "mm256-ramp.sweep", "--seed", "3000000123",
                   "--seconds", "0.2", "--trace", "0"],
                  require_device=False, bench=bench)
    out, err = capsys.readouterr()
    assert rc == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["check"] == {"max_gap": {"value": 0.0, "limit": 0.0}}


def test_binary32_control_fails():
    """The reference computed in binary32 is not correct on the matmul
    sweep: the exact comparison tells the two precisions apart."""
    gaps = check.control(spec.problem(_small()),
                         Traffic(spec.traffic("oneshot"), 11), 2)
    assert not check.correct(gaps)
    assert gaps.worst() > 0.0


def _trace(modules):
    return {"modules": modules}


@pytest.mark.parametrize("modules,want", [
    ({"jit_run_heap": {"seconds": 0.25, "launches": 2, "steps": 5000,
                       "loops": 2},
      "jit_run": {"seconds": 1.0, "launches": 1, "steps": 10, "loops": 1}},
     50.0),
    ({"jit_run": {"seconds": 1.0, "launches": 1, "steps": 10, "loops": 1}},
     None),
    ({"jit_run_heap": {"seconds": 0.25, "launches": 2, "steps": 2500,
                       "loops": 1}}, None),
])
def test_heap_scan_metric_reads_its_module(modules, want):
    read = spec.reader({"name": "heap_scan_us_per_step"})
    assert read(types.SimpleNamespace(trace=_trace(modules))) == want
    assert read(types.SimpleNamespace(trace=None)) is None
