"""The reduction of the program's own spans: on hand-made planes, on a
trace recorded before the program had spans, and on a small jax sweep
traced on the CPU."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from harness import program, spans, spec, tracing

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def test_self_times_and_idle_by_innermost_span():
    """Nested spans on the sweep's thread; the same names on another
    thread and outside the sweep, and the runtime's own events, count for
    nothing; idle instants under no program span are ``none``."""
    sweep_thread = [
        ev("window", 0, 1000), ev("sweep", 0, 1000),
        ev("explore.sweep", 10, 990),
        ev("explore.graph", 20, 120),
        ev("replay.plan", 130, 400), ev("replay.exact", 150, 350),
        ev("jaxsim.prep", 410, 500), ev("jaxsim.transfer", 450, 480),
        ev("jaxsim.launch", 500, 700), ev("PjitFunction(run)", 510, 600),
        ev("replay.assemble", 710, 800), ev("explore.rank", 850, 950),
        ev("explore.graph", 1100, 1200)]
    planes = [
        NS(name="/host:CPU", lines=[
            NS(name="worker", events=[ev("replay.exact", 0, 1000)]),
            NS(name="python", events=sweep_thread)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=[
            ev("jit_run(7)", 550, 680), ev("jit_other(2)", 1000, 1050)])]),
        NS(name="/device:TPU:1", lines=[NS(name="XLA Modules", events=[
            ev("far", 0, 1000)])]),
    ]
    r = spans.reduce(planes, devices=1)
    assert r["sweep_s"] == pytest.approx(1000e-9)
    assert {k: round(v * 1e9) for k, v in r["self_s"].items()} == {
        "explore.sweep": 130, "explore.graph": 100, "replay.plan": 70,
        "replay.exact": 200, "jaxsim.prep": 60, "jaxsim.transfer": 30,
        "jaxsim.launch": 200, "replay.assemble": 90, "explore.rank": 100}
    assert sum(r["self_s"].values()) == pytest.approx(980e-9)
    assert r["spans"] == {name: 1 for name in spans.PROGRAM}
    # idle: 0..550 and 680..1000 of the sweep (870 ns)
    assert {k: round(v * 1e9) for k, v in r["idle_by_span"].items()} == {
        "none": 20, "explore.sweep": 130, "explore.graph": 100,
        "replay.plan": 70, "replay.exact": 200, "jaxsim.prep": 60,
        "jaxsim.transfer": 30, "jaxsim.launch": 70, "replay.assemble": 90,
        "explore.rank": 100}


def test_without_a_sweep_reads_nothing():
    planes = [NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("explore.sweep", 0, 10)])])]
    assert spans.reduce(planes, devices=1) == {}


def test_trace_without_program_spans_is_all_none():
    """A trace of a program without spans (recorded on a TPU v5e before
    they existed): no self times, and all idle time under ``none``."""
    with open(os.path.join(DATA, "tpu_window.xplane.pb"), "rb") as f:
        r = spans.read(f.read(), devices=1)
    assert r["self_s"] == {} and r["spans"] == {}
    assert list(r["idle_by_span"]) == ["none"]
    assert 0 < r["idle_by_span"]["none"] < r["sweep_s"]


#: Where each span may sit: the program span that directly encloses it.
PARENTS = {
    "explore.sweep": {None},
    "explore.graph": {"explore.sweep"},
    "replay.plan": {"explore.sweep"},
    "replay.exact": {"replay.plan", "replay.assemble"},
    "jaxsim.prep": {"explore.sweep"},
    "jaxsim.transfer": {"jaxsim.prep"},
    "jaxsim.launch": {"explore.sweep"},
    "replay.assemble": {"explore.sweep"},
    "explore.rank": {"explore.sweep"},
}


def test_a_traced_sweep_emits_every_span_nested():
    """One small jax sweep under JAX's profiler on the CPU (the dgemm
    ramp: unlike the small Fig. 9 grid, some of its lanes reach the scan):
    every program span appears on the sweep's thread, each inside its
    parent, and the self times add up to ``explore.sweep``."""
    import jax
    with open(os.path.join(DATA, "chol768-dgemm8.json")) as f:
        problem = spec.problem(json.load(f))
    inp = program.inputs(problem)
    program.sweep(problem, inp, problem.reports, lambda ranked: ())
    tracer = tracing.Tracer()
    tracer.start()
    with jax.profiler.TraceAnnotation(tracing.SWEEP):
        program.sweep(problem, inp, problem.reports, lambda ranked: ())
    xspace = tracer.stop()
    planes = list(
        jax.profiler.ProfileData.from_serialized_xspace(xspace).planes)
    (line,) = [line for plane in planes if plane.name.startswith("/host:")
               for line in plane.lines
               if any(e.name == tracing.SWEEP for e in line.events)]
    got = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
           for e in line.events if e.name in spans.PROGRAM]
    assert {n for _, _, n in got} == set(spans.PROGRAM)
    for s, e, name in got:
        outer = [(s2, -e2, n2) for s2, e2, n2 in got
                 if s2 <= s and e2 >= e and (s2, e2) != (s, e)]
        parent = max(outer)[2] if outer else None
        assert parent in PARENTS[name], (name, parent)

    r = spans.reduce(planes, devices=1)
    (whole,) = [e - s for s, e, n in got if n == "explore.sweep"]
    assert sum(r["self_s"].values()) == pytest.approx(whole * 1e-9)
    assert r["spans"]["explore.sweep"] == 1


def test_layers_reads_the_spans_and_counters_of_a_sweep(small_bench,
                                                        capsys):
    """``layers.py`` on the CPU at test size: one line per seed with the
    six layer metrics, then the cost of a span."""
    import layers
    rc = layers.main(["--workload", "chol768-dgemm8.sweep", "--seeds",
                      "3000000123"], require_device=False, bench=small_bench)
    assert rc == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    (got, cost) = lines
    assert set(got["metrics"]) == {
        "graph_build_s", "exact_path_s", "scan_prep_s", "assemble_s",
        "scan_lane_fill", "h2d_mb_per_sweep"}
    assert 0 < got["metrics"]["scan_lane_fill"] <= 100
    assert got["counters"]["h2d_bytes"] > 0 and got["compiles"] == 0
    assert got["spans"]["explore.sweep"] == 1
    assert cost["span_cost_us"] > 0
