"""The trace reduction, on hand-made planes and on a small trace recorded
on a TPU v5e (``data/tpu_window.xplane.pb``: one traced window of a
test-sized sweep)."""
import os
from types import SimpleNamespace as NS

import pytest

from harness import tracing

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes(ops, modules, host):
    return [
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=modules),
            NS(name="XLA Ops", events=ops)]),
        NS(name="/device:TPU:1", lines=[NS(name="XLA Modules", events=[
            ev("far", 0, 10_000)])]),
    ]


def test_busy_is_the_union_of_programs_inside_the_window():
    mods = [ev("jit_run(7)", 50, 170),            # 50..220
            ev("jit_small(2)", 200, 40),          # overlaps: 50..240
            ev("jit_run(7)", 400, 100),           # 400..500
            ev("jit_other(3)", 950, 200)]         # clipped to 950..1000
    ops = [ev("a", 50, 100), ev("b", 120, 100), ev("c", 400, 100)]
    host = [ev("window", 0, 1000), ev("sweep", 0, 600),
            ev("sweep", 600, 400), ev("Transpose", 230, 180)]
    r = tracing.reduce(planes(ops, mods, host), devices=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((190 + 100 + 50) * 1e-9)
    assert r["modules"]["jit_run"] == {"seconds": pytest.approx(270e-9),
                                       "launches": 2, "steps": 0,
                                       "loops": 0}
    assert r["modules"]["jit_other"]["launches"] == 1
    assert [k for k, _ in r["device_ops"]] == ["a", "b", "c"]
    # idle: 0..50, 240..400, 500..950 (cut at the sweep boundary 600)
    assert [[lab, round(s * 1e9)] for lab, s in r["idle_gaps"]] == [
        ["sweep before first program", 350],
        ["sweep between programs / Transpose", 160],
        ["sweep after last program", 100],
        ["sweep before first program", 50]]


def test_without_operation_events_programs_stand_in():
    mods = [ev("jit_run(7)", 100, 300)]
    host = [ev("window", 0, 1000), ev("sweep", 0, 1000)]
    r = tracing.reduce(planes([], mods, host), devices=1)
    assert r["device_ops"] == [["jit_run", pytest.approx(300e-9)]]
    assert [lab for lab, _ in r["idle_gaps"]] == [
        "sweep after last program", "sweep before first program"]


def test_no_window_or_no_device_reads_nothing():
    assert tracing.reduce(planes([], [], []), devices=1) == {}
    host = [ev("window", 0, 10)]
    assert tracing.reduce([NS(name="/host:CPU", lines=[
        NS(name="t", events=host)])], devices=1) == {}


def test_loop_steps_from_the_operand_shapes():
    name = ("%while.3 = (s32[], u32[9,4]{0,1}, f32[8,4,2]{2,1,0}, "
            "pred[8,4]{1,0}, s32[8,4], u32[4]) while((s32[], u32[9,4]) "
            "%tuple.1), condition=%cond, body=%body")
    assert tracing.loop_steps(name) == 8
    assert tracing.loop_steps("%while.1 = (s32[], u32[4]) while(...)") is None


def test_each_launch_takes_its_longest_inner_loop():
    mods = [ev("jit_run(7)", 0, 100), ev("jit_run(7)", 200, 100)]
    ops = [ev("%while.1 = (s32[3,2], s32[3,2]) while(x)", 10, 20),
           ev("%while.2 = (s32[50,2], s32[50,2]) while(x)", 5, 90),
           ev("%while.2 = (s32[70,2], s32[70,2]) while(x)", 210, 80)]
    host = [ev("window", 0, 1000), ev("sweep", 0, 1000)]
    r = tracing.reduce(planes(ops, mods, host), devices=1)
    assert r["modules"]["jit_run"]["steps"] == 120
    assert r["modules"]["jit_run"]["loops"] == 2


def test_union_length():
    total, merged = tracing.union_length([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert total == 5 and merged == [(0, 4), (5, 6)]


def test_recorded_tpu_window():
    """One traced sweep of the test-sized synth ramp on a TPU v5e (seed
    4242): one scan launch of 240 steps, 16 lanes."""
    with open(os.path.join(DATA, "tpu_window.xplane.pb"), "rb") as f:
        r = tracing.read(f.read(), devices=1)
    assert r["window_s"] == pytest.approx(0.035655627)
    assert r["busy_s"] == pytest.approx(0.007225275)
    assert r["modules"]["jit_run"] == {
        "seconds": pytest.approx(0.007212842), "launches": 1, "steps": 240,
        "loops": 1}
    assert r["device_ops"][0][0].startswith("%while.11 = ")
    assert r["device_ops"][0][1] == pytest.approx(0.007202778)
    assert len(r["device_ops"]) == tracing.TOP
    assert [lab for lab, _ in r["idle_gaps"][:3]] == [
        "sweep before first program", "sweep after last program",
        "sweep between programs"]
    assert r["idle_gaps"][0][1] == pytest.approx(0.016464779)
