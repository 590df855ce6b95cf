"""``BENCHMARK.json`` and the files it names fit together."""
import json
import os
import re

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.reader({"name": metric}))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_builds_and_reports(cell):
    w = spec.workload(BENCH, cell)
    cfg = spec.config(BENCH, w["config"])
    problem = spec.problem(cfg)
    assert problem.cands and problem.events
    assert spec.traffic(w["traffic"])["scaled_fields"]
    e2e = [m["name"] for m in spec.metrics_for(BENCH, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(BENCH, True)
    moved = {m["moves"] for m in spec.metrics_for(BENCH, True)}
    assert moved <= set(e2e)


def test_configuration_files_are_the_named_ones():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        cfg = spec.config(BENCH, c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert c["file"].startswith(BENCH["paths"][0] + "/")


def test_peaks_know_the_v5e():
    peaks = spec.peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["hbm_bytes"] == 16e9
    assert spec.peaks("no such device") is None
