"""The ``python -m repro.explore`` one-stop driver (tier-1 smoke).

Covers both trace sources (``synth:N`` and a ``Trace.save`` JSONL file with
a reports JSON), the warm-start path through ``--cache-dir``, and the
entrypoint itself via a real subprocess.
"""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from repro.explore import _parse_accs, main
from repro.testing.synth import synth_report, synth_trace


def run_main(args, capsys):
    rc = main(args)
    out = capsys.readouterr().out
    return rc, json.loads(out)


def test_synth_trace_end_to_end(capsys):
    rc, doc = run_main(["synth:24", "--accs", "1-6", "--top-k", "3"], capsys)
    assert rc == 0
    assert doc["candidates"] == 12 and doc["engine"] == "batch"
    assert doc["best"] == doc["top"][0]["name"]
    assert len(doc["top"]) == 3 and doc["top"][0]["rank"] == 0
    spans = [t["makespan_s"] for t in doc["top"]]
    assert spans == sorted(spans)
    assert "serial_fallback_lanes" in doc["replay"]
    # timings: no admission queue in one-shot mode, sweep <= total
    t = doc["timings"]
    assert t["queue_s"] == 0.0
    assert 0.0 < t["sweep_s"] <= t["total_s"]
    assert t["sweep_s"] == pytest.approx(doc["wall_seconds"], abs=1e-6)


def test_file_trace_with_reports_and_warm_cache(tmp_path, capsys):
    trace_path = str(tmp_path / "trace.jsonl")
    synth_trace(40).save(trace_path)
    rep = synth_report()
    reports_path = str(tmp_path / "reports.json")
    with open(reports_path, "w") as f:
        json.dump([dataclasses.asdict(rep)], f)
    cache = str(tmp_path / "store")
    args = [trace_path, "--reports", reports_path, "--accs", "1-8",
            "--cache-dir", cache, "--top-k", "2"]
    rc, cold = run_main(args, capsys)
    assert rc == 0 and cold["cache"]["disk_misses"] > 0
    assert cold["replay"]["reference_lanes"] > 0
    rc, warm = run_main(args, capsys)
    assert rc == 0
    assert warm["cache"]["disk_hits"] > 0           # graphs/sims from disk
    assert warm["top"] == cold["top"]
    assert os.listdir(cache)


def test_ppa_flags_produce_frontier_document(capsys):
    rc, doc = run_main(["synth:24", "--accs", "1-6", "--top-k", "3",
                        "--objectives", "area_mm2,energy_j",
                        "--budget", "power_w=5.0"], capsys)
    assert rc == 0
    # budget axes join the objectives, canonical order
    assert doc["objectives"] == ["makespan_s", "area_mm2", "power_w",
                                 "energy_j"]
    assert doc["budgets"] == {"power_w": 5.0}
    assert doc["frontier"] and isinstance(doc["dominated"], int)
    names = [e["name"] for e in doc["frontier"]]
    assert doc["best"] in names                 # makespan minimum is Pareto
    for e in doc["frontier"]:
        assert set(e["objectives"]) == set(doc["objectives"])
        assert e["ppa"]["area_mm2"] == e["objectives"]["area_mm2"]
    # top entries carry the objective values too in PPA mode
    assert all("objectives" in t for t in doc["top"])


def test_ppa_flag_errors_exit_2(capsys):
    for args in (["synth:8", "--objectives", "latency"],
                 ["synth:8", "--budget", "power_w"],
                 ["synth:8", "--budget", "bogus=1"],
                 ["synth:8", "--budget", "power_w=-2"]):
        assert main(args) == 2
        err = capsys.readouterr().err
        assert err.startswith("error:") and "Traceback" not in err


def test_file_trace_requires_reports(tmp_path, capsys):
    trace_path = str(tmp_path / "trace.jsonl")
    synth_trace(8).save(trace_path)
    with pytest.raises(SystemExit):
        main([trace_path])


def test_parse_accs():
    assert _parse_accs("1-4") == [1, 2, 3, 4]
    assert _parse_accs("1,2,4") == [1, 2, 4]
    assert _parse_accs("2-3,8") == [2, 3, 8]
    with pytest.raises(ValueError):
        _parse_accs("0")


def test_module_entrypoint_subprocess(tmp_path):
    out_path = str(tmp_path / "out.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.explore", "synth:16", "--accs", "1-4",
         "--no-smp", "--json", out_path],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    doc = json.load(open(out_path))
    assert doc["candidates"] == 4 and doc["best"]


def test_jax_engine_compile_cache_goes_where_the_env_says(tmp_path):
    cache = tmp_path / "jax-cache"
    out_path = str(tmp_path / "out.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.explore", "synth:16", "--accs", "1-6",
         "--engine", "jax", "--json", out_path],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert json.load(open(out_path))["engine_final"] == "jax"
    # the scan runner's executable is among the persisted entries
    assert any(p.name.startswith("jit_run") for p in cache.iterdir())


def test_persistent_cache_defaults_to_the_checkout(monkeypatch):
    import jax
    from repro.core.xlacache import (DEFAULT_JAX_CACHE_DIR,
                                     enable_persistent_cache)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_JAX_CACHE_DIR == os.path.join(root, ".jax_cache")
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert enable_persistent_cache() == DEFAULT_JAX_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_JAX_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
