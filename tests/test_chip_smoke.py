"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The script itself refuses any device but a TPU; its phase functions take
their sizes as arguments, so the same code paths — ``Explorer(engine=
"jax")`` against ``engine="batch"``, and ``POST /sweep`` to an in-process
sweep server — run here on small graphs.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY_REQUEST = {"trace": "synth:24", "accs": "1-8"}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_the_cpu(smoke, capsys):
    with pytest.raises(smoke.SmokeFailure, match="not a TPU"):
        smoke.main()
    assert '"ok": true' not in capsys.readouterr().out


def test_script_alone_fails_without_result(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_cholesky_grid_is_the_fig9_style_grid(smoke):
    grid = smoke.cholesky_grid(128)
    assert len(grid) == 496
    assert len({c.name for c in grid}) == 496
    shared_smp = [c for c in grid if c.name.endswith("/shared/smp")]
    assert len(shared_smp) == 124      # 4 + 4 + 4 + 16 + 16 + 16 + 64


def test_cholesky_phase_tiny(smoke, capsys):
    out = smoke.phase_cholesky(n=256, bs=64)
    assert out["candidates"] == 496
    assert out["ranked"] + out["infeasible"] == 496
    assert out["ranked"] > 0 and out["best"]
    assert "phase A:" in capsys.readouterr().out


def test_sweep_and_served_phases_tiny(smoke, capsys):
    ref = smoke.phase_sweep(TINY_REQUEST, min_lockstep=8)
    assert len(ref.ranked) == 16
    smoke.phase_served(ref, TINY_REQUEST, n_requests=2, top_k=3)
    out = capsys.readouterr().out
    assert out.count("phase C request") == 2


def test_sweep_phase_enforces_its_lockstep_floor(smoke):
    with pytest.raises(smoke.SmokeFailure, match="lockstep lanes"):
        smoke.phase_sweep(TINY_REQUEST, min_lockstep=10_000)
