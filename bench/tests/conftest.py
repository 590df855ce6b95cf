"""Shared set-up of the benchmark's own tests.

Run them by path, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They drive the harness at test sizes (the configurations under
``bench/tests/data``) with the device check skipped.
"""
import copy
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {"chol768-dgemm8.sweep": "chol768-dgemm8",
         "chol512-fig9.sweep": "chol512-fig9"}


@pytest.fixture(scope="session")
def small_bench():
    """``BENCHMARK.json`` with its cells swapped for test-sized ones of the
    same configurations' apps and the same traffic and metrics."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc = copy.deepcopy(doc)
    doc["configs"] = [{"name": c, "file": f"bench/tests/data/{c}.json"}
                      for c in SMALL.values()]
    doc["workloads"] = [{"name": w, "config": c, "traffic": "oneshot",
                         "chips": 1} for w, c in SMALL.items()]
    return doc
