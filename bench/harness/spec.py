"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

* a configuration: the file that its entry under ``configs`` names; its
  ``app`` key names the builder ``bench/apps/<app>.py``;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a metric: the reader ``bench/metrics/<metric name>.py``, which defines
  ``read(ctx)`` returning a number, or None when it finds nothing to read;
* a device's peaks: the file under ``bench/peaks/`` whose ``device_kind``
  is the one JAX reports.

Adding any of these is adding a file; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(Exception):
    """The benchmark's files do not define what a run asked for."""


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _load_json(os.path.join(ROOT, c["file"]))
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def problem(cfg: Dict):
    """The configuration's inputs as plain data (:class:`Problem`)."""
    app = _load_module(os.path.join(BENCH, "apps", cfg["app"] + ".py"),
                       f"bench_app_{cfg['app']}")
    return app.build(cfg)


def traffic(name: str) -> Dict:
    path = os.path.join(BENCH, "traffic", name + ".json")
    if not os.path.isfile(path):
        raise SpecError(f"no traffic file for {name!r}")
    return _load_json(path)


def metrics_for(bench: Dict, trace: bool) -> List[Dict]:
    """The metrics a run reports: the end-to-end ones with ``--trace 0``,
    the per-layer ones with ``--trace 1``.  Every cell reports all of them;
    a reader that finds nothing to read in a cell leaves its metric out."""
    return bench["per_layer"] if trace else bench["end_to_end"]


def reader(metric: Dict):
    mod = _load_module(os.path.join(BENCH, "metrics", metric["name"] + ".py"),
                       "bench_metric_" + metric["name"].replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def peaks(device_kind: str) -> Optional[Dict]:
    folder = os.path.join(BENCH, "peaks")
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".json"):
            doc = _load_json(os.path.join(folder, fname))
            if doc.get("device_kind") == device_kind:
                return doc
    return None
