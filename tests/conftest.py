"""Shared test configuration.

* **src-layout imports without PYTHONPATH** — ``pyproject.toml`` sets
  ``pythonpath = ["src"]`` for pytest ≥ 7; the explicit ``sys.path`` insert
  below keeps direct ``python tests/...`` invocations and exotic runners
  working too.
* **hypothesis fallback** — property-based tests import ``hypothesis`` at
  module level.  When the real package is missing (hermetic containers),
  ``repro.testing.minihypothesis`` registers a deterministic, shrink-free
  stand-in for the API surface the suite uses, so the property tests still
  *run* instead of hard-erroring at collection.
"""
import pathlib
import sys

_SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

from repro.testing import minihypothesis  # noqa: E402

minihypothesis.install()
