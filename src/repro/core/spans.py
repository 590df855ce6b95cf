"""Named host spans at the estimator's layer boundaries.

``with span("replay.exact"): ...`` marks one stretch of host work for
JAX's profiler: inside a profiler session it lands on the host plane of
the trace, on the same clock as the device's programs; outside one it
costs well under a microsecond.  Spans sit at layer boundaries only (a
sweep, a graph build, an exact lane, a scan launch), never per task or per
scan step.

A process that has not imported jax gets a shared no-op context, so
host-only process workers never import jax for a span.
"""
from __future__ import annotations

import contextlib
import sys

_NOOP = contextlib.nullcontext()


def span(name: str):
    """A context manager that marks ``name`` on the profiler's host
    plane: ``jax.profiler.TraceAnnotation`` once jax is loaded, else a
    no-op."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NOOP
    return jax.profiler.TraceAnnotation(name)
