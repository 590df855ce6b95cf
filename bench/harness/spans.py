"""The program's own host spans in a profiler trace, and what they explain.

The estimator marks its layers with named host spans
(``repro.core.spans``); :data:`PROGRAM` lists them.  They land on the
host plane, on the line of the thread that ran them, on the device
trace's clock.  The sweep's thread is the line that holds the benchmark's
``sweep`` span.  On that line, inside the first sweep, the reduction
takes:

* the self time of each span name: each span's duration less the part
  that its child program spans cover, summed over the spans of the name
  (spans on one thread nest, so the self times of the spans inside
  ``explore.sweep`` add up to its duration);
* how many spans of each name the sweep emitted;
* the device's idle time inside the sweep (no program on the ``XLA
  Modules`` line of the first device plane), split by the innermost
  program span that covers each instant, under ``none`` where no program
  span covers it.

Spans of other threads, and the runtime's own events, are left out.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from .tracing import SWEEP, _DEVICE, _clip, _complement, _events, \
    union_length

#: The estimator's span names, outermost layer first.
PROGRAM = ("explore.sweep", "explore.graph", "replay.plan", "replay.exact",
           "jaxsim.prep", "jaxsim.transfer", "jaxsim.launch",
           "replay.assemble", "explore.rank")
NONE = "none"

Span = Tuple[float, float, str]


def self_times(spans: Sequence[Span]) -> Dict[str, float]:
    """Self time by name, in the trace's unit, of spans that nest (one
    thread's spans): a span's duration less what its children cover."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [end, name, self]

    def close():
        _, name, own = stack.pop()
        out[name] = out.get(name, 0.0) + own

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close()
    return out


def innermost(spans: Sequence[Span], intervals: Sequence[Tuple[float, float]]
              ) -> Dict[str, float]:
    """The length of ``intervals`` by the innermost span covering each
    instant (the latest-starting of those that cover it), :data:`NONE`
    where none does."""
    cuts = sorted({b for s, e, _ in spans for b in (s, e)})
    out: Dict[str, float] = {}
    for lo, hi in intervals:
        edges = [lo] + [c for c in cuts if lo < c < hi] + [hi]
        for a, b in zip(edges, edges[1:]):
            cover = [(s, -e, n) for s, e, n in spans if s <= a and e >= b]
            name = max(cover)[2] if cover else NONE
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce(planes, devices: int) -> Dict:
    """Reduce the planes of one trace (``ProfileData.planes`` or objects
    with the same attributes, times in ns) to the program's spans inside
    the first sweep, in seconds.  Empty when the trace has no sweep."""
    sweep_line, device_planes = None, []
    for plane in planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) < devices:
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if sweep_line is None and any(
                        ev.name == SWEEP for ev in line.events):
                    sweep_line = line
    if sweep_line is None:
        return {}
    lo, hi = min((ev.start_ns, ev.start_ns + ev.duration_ns)
                 for ev in sweep_line.events if ev.name == SWEEP)
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for ev in sweep_line.events
             if ev.name in PROGRAM and ev.start_ns >= lo
             and ev.start_ns + ev.duration_ns <= hi]
    counts: Dict[str, int] = {}
    for _, _, name in spans:
        counts[name] = counts.get(name, 0) + 1
    out = {"sweep_s": (hi - lo) * 1e-9,
           "self_s": {k: v * 1e-9 for k, v in self_times(spans).items()},
           "spans": counts}
    if device_planes:
        busy = []
        for ev in _events({line.name: line for line in device_planes[0].lines}
                          .get("XLA Modules")):
            iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if iv is not None:
                busy.append(iv)
        _, merged = union_length(busy)
        idle = innermost(spans, _complement(merged, lo, hi))
        out["idle_by_span"] = {k: v * 1e-9 for k, v in idle.items()}
    return out


def read(xspace: bytes, devices: int) -> Dict:
    import jax
    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    return reduce(pd.planes, devices)
