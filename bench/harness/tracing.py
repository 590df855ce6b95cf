"""The profiler trace of a traced run, and its reduction.

A traced run records the first sweep of its window.  The benchmark's own
host spans (``jax.profiler.TraceAnnotation``) mark the traced stretch
(``window``) and each sweep (``sweep``).  From the trace the reduction
takes:

* busy time: the union of the intervals in which a program (the device
  plane's ``XLA Modules`` line) ran on a TPU device, inside the window,
  averaged over the devices used; the idle share is one less busy over
  the window.  Programs rather than their operations, because a TPU drops
  operation events when its trace buffers fill, and program events are
  few;
* time by XLA module: the device durations on the ``XLA Modules`` line,
  grouped by module name with the trailing program id dropped, how many
  times each module ran, and the steps of its loops: for each run of the
  module, the longest ``while`` operation inside it on the ``XLA Ops``
  line, whose trip count is the leading dimension that most of its
  operands share (a scan's stacked inputs are ``[steps, ...]``);
* the operations that took most device time (``XLA Ops``, else the
  programs), by the names the trace prints, cut to ``NAME`` characters;
* the longest idle gaps, cut at sweep boundaries, each labelled with where
  the host was: the benchmark's ``sweep`` span and whether the gap comes
  before the sweep's first program, between two, or after its last, and
  the innermost span of JAX's runtime that covers it, if one does.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "window"
SWEEP = "sweep"
TOP = 10
#: Characters of an operation's name that the breakdown keeps.
NAME = 120

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_PROGRAM_ID = re.compile(r"\(\d+\)$")
#: The dimensions of an operand of rank two or more in an HLO shape.
_DIMS = re.compile(r"\b(?:pred|[a-z]+\d+)\[(\d+(?:,\d+)+)\]")


class Tracer:
    """JAX's profiler session around the traced sweep, kept in memory: the
    trace is never written to disk (a sweep's runs to some hundred MB, and
    exporting it takes minutes)."""

    def start(self) -> None:
        import jax
        from jax._src import profiler as jax_profiler
        # as jax.profiler.start_trace does: the TPU tracer needs the
        # backend up before the session starts
        jax_profiler.xla_bridge.get_backend()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self._session = jax_profiler._profiler.ProfilerSession(opts)

    def stop(self) -> bytes:
        """The serialized ``XSpace`` of the session."""
        xspace, self._session = self._session.stop(), None
        return xspace


def union_length(intervals: Sequence[Tuple[float, float]]) -> Tuple[
        float, List[Tuple[float, float]]]:
    """Total length of the union, and the merged intervals in order."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def _clip(ev_start: float, ev_end: float, lo: float, hi: float
          ) -> Optional[Tuple[float, float]]:
    s, e = max(ev_start, lo), min(ev_end, hi)
    return (s, e) if e > s else None


def module_name(name: str) -> str:
    return _PROGRAM_ID.sub("", name)


def loop_steps(op_name: str) -> Optional[int]:
    """The trip count of a ``while`` operation, from the HLO text that the
    trace gives as its name: the leading dimension shared by most of the
    loop's operands of rank two or more (ties: the larger)."""
    head = op_name.split(" while(", 1)[0]
    counts: Dict[int, int] = {}
    for dims in _DIMS.findall(head):
        lead = int(dims.split(",")[0])
        counts[lead] = counts.get(lead, 0) + 1
    if not counts:
        return None
    return max(counts, key=lambda d: (counts[d], d))


def _loops(runs, whiles) -> Tuple[int, int]:
    """Steps summed over the runs of a module, and how many runs had a
    loop: for each run, the longest ``while`` operation inside it."""
    steps = found = 0
    for s, e in runs:
        inside = [(we - ws, name) for ws, we, name in whiles
                  if ws >= s and we <= e]
        n = loop_steps(max(inside)[1]) if inside else None
        if n:
            steps += n
            found += 1
    return steps, found


def reduce(planes, devices: int) -> Dict:
    """Reduce the planes of one trace (``ProfileData.planes``, or any
    objects with the same ``name``/``lines``/``events`` attributes, times
    in ns) to the window's device numbers.  ``devices`` is how many chips
    the cell uses."""
    host = []
    device_planes = []
    for plane in planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) < devices:
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    windows = [(s, e) for s, e, n in host if n == WINDOW]
    if not windows or not device_planes:
        return {}
    lo, hi = windows[0]
    busy_total = 0.0
    first: List[Tuple[float, float]] = []
    ops: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    for i, plane in enumerate(device_planes):
        lines = {line.name: line for line in plane.lines}
        intervals = []
        runs: Dict[str, List[Tuple[float, float]]] = {}
        for ev in _events(lines.get("XLA Modules")):
            iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if iv is not None:
                intervals.append(iv)
                acc = modules.setdefault(module_name(ev.name),
                                         [0.0, 0, 0, 0])
                acc[0] += iv[1] - iv[0]
                acc[1] += 1
                runs.setdefault(module_name(ev.name), []).append(iv)
        whiles = []
        for ev in _events(lines.get("XLA Ops")):
            iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if iv is not None:
                ops[ev.name] = ops.get(ev.name, 0.0) + (iv[1] - iv[0])
                if ev.name.startswith("%while"):
                    whiles.append((iv[0], iv[1], ev.name))
        for name, ivs in runs.items():
            steps, found = _loops(ivs, whiles)
            modules[name][2] += steps
            modules[name][3] += found
        busy, merged = union_length(intervals)
        busy_total += busy
        if i == 0:
            first = merged
    if not ops:       # a trace without per-operation events: programs
        ops = {k: v[0] for k, v in modules.items()}
    sweeps = sorted((s, e) for s, e, n in host if n == SWEEP)
    runtime = [(s, e, n) for s, e, n in host if n not in (WINDOW, SWEEP)]
    gaps = []
    for s, e in _complement(first, lo, hi):
        gaps.extend(_split(s, e, sweeps))
    gaps.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / len(device_planes) * 1e-9,
        "modules": {k: {"seconds": v[0] * 1e-9, "launches": v[1],
                        "steps": v[2], "loops": v[3]}
                    for k, v in modules.items()},
        "device_ops": [[k[:NAME], v * 1e-9] for k, v in top_ops],
        "idle_gaps": [[_label(s, e, sweeps, first, runtime),
                       (e - s) * 1e-9] for s, e in gaps[:TOP]],
    }


def _events(line):
    return line.events if line is not None else ()


def _complement(merged, lo, hi):
    """The idle intervals of ``[lo, hi]`` around the merged busy ones."""
    out, t = [], lo
    for s, e in merged + [(hi, hi)]:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    return out


def _split(s, e, sweeps):
    """A gap cut at the sweep boundaries it crosses."""
    cuts = sorted({s, e} | {b for sw in sweeps for b in sw if s < b < e})
    return list(zip(cuts, cuts[1:]))


def _label(s, e, sweeps, busy, runtime):
    """Where the host was while the device idled: the benchmark's span
    (``sweep`` and whether the gap lies before the sweep's first device
    program, between two, or after its last), and the innermost runtime
    event that covers the gap, if one does."""
    where = "outside sweeps"
    for ss, se in sweeps:
        if ss <= s and e <= se:
            before = any(bs >= ss and be <= s for bs, be in busy)
            after = any(bs >= e and be <= se for bs, be in busy)
            where = SWEEP + (" between programs" if before and after else
                             " before first program" if after else
                             " after last program" if before else
                             " without programs")
            break
    inner = [(ee - ss, n) for ss, ee, n in runtime if ss <= s and ee >= e]
    return f"{where} / {min(inner)[1]}" if inner else where


def read(xspace: bytes, devices: int) -> Dict:
    import jax
    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    return reduce(pd.planes, devices)

