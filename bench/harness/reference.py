"""The plain reference: the estimator's semantics written out once more,
independently of the program.

It follows the paper's model (arXiv:1508.06830 §IV) as the configuration
files state it:

* the dependences of the sequential trace under the OmpSs rules (a reader
  waits for the last writer of each region, a writer for the last writer
  and for every reader since);
* the augmentation of every task with a creation task on the SMP, chained
  in program order, and, for a task that may run on an accelerator, a DMA
  ``submit`` task per read region, and a ``submit`` plus a serialised
  ``dma_out`` transfer per written region.  These vanish (zero time, no
  resource) when the task lands on the SMP.  Input transfers are folded
  into the accelerator's occupancy;
* list scheduling in the order of a heap keyed by (ready time, creation
  index, task id): a compute task's device is chosen when the first task
  of its unit becomes ready, as the one that can start it earliest (the
  ``availability`` policy of the paper's runtime), an accelerator before
  the SMP on ties; a task then takes the pool's earliest free slot.

Times are plain Python floats (IEEE binary64), added in the order the
events happen.  ``num=numpy.float32`` computes every time in binary32
instead: that is the control that the comparison has to reject.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Callable, Dict, List, Mapping, Optional, Tuple

from .problem import DMA_OUT, SMP, SUBMIT, Cand, Problem, Report, pool_name


@dataclasses.dataclass
class Result:
    makespan: float
    busy: Dict[str, float]            # pool name -> summed busy seconds
    placements: Dict[int, str]        # compute task id -> device kind


@dataclasses.dataclass
class Graph:
    """The augmented task graph, one list entry per task id."""

    creation: List[int]
    devices: List[Tuple[str, ...]]
    costs: List[Dict[str, float]]
    is_compute: List[bool]
    cond: List[Optional[int]]         # compute task whose placement gates it
    active: List[Tuple[str, ...]]     # kinds under which a gated task runs
    succ: List[set]
    pred: List[set]


def _reads(direction: str) -> bool:
    return direction in ("in", "inout")


def _writes(direction: str) -> bool:
    return direction in ("out", "inout")


def build_graph(problem: Problem, reports: Mapping[Tuple[str, str], Report],
                cand: Cand) -> Graph:
    events = problem.events
    elig = dict(cand.eligibility)
    available = {SMP, SUBMIT, DMA_OUT} | {k for k, _ in cand.accelerators}
    mean: Dict[str, float] = {}
    if problem.smp_cost[0] == "mean":
        tot: Dict[str, float] = {}
        cnt: Dict[str, int] = {}
        for ev in events:
            tot[ev.name] = tot.get(ev.name, 0.0) + ev.elapsed_smp
            cnt[ev.name] = cnt.get(ev.name, 0) + 1
        mean = {k: tot[k] / cnt[k] for k in tot}

    g = Graph([], [], [], [], [], [], [], [])

    def add(creation, devices, costs, compute, cond=None, active=()):
        g.creation.append(creation)
        g.devices.append(devices)
        g.costs.append(costs)
        g.is_compute.append(compute)
        g.cond.append(cond)
        g.active.append(active)
        g.succ.append(set())
        g.pred.append(set())
        return len(g.creation) - 1

    def edge(a, b):
        if a != b:
            g.succ[a].add(b)
            g.pred[b].add(a)

    # pass 1: the compute tasks and their data dependences
    last_writer: Dict[object, int] = {}
    readers: Dict[object, List[int]] = {}
    for i, ev in enumerate(events):
        kinds = tuple(k for k in elig.get(ev.name, (SMP,)) if k in available)
        if not kinds:
            raise ValueError(f"{cand.name}: no device for task {ev.name}")
        costs = {}
        for k in kinds:
            if k == SMP:
                s = problem.smp_seconds(ev)
                costs[k] = s if s is not None else mean[ev.name] * 1.0
            else:
                rep = reports[(ev.name, k)]
                costs[k] = rep.dma_in_s + rep.compute_s
        t = add(i, kinds, costs, True)
        for key, d, _ in ev.accesses:
            if _reads(d) and key in last_writer:
                edge(last_writer[key], t)
            if _writes(d):
                if key in last_writer:
                    edge(last_writer[key], t)
                for r in readers.get(key, ()):
                    edge(r, t)
        for key, d, _ in ev.accesses:
            if _writes(d):
                last_writer[key] = t
                readers[key] = []
        for key, d, _ in ev.accesses:
            if _reads(d) and not _writes(d):
                readers.setdefault(key, []).append(t)

    n = len(events)
    data_succ = [set(g.succ[t]) for t in range(n)]
    data_pred = [set(g.pred[t]) for t in range(n)]

    def writes_key(t, key):
        return any(k == key and _writes(d) for k, d, _ in events[t].accesses)

    def touches_key(t, key):
        return any(k == key for k, _, _ in events[t].accesses)

    # pass 2: creation, DMA submit and output-transfer tasks
    prev = None
    for t in range(n):
        ev = events[t]
        c = add(t, (SMP,), {SMP: problem.task_creation_s}, False)
        if prev is not None:
            edge(prev, c)
        edge(c, t)
        prev = c
        accel = tuple(k for k in g.devices[t] if k != SMP)
        if not accel:
            continue
        rep0 = next(reports[(ev.name, k)] for k in accel
                    if (ev.name, k) in reports)
        for key, d, _ in ev.accesses:
            if not _reads(d):
                continue
            s = add(t, (SUBMIT,), {SUBMIT: problem.dma_submit_s}, False,
                    t, accel)
            edge(c, s)
            for p in data_pred[t]:
                if writes_key(p, key):
                    edge(p, s)
            edge(s, t)
        for key, d, _ in ev.accesses:
            if not _writes(d):
                continue
            so = add(t, (SUBMIT,), {SUBMIT: problem.dma_submit_s}, False,
                     t, accel)
            edge(t, so)
            xo = add(t, (DMA_OUT,), {DMA_OUT: rep0.dma_out_s}, False,
                     t, accel)
            edge(so, xo)
            for nxt in data_succ[t]:
                if touches_key(nxt, key):
                    edge(xo, nxt)
    return g


def simulate(g: Graph, problem: Problem, cand: Cand,
             num: Callable = float) -> Result:
    """List-schedule the graph on the candidate's pools."""
    pools: List[Tuple[str, List]] = [(SMP, [num(0.0)] * problem.smp_cores)]
    kind_pool: Dict[str, int] = {SMP: 0}
    for kind, slots in cand.accelerators:
        kind_pool[kind] = len(pools)
        pools.append((pool_name(kind), [num(0.0)] * slots))
    for name in (SUBMIT, DMA_OUT):
        kind_pool[name] = len(pools)
        pools.append((name, [num(0.0)]))
    costs = [{k: num(v) for k, v in c.items()} for c in g.costs]
    if problem.policy != "availability":
        raise ValueError(f"the reference schedules by availability only, "
                         f"not {problem.policy!r}")

    def choose(t, ready):
        best = None
        for idx, kind in enumerate(g.devices[t]):
            p = kind_pool.get(kind)
            if p is None:
                continue
            start = max(ready, min(pools[p][1]))
            key = (start, 1 if kind == SMP else 0, idx)
            if best is None or key < best[0]:
                best = (key, kind)
        return best[1]

    n_tasks = len(g.creation)
    n_pred = [len(p) for p in g.pred]
    ready = [num(0.0)] * n_tasks
    placements: Dict[int, str] = {}
    busy: Dict[str, float] = {}
    heap = [(num(0.0), g.creation[u], u) for u in range(n_tasks)
            if n_pred[u] == 0]
    heapq.heapify(heap)
    makespan = num(0.0)
    done = 0
    while heap:
        rt, _, u = heapq.heappop(heap)
        done += 1
        end = None
        parent = g.cond[u]
        if parent is not None:
            if parent not in placements:
                placements[parent] = choose(parent, rt)
            if placements[parent] not in g.active[u]:
                end = rt                 # the task ran on the SMP: no DMA
        if end is None:
            if g.is_compute[u]:
                if u not in placements:
                    placements[u] = choose(u, rt)
                kind = placements[u]
            else:
                kind = g.devices[u][0]
            name, clocks = pools[kind_pool[kind]]
            t = min(clocks)
            slot = clocks.index(t)
            start = max(rt, t)
            end = start + costs[u][kind]
            clocks[slot] = end
            busy[name] = busy.get(name, num(0.0)) + (end - start)
        makespan = max(makespan, end)
        for v in g.succ[u]:
            ready[v] = max(ready[v], end)
            n_pred[v] -= 1
            if n_pred[v] == 0:
                heapq.heappush(heap, (ready[v], g.creation[v], v))
    if done != n_tasks:
        raise RuntimeError(f"{cand.name}: deadlock after {done}/{n_tasks}")
    return Result(float(makespan), {k: float(v) for k, v in busy.items()},
                  placements)


class Reference:
    """The reference over one sweep's inputs, building each distinct
    augmented graph once."""

    def __init__(self, problem: Problem,
                 reports: Mapping[Tuple[str, str], Report],
                 num: Callable = float):
        self.problem = problem
        self.reports = reports
        self.num = num
        self._graphs: Dict[Tuple, Graph] = {}

    def run(self, cand: Cand) -> Result:
        key = (cand.eligibility, tuple(sorted(k for k, _ in
                                              cand.accelerators)))
        g = self._graphs.get(key)
        if g is None:
            g = self._graphs[key] = build_graph(self.problem, self.reports,
                                                cand)
        return simulate(g, self.problem, cand, self.num)

