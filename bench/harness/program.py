"""The system under test: one sweep through ``Explorer(engine="jax")``.

This is the only module of the benchmark that imports the program.  It
turns the plain records of :mod:`harness.problem` into the program's input
objects and runs one sweep the way a co-design engineer does: a fresh
``Explorer`` over the trace and this sweep's kernel reports, then
``explore`` over every candidate.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys
from typing import Dict, List, Mapping, Tuple

from .problem import (DMA_OUT, SMP, SUBMIT, Problem, Report, Sweep,
                      pool_name)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core.augment import Eligibility            # noqa: E402
from repro.core.devices import (DevicePool, SharedResource,  # noqa: E402
                                SystemConfig)
from repro.core.explore import Candidate, Explorer    # noqa: E402
from repro.core.hlsreport import KernelReport          # noqa: E402
from repro.core.trace import Trace, TraceEvent         # noqa: E402

ENGINE = "jax"


def _flops_seconds(gflops: float, event) -> float:
    return event.flops / (gflops * 1e9)


@dataclasses.dataclass
class Inputs:
    """The program's view of a configuration, built once per run: the
    trace and the candidates do not change from sweep to sweep."""

    trace: Trace
    cands: List[Candidate]
    smp_seconds_fn: object


def kernel_report(rep: Report) -> KernelReport:
    return KernelReport(kernel=rep.kernel, device_kind=rep.kind,
                        compute_s=rep.compute_s, dma_in_s=rep.dma_in_s,
                        dma_out_s=rep.dma_out_s,
                        resources=dict(rep.resources))


def reports_map(reports: Mapping[Tuple[str, str], Report]
                ) -> Dict[Tuple[str, str], KernelReport]:
    return {k: kernel_report(r) for k, r in reports.items()}


def inputs(problem: Problem) -> Inputs:
    trace = Trace(events=[
        TraceEvent(index=i, name=ev.name, created_at=0.0,
                   elapsed_smp=ev.elapsed_smp,
                   accesses=[tuple(a) for a in ev.accesses],
                   devices=("fpga", SMP), flops=ev.flops)
        for i, ev in enumerate(problem.events)])
    cands = []
    for c in problem.cands:
        system = SystemConfig(
            name=c.name,
            pools=[DevicePool(SMP, (SMP,), problem.smp_cores)]
            + [DevicePool(pool_name(k), (k,), n) for k, n in c.accelerators],
            shared=[SharedResource(SUBMIT, 1), SharedResource(DMA_OUT, 1)],
            overlap_inputs=True, overlap_outputs=False,
            task_creation_cost=problem.task_creation_s,
            dma_submit_cost=problem.dma_submit_s)
        fabric = [(kernel_report(problem.reports[(kernel, kind)]), n)
                  for kernel, kind, n in c.fabric]
        cands.append(Candidate(name=c.name, system=system,
                               eligibility=Eligibility(dict(c.eligibility)),
                               fabric=fabric))
    model, gflops = problem.smp_cost
    fn = functools.partial(_flops_seconds, gflops) if model == "flops" \
        else None
    return Inputs(trace, cands, fn)


def sweep(problem: Problem, inp: Inputs,
          reports: Mapping[Tuple[str, str], Report], keep) -> Sweep:
    """One sweep; ``keep(ranked_names)`` names the candidates whose
    schedule-free results the check will read."""
    ex = Explorer(inp.trace, reports_map(reports), policy=problem.policy,
                  smp_seconds_fn=inp.smp_seconds_fn, budget=problem.budget,
                  engine=ENGINE)
    res = ex.explore(inp.cands)
    ranked = [o.name for o in res.ranked]
    wanted = keep(ranked)
    return Sweep(
        statuses={o.name: o.status for o in res.outcomes},
        makespans={o.name: o.makespan_s for o in res.outcomes
                   if o.status == "ok"},
        ranked=ranked,
        sims={n: res.estimates[n].sim for n in wanted
              if n in res.estimates},
        engine=ex.engine, demotions=ex.stats.engine_demotions,
        lockstep_lanes=ex.batch_stats.lockstep_lanes,
        simulated=sum(1 for o in res.outcomes if o.status != "infeasible"))
