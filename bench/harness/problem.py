"""The inputs of one sweep as plain data, shared by the program and the
reference.

A configuration's app module (``bench/apps/<app>.py``) turns the
configuration file into a :class:`Problem`: the task trace of the
application, the per-kernel cost reports, the candidate systems and the
runtime constants.  The traffic scales the reports for each sweep
(:meth:`Problem.scaled_reports`).  Nothing here imports the program:
:mod:`harness.program` turns these records into the program's own input
objects, and :mod:`harness.reference` simulates them on its own.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

#: Kinds that name the shared, serialising resources of the Zynq model.
SUBMIT = "submit"
DMA_OUT = "dma_out"
SMP = "smp"


@dataclasses.dataclass(frozen=True)
class Event:
    """One task instance of the sequential program, in creation order."""

    name: str
    accesses: Tuple[Tuple[object, str, int], ...]   # (region key, dir, bytes)
    flops: float
    elapsed_smp: float


@dataclasses.dataclass(frozen=True)
class Report:
    """The HLS-style cost report of one kernel on one accelerator kind."""

    kernel: str
    kind: str
    compute_s: float
    dma_in_s: float
    dma_out_s: float
    resources: Tuple[Tuple[str, float], ...]

    def scaled(self, factor: float, fields: Sequence[str]) -> "Report":
        return dataclasses.replace(
            self, **{f: getattr(self, f) * factor for f in fields})


@dataclasses.dataclass(frozen=True)
class Cand:
    """One candidate system: accelerator slots, eligibility, fabric."""

    name: str
    accelerators: Tuple[Tuple[str, int], ...]       # (kind, slots), n > 0
    eligibility: Tuple[Tuple[str, Tuple[str, ...]], ...]  # kernel -> kinds
    fabric: Tuple[Tuple[str, str, int], ...]        # (kernel, kind, count)


@dataclasses.dataclass
class Problem:
    """Everything one sweep of a configuration needs, as plain data."""

    name: str
    events: List[Event]
    reports: Dict[Tuple[str, str], Report]
    cands: List[Cand]
    smp_cores: int
    task_creation_s: float
    dma_submit_s: float
    #: ``("flops", gflops)``: an SMP task takes flops / (gflops * 1e9)
    #: seconds; ``("mean", None)``: the kernel's mean measured time.
    smp_cost: Tuple[str, Optional[float]]
    budget: Dict[str, float]
    policy: str = "availability"

    def scaled_reports(self, factor: float, fields: Sequence[str]
                       ) -> Dict[Tuple[str, str], Report]:
        return {k: r.scaled(factor, fields) for k, r in self.reports.items()}

    def smp_seconds(self, ev: Event) -> Optional[float]:
        """The SMP cost of one event under a ``flops`` model, else None."""
        model, gflops = self.smp_cost
        if model == "flops":
            return ev.flops / (gflops * 1e9)
        if model == "mean":
            return None
        raise ValueError(f"unknown SMP cost model {model!r}")

    def feasible(self, cand: Cand) -> bool:
        """Sum of the fabric's resources within the budget."""
        usage: Dict[str, float] = {}
        for kernel, kind, count in cand.fabric:
            for res, amount in self.reports[(kernel, kind)].resources:
                usage[res] = usage.get(res, 0.0) + amount * count
        return all(usage.get(res, 0.0) <= cap
                   for res, cap in self.budget.items())


@dataclasses.dataclass
class Sweep:
    """What one sweep returned, reduced to what the check and the metrics
    read: each candidate's status and makespan, the schedule-free results
    (busy and placements) of the candidates the check samples, and the
    engine's counters."""

    statuses: Dict[str, str]
    makespans: Dict[str, float]
    ranked: List[str]
    sims: Dict[str, object]
    engine: str
    demotions: int
    lockstep_lanes: int
    simulated: int


def pool_name(kind: str) -> str:
    """The device pool that holds an accelerator kind's slots."""
    return kind.replace("fpga:", "acc_")
