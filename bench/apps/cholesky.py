"""Tiled left-looking Cholesky (arXiv:1508.06830 Fig. 4) over the Fig. 9
co-design grid, as plain data.

The trace is the task sequence of the paper's loop nest, written out from
the loop bounds: no factorisation runs.  Each task declares the tiles it
reads and writes (``in`` before ``inout``, in argument order, as the
OmpSs pragmas of Fig. 4 do) and its work in flops.  The kernel reports
follow the analytic HLS model that the configuration file states
(pipelined MAC lanes at II=1, an AXI DMA bus, DSP/BRAM/LUT per lane).
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

from harness.problem import Cand, Event, Problem, Report

KERNELS = ("dgemm", "dsyrk", "dtrsm")


def events(n: int, bs: int) -> List[Event]:
    nb = n // bs
    b = float(bs)
    tile = bs * bs * 8
    flops = {"dsyrk": b ** 3 + b ** 2, "dpotrf": b ** 3 / 3.0,
             "dgemm": 2.0 * b ** 3, "dtrsm": b ** 3 + b ** 2}
    out: List[Event] = []

    def task(name, *accesses):
        out.append(Event(name, tuple((key, d, tile) for key, d in accesses),
                         flops[name], 0.0))

    for k in range(nb):
        for j in range(k):
            task("dsyrk", ((j, k), "in"), ((k, k), "inout"))
        task("dpotrf", ((k, k), "inout"))
        for i in range(k + 1, nb):
            for j in range(k):
                task("dgemm", ((j, i), "in"), ((j, k), "in"),
                     ((k, i), "inout"))
        for i in range(k + 1, nb):
            task("dtrsm", ((k, k), "in"), ((k, i), "inout"))
    return out


def hls_report(op: str, bs: int, full: bool, hls: Dict) -> Report:
    """One tile kernel's report under the configuration's HLS model."""
    b = float(bs)
    macs = {"dgemm": b ** 3, "dsyrk": b ** 3 / 2.0 + bs * bs / 2.0,
            "dtrsm": b ** 3 / 2.0 + bs * bs / 2.0}[op]
    n_in = {"dgemm": 3, "dsyrk": 2, "dtrsm": 2}[op]
    itemsize = hls["itemsize"]
    unroll = hls["unroll_full"] if full else hls["unroll_shared"]
    clock = hls["clock_hz"]
    bus = hls["bus_bytes_per_cycle"]
    cycles = macs / unroll + hls["pipeline_ramp"]
    in_bytes = n_in * bs * bs * itemsize
    out_bytes = bs * bs * itemsize
    kind = f"fpga:{op}" + ("FR" if full else f"{bs}")
    return Report(
        kernel=op, kind=kind, compute_s=cycles / clock,
        dma_in_s=(in_bytes / bus) / clock, dma_out_s=(out_bytes / bus) / clock,
        resources=(("dsp", hls["dsp_per_lane"] * unroll),
                   ("bram_kb", in_bytes / 1024.0),
                   ("lut", hls["lut_base"] + hls["lut_per_lane"] * unroll)))


def grid(reps: Dict[str, Dict[bool, Report]], max_accs: int,
         subsets: Optional[Sequence[Sequence[str]]] = None) -> List[Cand]:
    """Accelerator counts 1..max_accs for every subset of the FPGA-able
    kernels that the configuration names (by default every non-empty one),
    shared or full resources, SMP fallback on or off."""
    out = []
    if subsets is None:
        subsets = [s for r in range(1, len(KERNELS) + 1)
                   for s in itertools.combinations(KERNELS, r)]
    for subset in subsets:
        for counts, full, smp in itertools.product(
                itertools.product(range(1, max_accs + 1),
                                  repeat=len(subset)),
                (False, True), (False, True)):
            used = [(op, reps[op][full], c) for op, c in zip(subset, counts)]
            elig = {op: ("smp",) for op in ("dpotrf",) + KERNELS}
            for op, rep, _ in used:
                elig[op] = (rep.kind,) + (("smp",) if smp else ())
            name = "+".join(f"{op}{c}" for op, c in zip(subset, counts)) \
                + ("/full" if full else "/shared") + ("/smp" if smp else "")
            out.append(Cand(
                name=name,
                accelerators=tuple((rep.kind, c) for _, rep, c in used),
                eligibility=tuple(elig.items()),
                fabric=tuple((op, rep.kind, c) for op, rep, c in used)))
    return out


def build(cfg: Dict) -> Problem:
    n, bs = cfg["n"], cfg["bs"]
    reps = {op: {full: hls_report(op, bs, full, cfg["hls"])
                 for full in (False, True)} for op in KERNELS}
    reports: Dict[Tuple[str, str], Report] = {
        (op, r.kind): r for op in KERNELS for r in reps[op].values()}
    return Problem(
        name=cfg["name"], events=events(n, bs), reports=reports,
        cands=grid(reps, cfg["max_accs_per_kernel"], cfg.get("subsets")),
        smp_cores=cfg["smp"]["cores"],
        task_creation_s=cfg["runtime"]["task_creation_s"],
        dma_submit_s=cfg["runtime"]["dma_submit_s"],
        smp_cost=("flops", cfg["smp"]["gflops"]),
        budget=dict(cfg["budget"]), policy=cfg["policy"])
