"""Blocked matrix multiplication (arXiv:1508.06830 Fig. 1, ``mxmBlock``)
over a ramp of matmul accelerators, as plain data.

The trace is the task sequence of the paper's loop nest ``k, i, j``,
written out from the loop bounds: no multiplication runs.  Each task reads
the blocks ``A[i][k]`` and ``B[k][j]`` and updates ``C[i][j]`` (``in(A,B)
inout(C)``, in argument order, as the OmpSs pragma of Fig. 1 does), and
its work is ``2 bs^3`` flops.  The kernel report follows the analytic HLS
model that the configuration file states (pipelined MAC lanes at II=1,
an AXI DMA bus streaming A, B and C in and C out, DSP/BRAM/LUT per lane).
"""
from __future__ import annotations

from typing import Dict, List

from harness.problem import Cand, Event, Problem, Report

KERNEL = "mxm_block"


def events(n: int, bs: int, itemsize: int) -> List[Event]:
    nb = n // bs
    tile = bs * bs * itemsize
    flops = 2.0 * float(bs) ** 3
    return [Event(KERNEL, ((("A", i, k), "in", tile),
                           (("B", k, j), "in", tile),
                           (("C", i, j), "inout", tile)), flops, 0.0)
            for k in range(nb) for i in range(nb) for j in range(nb)]


def hls_report(bs: int, hls: Dict) -> Report:
    """The block kernel's report under the configuration's HLS model."""
    itemsize = hls["itemsize"]
    unroll = hls["unroll"]
    clock = hls["clock_hz"]
    bus = hls["bus_bytes_per_cycle"]
    cycles = float(bs) ** 3 / unroll + hls["pipeline_ramp"]
    in_bytes = 3 * bs * bs * itemsize           # A, B and C stream in
    out_bytes = bs * bs * itemsize
    return Report(
        kernel=KERNEL, kind=f"fpga:mxm{bs}", compute_s=cycles / clock,
        dma_in_s=(in_bytes / bus) / clock, dma_out_s=(out_bytes / bus) / clock,
        resources=(("dsp", hls["dsp_per_lane"] * unroll),
                   ("bram_kb", in_bytes / 1024.0),
                   ("lut", hls["lut_base"] + hls["lut_per_lane"] * unroll)))


def ramp(rep: Report, bs: int, max_accs: int) -> List[Cand]:
    """1..max_accs accelerators of the one kind, FPGA-only or with the SMP
    as a fallback."""
    out = []
    for n_acc in range(1, max_accs + 1):
        for smp in (False, True):
            kinds = (rep.kind, "smp") if smp else (rep.kind,)
            out.append(Cand(
                name=f"{n_acc}acc{bs}" + ("+smp" if smp else ""),
                accelerators=((rep.kind, n_acc),),
                eligibility=((KERNEL, kinds),),
                fabric=((KERNEL, rep.kind, n_acc),)))
    return out


def build(cfg: Dict) -> Problem:
    n, bs = cfg["n"], cfg["bs"]
    rep = hls_report(bs, cfg["hls"])
    return Problem(
        name=cfg["name"], events=events(n, bs, cfg["hls"]["itemsize"]),
        reports={(KERNEL, rep.kind): rep},
        cands=ramp(rep, bs, cfg["max_accs"]),
        smp_cores=cfg["smp"]["cores"],
        task_creation_s=cfg["runtime"]["task_creation_s"],
        dma_submit_s=cfg["runtime"]["dma_submit_s"],
        smp_cost=("flops", cfg["smp"]["gflops"]),
        budget=dict(cfg["budget"]), policy=cfg["policy"])
