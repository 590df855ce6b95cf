"""Where a sweep's time goes, by the program's own spans and scan counters.

    python bench/layers.py --workload <cell> --seeds 1,2

runs one cell's set-up as ``run.py`` does (warm-up until nothing new
compiles), then for each seed the first sweep of that seed's window
twice, with the same cost factor: once untraced and once under JAX's
profiler.  Each prints one JSON line: both sweeps' wall times, the traced
sweep's span self times, its device-idle time by innermost program span
(:mod:`harness.spans`), the spans it emitted, its scan counters
(``BatchStats``), and the layer metrics read from them.  A last line gives
the cost of one span outside a profiler session.

The device-resident step inputs are dropped before each sweep, so both
sweeps upload them, as every sweep of a window does (its cost factors
differ from sweep to sweep).  Off a TPU the run exits 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import timeit

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

#: How many spans the span-cost loop enters.
SPAN_LOOPS = 200_000


def metrics(self_s, stats) -> dict:
    """The layer metrics of one sweep, from its span self times (s) and
    its ``BatchStats``."""
    def s(*names):
        return sum(self_s.get(n, 0.0) for n in names)

    out = {"graph_build_s": s("explore.graph"),
           "exact_path_s": s("replay.exact"),
           "scan_prep_s": s("jaxsim.prep", "jaxsim.transfer"),
           "assemble_s": s("replay.assemble", "explore.rank"),
           "h2d_mb_per_sweep": stats.h2d_bytes / 1e6}
    if stats.scan_lane_steps:
        out["scan_lane_fill"] = \
            100.0 * stats.scan_kept_lane_steps / stats.scan_lane_steps
    return out


def main(argv=None, require_device: bool = True, bench: dict = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the sweeps to trace")
    args = ap.parse_args(argv)
    bench = spec.benchmark() if bench is None else bench
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    chips = int(cell["chips"])

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(spec.ROOT, ".jax_cache"))
    import jax
    from harness import device, program, spans, tracing
    from harness.traffic import Traffic
    if require_device:
        try:
            device.require_tpu(chips)
        except device.DeviceError as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 2
    from repro.core import jaxsim
    from repro.core.explore import Explorer
    from repro.core.spans import span
    from repro.core.xlacache import enable_persistent_cache
    enable_persistent_cache()

    problem = spec.problem(cfg)
    inp = program.inputs(problem)
    doc = spec.traffic(cell["traffic"])
    warm = Traffic(doc, 0)

    def sweep(factor):
        jaxsim._DEV_XS_CACHE.clear()
        ex = Explorer(inp.trace, program.reports_map(problem.scaled_reports(
                          factor, warm.fields)),
                      policy=problem.policy,
                      smp_seconds_fn=inp.smp_seconds_fn,
                      budget=problem.budget, engine=program.ENGINE)
        ex.explore(inp.cands)
        return ex.batch_stats

    counter = device.CompileCounter()
    for i, f in enumerate(warm.warmup_factors()):
        before = counter.compiles
        sweep(f)
        if i + 1 >= warm.warmup["min_sweeps"] and counter.compiles == before:
            break

    for seed in (int(s) for s in args.seeds.split(",")):
        f = Traffic(doc, seed).factor(0)
        compiles = counter.compiles
        t0 = time.perf_counter()
        sweep(f)
        untraced_s = time.perf_counter() - t0
        tracer = tracing.Tracer()
        tracer.start()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracing.WINDOW), \
                jax.profiler.TraceAnnotation(tracing.SWEEP):
            stats = sweep(f)
        traced_s = time.perf_counter() - t0
        xspace = tracer.stop()
        got = spans.read(xspace, chips)
        window = tracing.read(xspace, chips)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "factor": f,
            "untraced_s": untraced_s, "traced_s": traced_s,
            "metrics": metrics(got.get("self_s", {}), stats),
            "busy_s": window.get("busy_s"), **got,
            "counters": {k: v for k, v in stats.as_dict().items()
                         if k.startswith(("scan_", "h2d_", "lockstep_"))},
            "compiles": counter.compiles - compiles}), flush=True)

    def one():
        with span("explore.sweep"):
            pass
    cost = timeit.timeit(one, number=SPAN_LOOPS) / SPAN_LOOPS
    print(json.dumps({"span_cost_us": cost * 1e6}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
