"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the TPU chips the
cell asks for.  The run sets up (imports, device, inputs, warm-up sweeps
until nothing new compiles), measures a closed loop of sweeps for
``--seconds`` (the sweep running when the time is up runs to its end),
then checks every sweep of the window against the plain reference.  With
``--trace 1`` the run reports the cell's per-layer metrics, with
``--trace 0`` its end-to-end metrics.  Traced, JAX's profiler records the
window's first sweep, in memory; the counters cover the whole window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``, then ``check``, the compared numbers beside their limits.
Standard error ends with the same numbers, one per line.  Off a TPU, with
fewer chips than the cell asks for, or on a device the peak table does not
know, the run exits 2 and prints no result.

JAX's persistent compilation cache lives in ``.jax_cache/`` at the root of
the checkout, so only a cell's first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"error: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None, require_device: bool = True, t_start: float = None,
         bench: dict = None) -> int:
    """``require_device=False`` and ``bench`` (a stand-in for
    ``BENCHMARK.json``) let the tests drive a run on the CPU at a small
    size."""
    args = parse(argv)
    t_start = T_START if t_start is None else t_start
    try:
        bench = spec.benchmark() if bench is None else bench
        cell = spec.workload(bench, args.workload)
        cfg = spec.config(bench, cell["config"])
        traffic_doc = spec.traffic(cell["traffic"])
    except spec.SpecError as exc:
        return fail(str(exc))
    chips = int(cell["chips"])

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    from harness import device
    if require_device:
        try:
            dev = device.require_tpu(chips)
        except device.DeviceError as exc:
            return fail(str(exc))
        if spec.peaks(dev["kind"]) is None:
            return fail(f"no peak table for device kind {dev['kind']!r}")
    else:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(jax.devices())}

    from harness import check, program, tracing
    from harness.traffic import Traffic
    from repro.core.xlacache import enable_persistent_cache
    enable_persistent_cache()
    counter = device.CompileCounter()
    problem = spec.problem(cfg)
    inp = program.inputs(problem)
    traffic = Traffic(traffic_doc, args.seed)
    feasible = [c.name for c in problem.cands if problem.feasible(c)]

    # warm-up: the traffic file's own factors, until a sweep compiles
    # nothing new
    warm = 0
    with jax.profiler.TraceAnnotation("warmup"):
        for f in traffic.warmup_factors():
            before = counter.compiles
            program.sweep(problem, inp, problem.scaled_reports(
                f, traffic.fields), lambda ranked: ())
            warm += 1
            if warm >= traffic.warmup["min_sweeps"] \
                    and counter.compiles == before:
                break
    setup = counter.snapshot()
    setup_s = time.perf_counter() - t_start

    # traced, the profiler records the window's first sweep: a sweep's
    # device trace runs to some hundred MB
    tracer = tracing.Tracer() if args.trace else None
    if tracer is not None:
        tracer.start()
    xspace = None
    sweeps, samples = [], []
    compiles0 = counter.compiles
    t0 = time.perf_counter()
    while True:
        i = len(sweeps)
        f = traffic.factor(i)

        def keep(ranked, i=i):
            samples.append(check.sample(traffic.seed, i, ranked,
                                        feasible, traffic.check))
            return samples[-1]

        with contextlib.ExitStack() as spans:
            if tracer is not None and i == 0:
                spans.enter_context(
                    jax.profiler.TraceAnnotation(tracing.WINDOW))
            with jax.profiler.TraceAnnotation(tracing.SWEEP):
                sw = program.sweep(problem, inp, problem.scaled_reports(
                    f, traffic.fields), keep)
        if tracer is not None and i == 0:
            xspace = tracer.stop()
        sweeps.append((f, sw))
        if time.perf_counter() - t0 >= args.seconds:
            break
    window_s = time.perf_counter() - t0
    window_compiles = counter.compiles - compiles0
    trace = tracing.read(xspace, chips) if xspace else None
    mem = device.memory_peak_bytes(chips) if require_device else 0
    gc.collect()

    gaps = check.compare(problem, traffic.fields, sweeps, samples)
    nums = check.readings(gaps)
    ok = check.correct(gaps)

    # what the metric readers read
    ctx = types.SimpleNamespace(
        config=cfg, sweeps=[s for _, s in sweeps], window_s=window_s,
        setup_s=setup_s, setup_compiles=setup,
        window_compiles=window_compiles, trace=trace)
    metrics = {}
    for m in spec.metrics_for(bench, bool(args.trace)):
        value = spec.reader(m)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted = sum(len(s.statuses) for _, s in sweeps)
    failed = sum(1 for _, s in sweeps for st in s.statuses.values()
                 if st not in ("ok", "infeasible", "pruned"))
    dev = dict(dev, memory_peak_bytes=mem)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["check"] = nums
    print(f"info: {len(sweeps)} sweeps in {window_s:.3f} s after "
          f"{warm} warm-up sweeps; {gaps.lanes} lanes compared, "
          f"{gaps.covered} of {len(feasible)} candidates at least once; "
          f"largest gap: {gaps.where or 'none'}", file=sys.stderr)
    for name, v in nums.items():
        print(f"{name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
