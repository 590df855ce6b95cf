"""Benchmark harness entry: ``python -m benchmarks.run``.

One module per paper table/figure (fig3/fig5/fig6/fig9), plus the
framework-level benches (roofline table + step estimator) that read the
dry-run artifacts.  Output: ``name,us_per_call,derived`` CSV rows, teed by
the top-level driver into bench_output.txt.

``--json [PATH]`` additionally writes a machine-readable perf-trajectory
artifact (default ``BENCH_simulator.json`` at the repo root): every CSV row
plus the fig6 sweep metrics — candidates/sec for each engine (including the
``sweep_batch_*`` lockstep rows — cold and ``sweep_batch_warm``, the
repeat sweep over a warm dispatch-order library with its rescue counters —
and the ``sweep_jax_*`` compiled-scan rows), cache hit rates,
fast-vs-reference and disk-rerank speedups — so future PRs can diff the
numbers instead of eyeballing logs.  ``--baseline PATH`` turns the run into a regression gate:
every throughput-like metric recorded in the baseline artifact is compared
against this run (the warm-sweep throughput and its
``sweep_batch_warm_vs_cold_speedup`` ratio are gated like every other
``sweep_*`` metric) and the process exits non-zero when any drops more than
20%.  ``--only fig6`` (etc.) restricts the run; CI uses ``--only fig6
--smoke`` as the smoke invocation.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]

# Regression tolerance for --baseline: a recorded throughput/speedup may
# drop by at most this fraction (seconds metrics may grow by the inverse).
BASELINE_TOLERANCE = 0.20


def _gated_metric(key: str) -> bool:
    """Only the sweep-trajectory metrics are load-bearing enough to gate
    on: the 200-candidate rows run hundreds of milliseconds and the
    normalized batch ratio is machine-speed invariant.  The sub-50 ms
    micro-section metrics (``explore_engine_*`` etc.) swing far beyond any
    sane tolerance on shared boxes and are reported informationally only.
    """
    return (key.startswith("sweep_") and not key.endswith("_stats")) \
        or key.startswith("candidates_per_sec") \
        or key == "batch_vs_pr2_fast_speedup" \
        or key == "jax_megabatch_vs_chunked_speedup" \
        or key == "serve_coalesced_8c_speedup"


def check_baseline(metrics: dict, baseline_path: Path,
                   tolerance: float = BASELINE_TOLERANCE) -> int:
    """Compare this run's fig6 metrics against a recorded trajectory.

    Absolute metrics are compared *at equal machine speed*: the pr1 row
    exercises engine code that has not changed since PR 1, so the ratio of
    its recorded and measured times is the machine/load factor between the
    two runs, and every absolute throughput/seconds metric is scaled by it
    before the tolerance test (the pr1 yardstick itself is reported but
    never flagged).  Higher-is-better metrics regress when the scaled
    value drops below ``(1 - tolerance) ×`` the baseline; ``*_seconds``
    metrics when they grow beyond the inverse.  Ratio metrics
    (``*_speedup``) are machine-invariant already and compare unscaled.
    Returns the number of regressions.
    """
    base_doc = json.loads(baseline_path.read_text())
    # the serve-load block records its own metric namespace; fold it in so
    # its speedup ratio rides the same gate (keys are disjoint by prefix)
    base = {**base_doc.get("simulator", {}), **base_doc.get("serve", {})}
    # comparability guards: a run that never produced the fig6 sweep (wrong
    # --only, crashed module) or ran it at a different candidate count
    # (--smoke vs full) must FAIL the gate, not silently compare nothing
    old_nc, new_nc = base.get("sweep_candidates"), \
        metrics.get("sweep_candidates")
    if new_nc is None:
        print("# baseline: this run produced no fig6 sweep metrics — "
              "nothing to gate on (run with `--only fig6` or the full "
              "suite)", flush=True)
        return 1
    if old_nc is not None and old_nc != new_nc:
        print(f"# baseline: sweep sizes differ ({old_nc} recorded vs "
              f"{new_nc} measured — e.g. --smoke vs full run); metrics are "
              f"not comparable", flush=True)
        return 1
    old_pr1 = base.get("sweep_pr1_cached_seconds")
    new_pr1 = metrics.get("sweep_pr1_cached_seconds")
    slowdown = (new_pr1 / old_pr1) if old_pr1 and new_pr1 else 1.0
    print(f"# baseline machine-speed factor (pr1 yardstick): "
          f"{slowdown:.2f}x {'slower' if slowdown >= 1 else 'faster'} "
          f"than the recorded run", flush=True)
    regressions = 0
    compared = 0
    for key, old in sorted(base.items()):
        new = metrics.get(key)
        if not isinstance(old, (int, float)) or not isinstance(new,
                                                               (int, float)):
            continue
        if not _gated_metric(key):
            continue
        yardstick = key in ("sweep_pr1_cached_seconds",
                            "candidates_per_sec_pr1")
        if key.endswith("_seconds"):
            bad = old > 0 and (new / slowdown) > old / (1.0 - tolerance)
            direction = "slower"
        elif key.endswith("_speedup"):
            bad = new < old * (1.0 - tolerance)
            direction = "dropped"
        elif key.startswith("candidates_per_sec"):
            bad = (new * slowdown) < old * (1.0 - tolerance)
            direction = "dropped"
        else:
            continue
        bad = bad and not yardstick
        compared += 1
        mark = "yardstick" if yardstick else \
            ("REGRESSION" if bad else "ok")
        print(f"# baseline {key}: {old:.4g} -> {new:.4g} [{mark}]",
              flush=True)
        if bad:
            regressions += 1
            print(f"#   {key} {direction} more than {tolerance:.0%} at "
                  f"equal machine speed vs {baseline_path}", flush=True)
    if compared == 0:
        print(f"# baseline: no gated metric present in both runs — "
              f"{baseline_path} is not a comparable trajectory", flush=True)
        return 1
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const=str(REPO_ROOT / "BENCH_simulator.json"),
                    default=None, metavar="PATH",
                    help="write the BENCH_simulator.json perf artifact")
    ap.add_argument("--only", nargs="+", default=None,
                    choices=["fig3", "fig5", "fig6", "fig9", "step",
                             "serve", "roofline"],
                    metavar="NAME", help="run only these modules "
                    "(fig3 fig5 fig6 fig9 step serve roofline)")
    ap.add_argument("--smoke", action="store_true",
                    help="pass smoke mode to modules that support it")
    ap.add_argument("--baseline", metavar="PATH", default=None,
                    help="compare fig6 sweep metrics against a recorded "
                    "BENCH_simulator.json; exit non-zero if any recorded "
                    "throughput drops more than the tolerance")
    ap.add_argument("--baseline-tolerance", type=float,
                    default=BASELINE_TOLERANCE, metavar="FRAC",
                    help="allowed fractional drop before --baseline fails "
                    "(default %(default)s)")
    args = ap.parse_args(argv)

    from repro.core.xlacache import enable_persistent_cache
    enable_persistent_cache()
    from benchmarks import (fig3_dma_overlap, fig5_matmul,
                            fig6_analysis_time, fig9_cholesky,
                            serve_load, step_estimator)

    # serve first: its throughput ratio is thread-scheduling sensitive,
    # and the jax modules leave XLA worker threads resident for the rest
    # of the process
    modules = {
        "serve": serve_load,
        "fig3": fig3_dma_overlap, "fig5": fig5_matmul,
        "fig6": fig6_analysis_time, "fig9": fig9_cholesky,
        "step": step_estimator,
    }
    selected = args.only if args.only else list(modules) + ["roofline"]

    failures = 0
    rows = []
    for key in selected:
        if key == "roofline":
            continue
        mod = modules[key]
        print(f"# --- {mod.__name__} ---", flush=True)
        try:
            kwargs = {}
            if args.smoke and mod is fig6_analysis_time:
                kwargs = {"n": 128, "sweep": 24, "smoke": True}
            elif args.smoke and mod is serve_load:
                kwargs = {"smoke": True}
            for name, us, derived in mod.run(**kwargs):
                rows.append([name, us, derived])
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()

    if "roofline" in selected:
        print("# --- roofline table (benchmarks/artifacts/roofline.md) ---",
              flush=True)
        try:
            from benchmarks import roofline_table
            roofline_table.main()
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()

    if args.baseline:
        print(f"# --- baseline regression check vs {args.baseline} ---",
              flush=True)
        try:
            failures += check_baseline({**fig6_analysis_time.METRICS,
                                        **serve_load.METRICS},
                                       Path(args.baseline),
                                       tolerance=args.baseline_tolerance)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()

    if args.json:
        artifact = {
            "bench": "simulator",
            "unix_time": time.time(),
            "smoke": bool(args.smoke),
            "failures": failures,
            "simulator": dict(fig6_analysis_time.METRICS),
            "serve": dict(serve_load.METRICS),
            "rows": rows,
        }
        Path(args.json).write_text(json.dumps(artifact, indent=2) + "\n")
        print(f"# wrote {args.json}", flush=True)
    return failures


if __name__ == "__main__":
    sys.exit(main())
