"""Readings that the limit of ``correct`` is set from, for one cell, in one
process on the chip:

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 51 --out <dir>

* for each ``--seeds`` seed, one whole run of the cell as ``run.py`` makes
  it (its compared numbers, its lane counts);
* for each ``--control-seeds`` seed, the control: the plain reference
  computed in binary32, put in the program's place for as many sweeps as
  the program's runs had in their windows, sampled and compared in the
  same way.

One JSON line per reading goes to ``<out>/<cell>.jsonl``.  The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import check, spec  # noqa: E402
from harness.traffic import Traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.workload + ".jsonl")
    sweeps = []
    with open(path, "a") as log:
        for seed in [int(s) for s in args.seeds.split(",") if s]:
            out, err = io.StringIO(), io.StringIO()
            t = time.perf_counter()
            with contextlib.redirect_stdout(out), \
                    contextlib.redirect_stderr(err):
                rc = run.main(["--workload", args.workload, "--seed",
                               str(seed), "--seconds", str(args.seconds),
                               "--trace", "0"], t_start=t)
            if rc != 0:
                print(err.getvalue(), file=sys.stderr)
                return rc
            res = json.loads(out.getvalue().strip().splitlines()[-1])
            info = [ln for ln in err.getvalue().splitlines()
                    if ln.startswith("info:")]
            n = int(info[-1].split()[1]) if info else 0
            sweeps.append(n)
            doc = {"kind": "program", "seed": seed, "sweeps": n,
                   "correct": res["correct"], "check": res["check"],
                   "metrics": res["metrics"], "info": info}
            log.write(json.dumps(doc) + "\n")
            log.flush()
            print(json.dumps(doc), flush=True)
        bench = spec.benchmark()
        cell = spec.workload(bench, args.workload)
        problem = spec.problem(spec.config(bench, cell["config"]))
        n_sweeps = max(sweeps) if sweeps else 3
        for seed in [int(s) for s in args.control_seeds.split(",") if s]:
            t = time.perf_counter()
            gaps = check.control(problem, Traffic(
                spec.traffic(cell["traffic"]), seed), n_sweeps)
            doc = {"kind": "control", "seed": seed, "sweeps": n_sweeps,
                   "correct": check.correct(gaps),
                   "check": check.readings(gaps),
                   "gaps": {k: getattr(gaps, k) for k in
                            ("makespan", "busy", "placement", "rank",
                             "outcome", "lanes", "covered", "where")},
                   "seconds": time.perf_counter() - t}
            log.write(json.dumps(doc) + "\n")
            log.flush()
            print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
