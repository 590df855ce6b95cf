"""Smoke test of the estimator's device path on one TPU chip.

Drives the jax lockstep scan through the entry points a user calls —
``Explorer(engine="jax")`` and the ``sweepd`` sweep server — at the sizes
users run, and checks every answer against the exact ``batch`` engine:

* phase A: the paper's application, tiled Cholesky at ``n=2048, bs=128``
  (816 kernel tasks), ranked over a 496-point Fig. 9-style co-design grid;
* phase B: a 256-lane lockstep sweep, the ``sweepd`` request
  ``{"trace": "synth:1024", "accs": "1-128"}``;
* phase C: three ``POST /sweep`` requests of phase B's shape to a sweep
  server running in this process.

Run from the repository root, with no arguments::

    python chip_smoke.py

Everything runs in this one process, which holds the chip; it starts no
child process.  JAX's persistent compilation cache goes to
``$JAX_COMPILATION_CACHE_DIR``, or to ``.jax_cache/`` in the checkout.

The script exits non-zero, without the result line, unless JAX's first
device is a TPU and every check passes: the jax engine must finish every
sweep itself (no demotion to another engine) and rank within the jax tier
of the batch engine.  Lines marked "informational" are not metrics.  The
last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: The three FPGA-able Cholesky kernels (dpotrf stays on the SMP).
KERNELS = ("dgemm", "dsyrk", "dtrsm")
#: Phase B's and phase C's request, from the sweepd protocol.
SWEEP_REQUEST = {"trace": "synth:1024", "accs": "1-128"}
#: Lockstep lanes phase B must keep on the device, of its 256.
MIN_DEVICE_LANES = 200
SERVED_REQUESTS = 3
#: Each served request's latency budget: wide, so that a slow chip shows
#: as slow phase C seconds, never as candidates quarantined at a deadline.
REQUEST_BUDGET_S = 600.0


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def info(label: str, **values) -> None:
    print(f"informational {label}: {json.dumps(values, sort_keys=True)}",
          flush=True)


def require_tpu() -> dict:
    """JAX's device, as the result line reports it; raises off a TPU."""
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"device: {json.dumps(dev)}", flush=True)
    check(dev["platform"] == "tpu",
          f"JAX's first device is {dev['platform']!r}, not a TPU")
    return dev


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events (a cache hit still records a short compile)."""

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles,
                "compile_s": self.compile_s,
                "persistent_cache_hits": self.cache_hits}


def lanes(ex) -> dict:
    b = ex.batch_stats
    return {"lockstep": b.lockstep_lanes, "diverged": b.diverged_lanes,
            "small_group": b.small_group_lanes,
            "serial_fallback": b.serial_fallback_lanes,
            "reference": b.reference_lanes}


def check_jax_ran(ex, phase: str) -> None:
    check(ex.engine == "jax",
          f"{phase}: the sweep finished on {ex.engine!r}, not jax")
    check(ex.stats.engine_demotions == 0,
          f"{phase}: {ex.stats.engine_demotions} engine demotion(s)")


def check_rankings(got, ref, phase: str) -> None:
    """Jax ranking against the exact batch ranking, at the jax tier."""
    from repro.core.replay import JAX_RTOL, rankings_equivalent
    got_names = [o.name for o in got.ranked]
    ref_names = [o.name for o in ref.ranked]
    spans = {o.name: o.makespan_s for o in ref.ranked}
    check(got_names and rankings_equivalent(got_names, ref_names, spans,
                                            JAX_RTOL),
          f"{phase}: the jax ranking differs from the batch ranking")
    check(sorted(o.name for o in got.outcomes if o.status != "ok")
          == sorted(o.name for o in ref.outcomes if o.status != "ok"),
          f"{phase}: jax and batch disagree on which candidates are out")


def cholesky_grid(bs: int):
    """The Fig. 9-style grid: per-kernel accelerator counts 1-4 over every
    non-empty subset of the FPGA-able kernels, shared or full resources,
    SMP fallback on or off — 496 candidates, built the way
    ``repro.apps.cholesky.candidates`` builds the paper's six."""
    from repro.apps.cholesky import hls_reports
    from repro.core.augment import Eligibility
    from repro.core.devices import zynq_system
    from repro.core.explore import Candidate
    reps = hls_reports(bs)
    subsets = [s for r in range(1, len(KERNELS) + 1)
               for s in itertools.combinations(KERNELS, r)]
    out = []
    for subset in subsets:
        for counts, full, smp in itertools.product(
                itertools.product(range(1, 5), repeat=len(subset)),
                (False, True), (False, True)):
            used = {op: (reps[op][full], c) for op, c in zip(subset, counts)}
            elig = {op: ("smp",) for op in ("dpotrf",) + KERNELS}
            for op, (rep, _) in used.items():
                elig[op] = (rep.device_kind,) + (("smp",) if smp else ())
            name = "+".join(f"{op}{c}" for op, c in zip(subset, counts)) \
                + ("/full" if full else "/shared") + ("/smp" if smp else "")
            out.append(Candidate(
                name=name,
                system=zynq_system(name, {rep.device_kind: c
                                          for rep, c in used.values()}),
                eligibility=Eligibility(elig),
                fabric=list(used.values())))
    return out


def phase_cholesky(n: int = 2048, bs: int = 128) -> dict:
    """Phase A: the paper's application at real size, jax against batch."""
    from repro.apps.cholesky import report_map, trace_cholesky
    from repro.core import Explorer, a9_smp_seconds
    trace = trace_cholesky(n=n, bs=bs, verify=False)
    reports = report_map(bs=bs)
    cands = cholesky_grid(bs)
    a9 = a9_smp_seconds("float64")
    ex = Explorer(trace, reports, smp_seconds_fn=a9, engine="jax")
    got = ex.explore(cands)
    check_jax_ran(ex, "phase A")
    ref = Explorer(trace, reports, smp_seconds_fn=a9,
                   engine="batch").explore(cands)
    check_rankings(got, ref, "phase A")
    out = {"kernel_tasks": len(trace), "candidates": len(cands),
           "ranked": len(got.ranked), "infeasible": len(got.infeasible),
           "best": got.best_name, "lanes": lanes(ex)}
    print(f"phase A: {json.dumps(out, sort_keys=True)}", flush=True)
    return out


def phase_sweep(request: dict = SWEEP_REQUEST,
                min_lockstep: int = MIN_DEVICE_LANES):
    """Phase B: a full-width lockstep sweep, jax against batch.  Returns
    the batch result, the reference for phase C."""
    from repro.core import Explorer
    from repro.serve.protocol import SweepRequest
    trace, reports, cands = SweepRequest(**request).materialize()
    ex = Explorer(trace, reports, engine="jax")
    got = ex.explore(cands)
    check_jax_ran(ex, "phase B")
    ref = Explorer(trace, reports, engine="batch").explore(cands)
    check_rankings(got, ref, "phase B")
    ln = lanes(ex)
    check(ln["lockstep"] >= min_lockstep,
          f"phase B: {ln['lockstep']} lockstep lanes, want >= "
          f"{min_lockstep} of {len(cands)}")
    out = {"candidates": len(cands), "best": got.best_name, "lanes": ln}
    print(f"phase B: {json.dumps(out, sort_keys=True)}", flush=True)
    return ref


def phase_served(ref, request: dict = SWEEP_REQUEST,
                 n_requests: int = SERVED_REQUESTS, top_k: int = 5) -> None:
    """Phase C: ``POST /sweep`` to a sweep server on a thread of this
    process; each answer must come from the jax engine and rank like the
    batch reference."""
    from repro.core.replay import JAX_RTOL, makespans_close
    from repro.serve.protocol import post_json
    from repro.serve.sweepd import SweepService, serve
    service = SweepService()
    httpd = serve(service, "127.0.0.1", 0)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    spans = {o.name: o.makespan_s for o in ref.ranked}
    ref_top = [o.name for o in ref.ranked[:top_k]]
    try:
        host, port = httpd.server_address[:2]
        body = dict(request, engine="jax", top_k=top_k,
                    budget_s=REQUEST_BUDGET_S)
        for i in range(n_requests):
            status, doc = post_json(f"http://{host}:{port}/sweep", body,
                                    timeout=REQUEST_BUDGET_S + 30.0)
            check(status == 200, f"phase C request {i}: HTTP {status} "
                                 f"{doc.get('error')}")
            check(not doc["failed"],
                  f"phase C request {i}: failed {doc['failed']}")
            check(doc["engine_final"] == "jax",
                  f"phase C request {i}: finished on "
                  f"{doc['engine_final']!r}, not jax")
            check(doc["faults"]["engine_demotions"] == 0,
                  f"phase C request {i}: engine demoted")
            names = [t["name"] for t in doc["top"]]
            # rankings_equivalent's tie rule, position by position against
            # the batch ranking: a served name may stand in for a batch one
            # only when their batch makespans tie within the jax tier (a
            # tie at the top-k boundary can swap which of the tied
            # candidates makes the cut)
            check(len(names) == len(ref_top) and all(
                n in spans and makespans_close(spans[n], spans[r], JAX_RTOL)
                for n, r in zip(names, ref_top)),
                f"phase C request {i}: top {names} does not rank like "
                f"the batch top {ref_top}")
            out = {"top": names, "lanes": doc["replay"]["lockstep_lanes"]}
            print(f"phase C request {i}: {json.dumps(out)}", flush=True)
    finally:
        service.begin_drain()
        drained = service.drained(timeout=120.0)
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=30.0)
    check(drained, "phase C: the server did not drain")


def main() -> int:
    dev = require_tpu()
    from repro.core.jaxsim import resolve_step_impl
    from repro.core.xlacache import enable_persistent_cache
    info("setup", step_impl=resolve_step_impl("auto"),
         jax_cache_dir=enable_persistent_cache())
    counter = CompileCounter()
    t0 = time.perf_counter()
    phase_cholesky()
    info("phase A", seconds=time.perf_counter() - t0, **counter.snapshot())
    t0 = time.perf_counter()
    ref = phase_sweep()
    info("phase B", seconds=time.perf_counter() - t0, **counter.snapshot())
    t0 = time.perf_counter()
    phase_served(ref)
    info("phase C", seconds=time.perf_counter() - t0, **counter.snapshot())
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
