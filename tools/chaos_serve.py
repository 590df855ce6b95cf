#!/usr/bin/env python
"""Live-traffic chaos drill for sweepd (the CI ``chaos-serve`` job).

Boots a real ``repro.explore serve`` subprocess with fault injection
armed (``REPRO_FAULTS=kill_worker:2,corrupt_cache:1``), then drives it
the way an unlucky day would:

1. **Storm** — 4 concurrent clients, each issuing 50-candidate sweep
   requests against the same synthetic application, while the injectors
   kill pool workers and corrupt the on-disk store underneath them.
   Every response must be a clean ranking (or carry an explicit
   ``failed`` list — never a crash, never a 500), and every ranking
   must be bit-identical across clients: the exact engine tier admits
   no drift, demotions included.
2. **Telemetry** — ``/healthz`` must show the recovery counters
   (worker retries / pool respawns) and the fault-state marker files
   must prove each injector really fired.
3. **Pareto** — a budgeted multi-objective request (the injectors are
   exhausted by now) must return a clean, well-formed frontier, and a
   fault-free one-shot CLI run of the same request must produce a
   bit-identical frontier/top/best document: chaos plus the service path
   change nothing about the PPA ranking.
4. **Drain** — SIGTERM lands while a request is in flight.  The
   in-flight request must still complete with the same ranking, a
   follow-up request must be refused (503 while draining, or connection
   refused once the listener is down), and the server process must exit
   0 with its drain summary on stderr.

Run from the repo root: ``PYTHONPATH=src python tools/chaos_serve.py``.
Exit status is non-zero on the first violated expectation.
"""
from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT / "src"))

from repro.serve.protocol import get_json, post_json  # noqa: E402

CLIENTS = 4
REQUESTS_PER_CLIENT = 6
FAULT_SPEC = "kill_worker:2,corrupt_cache:1"

#: 50 candidates per request: accs 1-25 with the SMP variant doubles up.
#: The engine stays "batch": the server child and the one-shot CLI child
#: run at the same time, and with the jax engine both would contend for
#: the one chip (a chip belongs to one process at a time).
SWEEP_DOC = {"trace": "synth:32", "engine": "batch", "accs": "1-25",
             "top_k": 5, "budget_s": 300.0}


def fail(msg: str) -> None:
    print(f"CHAOS-SERVE FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def start_server(cache_dir: str, state_dir: str) -> tuple:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO_ROOT / "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    env["REPRO_FAULTS"] = FAULT_SPEC
    env["REPRO_FAULTS_STATE"] = state_dir
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.explore", "serve",
         "--port", "0", "--processes", "2", "--cache-dir", cache_dir,
         "--max-concurrent", str(CLIENTS), "--queue-limit", "32"],
        env=env, stderr=subprocess.PIPE, text=True)
    # the listening line is the first thing the server says; port 0 means
    # only it knows which port the OS handed out
    line = proc.stderr.readline()
    m = re.search(r"listening on (http://\S+)", line)
    if not m:
        proc.kill()
        fail(f"no listening line from server, got: {line!r}")
    base = m.group(1)
    tail: list = []

    def pump() -> None:     # keep stderr drained; keep the drain summary
        for ln in proc.stderr:
            tail.append(ln)

    threading.Thread(target=pump, daemon=True).start()
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            status, _ = get_json(base + "/readyz")
            if status == 200:
                return proc, base, tail
        except OSError:
            pass
        time.sleep(0.1)
    proc.kill()
    fail("server never became ready")


def storm(base: str) -> list:
    """4 clients x 50-candidate requests; returns every response doc."""
    docs, errors = [], []
    lock = threading.Lock()

    def client(cid: int) -> None:
        for i in range(REQUESTS_PER_CLIENT):
            try:
                status, doc = post_json(base + "/sweep", SWEEP_DOC,
                                        timeout=300.0)
            except OSError as exc:
                with lock:
                    errors.append(f"client {cid} req {i}: {exc}")
                return
            with lock:
                if status != 200:
                    errors.append(f"client {cid} req {i}: HTTP {status} "
                                  f"{doc.get('error')}")
                else:
                    docs.append(doc)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        fail(f"{len(errors)} request(s) failed under chaos: {errors[0]}")
    return docs


def check_storm(docs: list) -> None:
    want = CLIENTS * REQUESTS_PER_CLIENT
    if len(docs) != want:
        fail(f"expected {want} responses, got {len(docs)}")
    for doc in docs:
        if doc["candidates"] != 50:
            fail(f"expected 50 candidates, got {doc['candidates']}")
        # the chaos contract: a clean ranking, or an *explicit* per-
        # candidate failure list — never a silent hole
        if not doc["top"] and not doc["failed"]:
            fail(f"response with neither ranking nor failures: {doc}")
    tops = [[t["name"] for t in doc["top"]] for doc in docs]
    if any(t != tops[0] for t in tops[1:]):
        fail(f"rankings diverged across clients: {tops[0]} vs next "
             f"differing entry")
    engines = sorted({doc["engine_final"] for doc in docs})
    print(f"storm ok: {len(docs)} responses, stable top-k {tops[0]}, "
          f"final engine(s) {engines}")


def check_telemetry(base: str, state_dir: str) -> None:
    status, health = get_json(base + "/healthz")
    if status != 200:
        fail(f"/healthz returned {status}")
    f = health["faults"]
    if f["worker_retries"] < 1 and f["pool_respawns"] < 1:
        fail(f"no worker recovery recorded after kill_worker: {f}")
    markers = os.listdir(state_dir)
    for site in ("kill_worker", "corrupt_cache"):
        if not any(m.startswith(site + ".") for m in markers):
            fail(f"injector {site} never fired (markers: {markers})")
    if health["requests"]["errors"]:
        fail(f"server counted errors: {health['requests']}")
    print(f"telemetry ok: fault counters {f}, "
          f"coalesce {health['coalesce']}")


#: The budgeted multi-objective variant of the storm request: same trace
#: and candidate ramp, ranked over makespan/area/energy with a peak-power
#: cap.  The spec library is server-fixed, so the service's frontier must
#: be bit-identical to a fault-free one-shot CLI run.
PARETO_DOC = dict(SWEEP_DOC, objectives=["area_mm2", "energy_j"],
                  budgets={"power_w": 5.0})


def check_pareto(base: str) -> None:
    status, doc = post_json(base + "/sweep", PARETO_DOC, timeout=300.0)
    if status != 200:
        fail(f"budgeted Pareto request got HTTP {status}: "
             f"{doc.get('error')}")
    if doc["failed"]:
        fail(f"budgeted Pareto request quarantined candidates: "
             f"{doc['failed']}")
    if not doc.get("frontier"):
        fail(f"budgeted Pareto response carried no frontier: {doc}")
    for entry in doc["frontier"]:
        if set(entry) != {"rank", "name", "makespan_s", "objectives",
                          "ppa"}:
            fail(f"malformed frontier entry: {entry}")
    if doc["best"] not in {e["name"] for e in doc["frontier"]}:
        fail(f"makespan winner {doc['best']} missing from the frontier")

    # fault-free one-shot CLI over the same request: every PPA field must
    # be bit-identical to what the (chaos-hardened) service returned
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO_ROOT / "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    env.pop("REPRO_FAULTS", None)
    env.pop("REPRO_FAULTS_STATE", None)
    out = os.path.join(tempfile.gettempdir(), "chaos_pareto_oneshot.json")
    cmd = [sys.executable, "-m", "repro.explore", SWEEP_DOC["trace"],
           "--accs", SWEEP_DOC["accs"], "--top-k", str(SWEEP_DOC["top_k"]),
           "--objectives", ",".join(PARETO_DOC["objectives"]),
           "--budget", "power_w=5.0", "--json", out]
    cp = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=300)
    if cp.returncode != 0:
        fail(f"one-shot Pareto CLI exited {cp.returncode}: {cp.stderr}")
    import json as _json
    ref = _json.load(open(out))
    for key in ("frontier", "top", "best", "objectives", "budgets",
                "dominated"):
        if doc[key] != ref[key]:
            fail(f"service/CLI Pareto mismatch on {key!r}: "
                 f"{doc[key]} vs {ref[key]}")
    print(f"pareto ok: frontier {[e['name'] for e in doc['frontier']]}, "
          f"{doc['dominated']} dominated, CLI one-shot bit-identical")


def check_drain(proc, base: str, expected_top: list) -> None:
    inflight: dict = {}

    def slow_request() -> None:
        inflight["status"], inflight["doc"] = post_json(
            base + "/sweep", SWEEP_DOC, timeout=300.0)

    t = threading.Thread(target=slow_request)
    t.start()
    time.sleep(0.3)         # let it reach the sweep proper
    proc.send_signal(signal.SIGTERM)
    time.sleep(0.2)
    try:
        status, doc = post_json(base + "/sweep", SWEEP_DOC, timeout=30.0)
        if status != 503:
            fail(f"post-SIGTERM request got HTTP {status}, wanted 503 "
                 f"(draining) or a refused connection")
        print(f"drain ok: new request refused with 503 "
              f"({doc.get('error')})")
    except OSError:
        print("drain ok: new request refused (listener already down)")
    t.join(timeout=120)
    if t.is_alive():
        fail("in-flight request never returned during drain")
    if inflight["status"] != 200:
        fail(f"in-flight request failed during drain: "
             f"HTTP {inflight['status']} {inflight['doc']}")
    got_top = [x["name"] for x in inflight["doc"]["top"]]
    if got_top != expected_top:
        fail(f"drained request's ranking diverged: {got_top}")
    rc = proc.wait(timeout=120)
    if rc != 0:
        fail(f"server exited {rc} after SIGTERM, wanted 0")
    print("drain ok: in-flight request completed bit-identically, "
          "server exited 0")


def main() -> int:
    with tempfile.TemporaryDirectory(prefix="chaos-serve-") as tmp:
        cache_dir = os.path.join(tmp, "store")
        state_dir = os.path.join(tmp, "fault-state")
        os.makedirs(state_dir)
        proc, base, tail = start_server(cache_dir, state_dir)
        try:
            docs = storm(base)
            check_storm(docs)
            check_telemetry(base, state_dir)
            check_pareto(base)
            expected_top = [t["name"] for t in docs[0]["top"]]
            check_drain(proc, base, expected_top)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        summary = [ln for ln in tail if "drain" in ln.lower()]
        if summary:
            print("server drain summary:", summary[-1].strip())
    print("chaos-serve: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
