"""§Perf hillclimbing driver.

For a chosen cell, re-lowers the two roofline probes under a candidate
change (plan override / mesh factorization), recomputes the three roofline
terms, and prints before→after — one hypothesis→change→measure→validate
iteration per candidate.  Results land as tagged artifacts next to the
baselines, so EXPERIMENTS.md §Perf can cite exact numbers.

Candidate enumeration and local search go through the exploration engine
(``repro.core.explore``): grids are ``DesignSpace`` points, and the Zynq
sweep is a cached ``Explorer.hillclimb`` — every re-visited neighbour is a
dictionary lookup, not a re-simulation.

Usage:
  python -m benchmarks.hillclimb gemma2-prefill     # hillclimb A
  python -m benchmarks.hillclimb llama4-train       # hillclimb B
  python -m benchmarks.hillclimb qwen3-codesign     # hillclimb C
  python -m benchmarks.hillclimb zynq-codesign      # hillclimb D (paper §VI)
"""
import json
import os
import sys
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[1] / "benchmarks" / "artifacts"


def _cell_from(records, arch, shape, tag_prefix=""):
    from repro.roofline.model import analyze_record
    fulls = [r for r in records if r["arch"] == arch and r["shape"] == shape
             and not r.get("tag")]
    probes = sorted((r for r in records
                     if r["arch"] == arch and r["shape"] == shape
                     and r.get("tag", "").startswith(f"{tag_prefix}probe")),
                    key=lambda r: r["n_layers"])
    full = fulls[0] if fulls else probes[-1]
    return analyze_record(full, probes=probes[:2] if len(probes) >= 2
                          else None)


def run_candidate(arch, shape, tag, plan_overrides=None, mesh=None,
                  cfg_overrides=None):
    """Two probes (+ extrapolation) under the candidate change."""
    from repro.launch.dryrun import probe_unit, run_cell
    from repro.roofline.model import analyze_record
    from repro import configs

    unit = probe_unit(configs.get_config(arch))
    recs = []
    for depth in (unit, 2 * unit):
        rec = run_cell(arch, shape, multi_pod=False,
                       plan_overrides=plan_overrides,
                       cfg_overrides=cfg_overrides, mesh_override=mesh,
                       probe_layers=depth, tag=f"{tag}-probe{depth}")
        recs.append(rec)
    recs.sort(key=lambda r: r["n_layers"])
    cell = analyze_record(recs[-1], probes=recs)
    return cell, recs


def show(label, c):
    print(f"  {label:28s} compute={c.compute_s:.4f}s mem_floor="
          f"{c.memory_s:.4f}s collective={c.collective_s:.4f}s "
          f"dominant={c.dominant} useful={c.useful_ratio:.3f} "
          f"roofline={c.roofline_fraction:.3f}", flush=True)


def gemma2_prefill():
    """Hillclimb A — most collective-bound cell: gemma2-2b × prefill_32k."""
    from repro.launch.mesh import mesh_variant
    from repro.roofline.model import load_artifacts

    arch, shape = "gemma2-2b", "prefill_32k"
    print(f"=== hillclimb A: {arch} × {shape} ===")
    base = _cell_from(load_artifacts(), arch, shape)
    show("baseline (16×16)", base)

    # iteration 1: H=8 does not divide model=16 ⇒ half-head shards force
    # per-layer activation resharding.  (data=32, model=8): heads shard
    # cleanly; predicted: collective term drops by ~the activation
    # all-gather volume (≈ S·d·bytes per layer pair).
    c1, _ = run_candidate(arch, shape, "m32x8", mesh=mesh_variant(32, 8))
    show("mesh 32×8 (clean heads)", c1)

    # iteration 2: even smaller model axis — TP=4 matches kv=4 exactly;
    # predicted: fewer reshards still, but larger per-device weights.
    c2, _ = run_candidate(arch, shape, "m64x4", mesh=mesh_variant(64, 4))
    show("mesh 64×4 (TP=kv=4)", c2)
    return {"baseline": base, "m32x8": c1, "m64x4": c2}


def llama4_train():
    """Hillclimb B — worst-fraction large cell: llama4 × train_4k."""
    from repro.roofline.model import load_artifacts

    arch, shape = "llama4-maverick-400b-a17b", "train_4k"
    print(f"=== hillclimb B: {arch} × {shape} ===")
    base = _cell_from(load_artifacts(), arch, shape)
    show("baseline (fsdp, remat=full)", base)

    # iteration 1: remat=full re-runs the forward in bwd ⇒ FSDP re-gathers
    # every weight a 3rd time.  remat=dots keeps matmul outputs; predicted
    # collective term ≈ ×2/3 of baseline, at higher activation memory.
    c1, _ = run_candidate(arch, shape, "rematdots",
                          plan_overrides={"remat": "dots"})
    show("remat=dots (no re-gather)", c1)

    # iteration 2: accumulate over 4 microbatches — activations shrink 4×,
    # so remat can stay off; gathers happen per microbatch ⇒ collective
    # unchanged, but compute waste from remat disappears.
    c2, _ = run_candidate(arch, shape, "accum4",
                          plan_overrides={"remat": "none",
                                          "accum_steps": 4})
    show("accum=4, remat=none", c2)

    # iteration 3: one-hot dispatch/combine einsums cost 2·Tg·E·C·d MACs
    # each way — at cf=1.25 that's ~2.5× the useful expert FLOPs.  The
    # scatter dispatch (models/moe.py) moves the same bytes with ZERO MACs;
    # predicted: compute term drops by the dispatch share, collective
    # unchanged.
    c3, _ = run_candidate(arch, shape, "scatter",
                          plan_overrides={"remat": "dots"},
                          cfg_overrides={"moe_dispatch": "scatter"})
    show("scatter dispatch + dots", c3)
    return {"baseline": base, "rematdots": c1, "accum4": c2,
            "scatter": c3}


def qwen3_codesign():
    """Hillclimb C — the paper's technique itself: pod co-design sweep for
    qwen3-4b × train_4k over mesh factorizations × overlap schedules."""
    from repro.core.steptask import estimate_step
    from repro.launch.mesh import mesh_variant
    from repro.roofline.model import load_artifacts

    arch, shape = "qwen3-4b", "train_4k"
    print(f"=== hillclimb C: {arch} × {shape} (steptask co-design) ===")
    records = load_artifacts()
    base = _cell_from(records, arch, shape)
    show("baseline (16×16)", base)

    # napkin math: Megatron-TP all-reduces move ~2·tokens_dev·d·bytes per
    # layer per pass (≈51 GB/dev/step measured at TP=16).  A 4B model's
    # weights (8 GB bf16) fit per-chip, so shrinking TP trades activation
    # collectives for weight/grad traffic: TP=4 → ~13 GB/dev; TP=1 (pure
    # DP) → only the gradient all-reduce ≈ 2·params·2B·(g-1)/g ≈ 15 GB/dev
    # once per step, overlappable with bwd.  Predicted: collective term
    # 1.03 s → ~0.3 s, cell flips compute-bound.
    variants = {"16x16": None}
    cells = {"16x16": base}
    for name, (d, m) in {"64x4": (64, 4), "256x1": (256, 1)}.items():
        c, recs = run_candidate(arch, shape, f"m{name}",
                                mesh=mesh_variant(d, m))
        cells[name] = c
        variants[name] = recs
        show(f"mesh {name}", c)

    # iteration 3: with collectives fixed the cell is compute-bound and
    # useful≈0.61 — remat=full recomputes the forward (6ND → 8ND).
    # remat=dots keeps matmul outputs: predicted compute ×6/8, useful→0.8,
    # at higher (but checked) activation memory.
    c3, recs3 = run_candidate(arch, shape, "m64x4dots",
                              mesh=mesh_variant(64, 4),
                              plan_overrides={"remat": "dots"})
    cells["64x4+dots"] = c3
    variants["64x4+dots"] = recs3
    show("mesh 64x4 + remat=dots", c3)

    # feed every variant through the paper-style estimator (ms each) in
    # both overlap modes; the decision table is the deliverable.  The
    # (variant × overlap) grid is a DesignSpace, evaluated through the
    # same order-preserving pool the Zynq explorer uses.
    from repro.core.explore import DesignSpace, parallel_map

    probes_base = sorted(
        (r for r in records if r["arch"] == arch and r["shape"] == shape
         and r.get("tag", "").startswith("probe")),
        key=lambda r: r["n_layers"])
    full = next(r for r in records if r["arch"] == arch
                and r["shape"] == shape and not r.get("tag"))
    space = DesignSpace({"variant": tuple(variants),
                         "overlap": (False, True)})

    def _estimate(point):
        pr = (probes_base if variants[point["variant"]] is None
              else variants[point["variant"]])
        tag = f"{point['variant']}/{'ovl' if point['overlap'] else 'blk'}"
        return estimate_step(arch, shape, pr[0], pr[1],
                             full["full_n_layers"], overlap=point["overlap"],
                             params=full["params"], variant=tag)

    table = {est.variant: est.makespan_s
             for est in parallel_map(_estimate, list(space.points()))}
    print("  co-design table (predicted step seconds):")
    for k, v in sorted(table.items(), key=lambda kv: kv[1]):
        print(f"    {k:12s} {v:.4f}")
    best = min(table, key=lambda k: table[k])
    print(f"  chosen: {best} — one full-scale compile instead of "
          f"{len(table)}")
    return cells


def zynq_codesign():
    """Hillclimb D — the paper's own §VI space, searched instead of swept.

    Axes: mxm granularity implied by the trace (bs=64), #accelerator slots
    and ±SMP heterogeneous execution.  The Explorer runs the array-compiled
    simulator and caches frozen graphs across the walk (slot-count moves
    share one payload), so each step is a fast simulate and each *revisit*
    is a dictionary lookup.  The on-disk store under benchmarks/artifacts
    persists the walk: re-running this driver starts from disk hits, not
    from graph builds.
    """
    from repro.apps import matmul as mm
    from repro.core import (DesignSpace, Eligibility, Explorer,
                            a9_smp_seconds, zynq_system)

    print("=== hillclimb D: Zynq mxm co-design (explore engine) ===")
    trace = mm.trace_matmul(n=256, bs=64, verify=False)
    reports = mm.report_map()
    reps = mm.hls_reports()
    explorer = Explorer(trace, reports,
                        smp_seconds_fn=a9_smp_seconds("float32"),
                        cache_dir=str(ARTIFACTS / "zynq_sweepcache"))
    space = DesignSpace({"n_acc": (1, 2, 3, 4), "smp": (False, True)})

    def build(point):
        kind = "fpga:mxm64"
        name = f"{point['n_acc']}acc64" + ("+smp" if point["smp"] else "")
        kinds = (kind, "smp") if point["smp"] else (kind,)
        return mm.Candidate(
            name=name, system=zynq_system(name, {kind: point["n_acc"]}),
            eligibility=Eligibility({"mxm_block": kinds}),
            fabric=[(reps[64], point["n_acc"])])

    best, best_s, history = explorer.hillclimb(
        space, build, start={"n_acc": 1, "smp": True})
    for point, s in history:
        label = f"{point['n_acc']}acc64" + ("+smp" if point["smp"] else "")
        t = "infeasible" if s == float("inf") else f"{s * 1e3:8.3f} ms"
        print(f"  {label:12s} {t}")
    print(f"  chosen: {best['n_acc']}acc64{'+smp' if best['smp'] else ''} "
          f"= {best_s * 1e3:.3f} ms after {len(history)} evals "
          f"(cache {explorer.stats.as_dict()})")
    return best, best_s


def main() -> int:
    # the dry-run probes lower on 512 virtual host devices: set before
    # jax picks a backend; importing this module leaves the environment
    # alone
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("gemma2-prefill", "all"):
        gemma2_prefill()
    if which in ("llama4-train", "all"):
        llama4_train()
    if which in ("qwen3-codesign", "all"):
        qwen3_codesign()
    if which in ("zynq-codesign", "all"):
        zynq_codesign()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
