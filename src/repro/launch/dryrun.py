"""Multi-pod dry-run: prove the distribution config is coherent without
real hardware.

For every (architecture × input shape × mesh) cell this driver:

  1. builds the exact published config + its :class:`ParallelPlan`;
  2. lowers the step (``train_step`` / ``prefill_step`` / ``serve_step``)
     under ``jax.jit`` with explicit in_shardings against
     ``ShapeDtypeStruct`` stand-ins (zero allocation);
  3. ``.compile()``s it — sharding mismatches, compile-OOM, or unsupported
     collectives fail HERE, which is the point;
  4. records ``memory_analysis()`` (proves it fits), ``cost_analysis()``
     (FLOPs/bytes for §Roofline), and the collective-bytes breakdown parsed
     from the optimized HLO, into a JSON artifact under
     ``benchmarks/artifacts/dryrun/``.

Run one cell:   python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
All cells:      python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import os
import re
import time
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_N_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_L_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16, "token": 0, "s4": 1, "u4": 1}


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_N_RE.search(line)          # replica_groups=[G,S]<=[N]
    if m:
        return int(m.group(2))
    m = _GROUPS_L_RE.search(line)          # replica_groups={{0,1,..},..}
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective traffic from the optimized (partitioned) HLO.

    HLO shapes in the SPMD-partitioned module are per-device shards.  The
    optimized text prints operands without inline types, so operand bytes
    are derived from the *result* shape and the replica-group size g:
    all-reduce/all-to-all/collective-permute operand = result; all-gather
    operand = result/g; reduce-scatter operand = result·g.  ``wire_bytes``
    additionally applies the ring cost model (AR 2·o·(g-1)/g, AG o·(g-1),
    RS/A2A o·(g-1)/g, CP o) — this is what §Roofline's collective term
    uses.  Async ``-start`` variants print the result as a tuple whose last
    element is the gathered output; the ``-done`` halves carry no shapes.
    """
    out = {k: 0 for k in _COLLECTIVES}
    wire = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    op_re = re.compile(r"([a-z][a-z0-9-]*)\(")
    for line in hlo_text.splitlines():
        ls = line.lstrip()
        if not ls.startswith("%") or " = " not in ls:
            continue
        lhs, _, rhs = ls.partition(" = ")
        # The op token is the name before the FIRST "(" in the rhs — this
        # also handles TUPLE results, e.g. the combined gradient all-reduce
        # ``(f32[16]{0}, f32[32,64]{1,0}, ...) all-reduce(...)``, whose
        # leading "(" breaks naive prefix splitting.
        m = op_re.search(rhs)
        if m is None:
            continue
        op_tok = m.group(1)
        op_hit = None
        for op in _COLLECTIVES:
            if op_tok in (op, f"{op}-start"):
                op_hit = op
                break
        if op_hit is None:
            continue
        head = rhs[:m.start()]
        shapes = _SHAPE_RE.findall(head)
        if not shapes:
            continue
        # Tuple results: a sync collective over a pytree (e.g. the gradient
        # all-reduce) lists EVERY reduced tensor in the result tuple — sum
        # them all.  Async ``-start`` tuples carry (operands..., results...)
        # → halve (exact for all-reduce-start; CPU HLO is sync anyway).
        result_b = sum(_bytes_of(d, s) for d, s in shapes)
        if op_tok.endswith("-start"):
            result_b //= 2
        g = max(_group_size(line), 1)
        if op_hit == "all-gather":
            operand = result_b // max(g, 1)
            w = operand * (g - 1)
        elif op_hit == "reduce-scatter":
            operand = result_b * g
            w = operand * (g - 1) / g
        elif op_hit == "all-reduce":
            operand = result_b
            w = 2 * operand * (g - 1) / g
        elif op_hit == "all-to-all":
            operand = result_b
            w = operand * (g - 1) / g
        else:                                 # collective-permute
            operand = result_b
            w = operand
        out[op_hit] += operand
        wire[op_hit] += w
        counts[op_hit] += 1
    return {"per_op_bytes": out, "per_op_counts": counts,
            "per_op_wire_bytes": {k: int(v) for k, v in wire.items()},
            "total_bytes": sum(out.values()),
            "wire_bytes": int(sum(wire.values()))}


def probe_unit(cfg) -> int:
    """Depth quantum for the linear roofline probes (one repeating unit)."""
    return cfg.shared_every if cfg.shared_every else len(cfg.pattern)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             plan_overrides: dict | None = None,
             cfg_overrides: dict | None = None,
             mesh_override=None, save: bool = True,
             tag: str = "", probe_layers: int | None = None) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.launch import mesh as mesh_mod
    from repro.models import transformer as T
    from repro.parallel import sharding as sh
    from repro.serve import engine
    from repro.train import optimizer as opt_mod
    from repro.train import step as step_mod

    shape = configs.SHAPES[shape_name]
    ok, why = configs.runnable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    cfg = configs.get_config(arch)
    plan = sh.plan_for(cfg)          # plan from the FULL config, always
    if plan_overrides:
        plan = dataclasses.replace(plan, **plan_overrides)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    full_layers = cfg.n_layers
    full_params = cfg.param_count()
    full_active = cfg.active_param_count()
    if probe_layers is not None:
        # reduced-depth UNROLLED probe: XLA cost_analysis counts a scan
        # body once, so roofline terms come from two unrolled probes,
        # extrapolated linearly in depth (roofline/model.py).
        cfg = dataclasses.replace(cfg, n_layers=probe_layers,
                                  unroll_scan=True)
        tag = tag or f"probe{probe_layers}"
    if shape.kind == "train" and plan.remat != "none":
        cfg = dataclasses.replace(cfg, remat=plan.remat)

    if mesh_override is not None:
        mesh = mesh_override
    else:
        mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    mesh_name = mesh_mod.describe(mesh)

    specs = configs.input_specs(cfg, shape)
    params_shape = jax.eval_shape(lambda: T.init(cfg, jax.random.PRNGKey(0)))

    t0 = time.perf_counter()
    if shape.kind == "train":
        tcfg = step_mod.TrainConfig(
            opt=opt_mod.OptConfig(moment_dtype=plan.moment_dtype),
            accum_steps=plan.accum_steps)
        step = step_mod.make_train_step(cfg, tcfg)
        opt_shape = jax.eval_shape(
            lambda p: opt_mod.init(tcfg.opt, p), params_shape)
        in_sh = (sh.param_shardings(cfg, mesh, plan, params_shape),
                 sh.opt_shardings(cfg, mesh, plan, opt_shape),
                 sh.batch_shardings(cfg, mesh, specs["batch"]))
        args = (params_shape, opt_shape, specs["batch"])
        out_sh = (in_sh[0], in_sh[1], None)
        donate = (0, 1)          # params/opt_state update in place
    elif shape.kind == "prefill":
        step = engine.make_prefill_step(cfg, max_len=shape.seq_len)
        in_sh = (sh.param_shardings(cfg, mesh, plan, params_shape),
                 sh.batch_shardings(cfg, mesh, specs["batch"]))
        args = (params_shape, specs["batch"])
        # the produced KV cache leaves sharded (batch over dp, seq over
        # model) — without this the full-length cache materializes
        # replicated and 100B-class prefill blows per-chip HBM
        cache_shape = jax.eval_shape(
            lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len))
        out_sh = (None, sh.cache_shardings(cfg, mesh, plan, cache_shape))
        donate = ()
    else:  # decode
        step = engine.make_serve_step(cfg)
        in_sh = (sh.param_shardings(cfg, mesh, plan, params_shape),
                 sh.batch_shardings(cfg, mesh, specs["tokens"]),
                 sh.cache_shardings(cfg, mesh, plan, specs["cache"]),
                 jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))
        args = (params_shape, specs["tokens"], specs["cache"],
                specs["length"])
        out_sh = (None, in_sh[2])
        donate = (2,)            # KV cache / recurrent state updates in place

    with mesh:
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())

    def _mem_field(name):
        v = getattr(mem, name, None)
        return int(v) if v is not None else None

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "n_devices": int(
            jnp.prod(jnp.array(list(mesh.shape.values())))),
        "plan": {"fsdp": plan.fsdp, "remat": plan.remat,
                 "moment_dtype": str(plan.moment_dtype),
                 "accum_steps": plan.accum_steps,
                 "seq_shard_cache": plan.seq_shard_cache,
                 "notes": plan.notes},
        "tag": tag,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "n_layers": cfg.n_layers, "full_n_layers": full_layers,
        "params": full_params,          # FULL config (probes are reduced)
        "active_params": full_active,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "collectives": coll,
        "memory": {k: _mem_field(k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "peak_memory_in_bytes", "generated_code_size_in_bytes")},
    }
    if save:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fn = ARTIFACTS / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
        fn.write_text(json.dumps(record, indent=1))
        record["artifact"] = str(fn)
    return record


def main() -> int:
    # 512 virtual host devices for the mesh, set before jax picks a
    # backend; importing this module leaves the environment alone
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--probes", action="store_true",
                    help="run the two unrolled roofline probes per cell")
    ap.add_argument("--shapes", default="",
                    help="comma-separated shape filter (e.g. train_4k)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from repro import configs

    shape_filter = {x for x in args.shapes.split(",") if x}
    cells = []
    if args.all:
        for a in configs.arch_ids():
            for s in configs.SHAPES:
                if not shape_filter or s in shape_filter:
                    cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    mesh_name = "pod=2×data=16×model=16" if args.multi_pod \
        else "data=16×model=16"

    jobs = []
    for arch, shape in cells:
        if args.probes:
            from repro import configs as _c
            unit = probe_unit(_c.get_config(arch))
            jobs.append((arch, shape, unit))
            jobs.append((arch, shape, 2 * unit))
        else:
            jobs.append((arch, shape, None))

    failures = 0
    for arch, shape, probe in jobs:
        suffix = f"__probe{probe}" if probe else ""
        if args.skip_existing:
            fn = ARTIFACTS / f"{arch}__{shape}__{mesh_name}{suffix}.json"
            if fn.exists():
                print(f"[skip existing] {arch} × {shape}{suffix}")
                continue
        try:
            rec = run_cell(arch, shape, args.multi_pod, probe_layers=probe)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures += 1
            print(f"[FAIL] {arch} × {shape}{suffix}: "
                  f"{type(e).__name__}: {e}", flush=True)
            continue
        if "skipped" in rec:
            print(f"[skip] {arch} × {shape}: {rec['skipped']}")
            continue
        c = rec["cost_analysis"]
        peak = rec["memory"]["peak_memory_in_bytes"] or 0
        print(f"[ok] {arch} × {shape} × {rec['mesh']}: "
              f"lower {rec['lower_s']}s compile {rec['compile_s']}s "
              f"flops/dev={c.get('flops', 0):.3e} "
              f"wire/dev={rec['collectives']['wire_bytes']:.3e}B "
              f"peak/dev={peak / 1e9:.2f}GB", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
