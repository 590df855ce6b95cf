"""Sharding rules: parameter / optimizer / batch / cache PartitionSpecs.

Mesh axes (launch/mesh.py): ``("data", "model")`` single-pod 16×16, or
``("pod", "data", "model")`` = (2, 16, 16) multi-pod.  The mapping:

* **DP**   — batch over ``("pod", "data")`` (gradient all-reduce composes
  hierarchically: reduce-scatter intra-pod ICI, all-reduce inter-pod DCI).
* **TP**   — attention heads / FFN hidden / vocab over ``"model"``.
* **EP**   — MoE experts over ``"model"`` when the expert count divides it
  (llama4's 128); otherwise per-expert FFN TP (mixtral's 8 over 16).
* **SP**   — decode KV caches sequence-sharded over ``"model"``
  (flash-decoding style: each chip attends to its cache slice, XLA inserts
  the partial-softmax combine).
* **FSDP** — for models whose params+moments exceed per-chip HBM under pure
  TP (>8B by default), the non-TP weight dim is additionally sharded over
  ``"data"`` (ZeRO-3: per-layer all-gather inside the scan); optimizer
  moments inherit it for free since they are param-shaped.

Rules are *path-based* over the parameter pytree; every rule guards
divisibility (a dim that does not divide its mesh axis stays unsharded
rather than silently padding).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import ModelConfig

Tree = Any

# --------------------------------------------------------------------------
# Parallel plan (per-arch knobs the dry-run / hillclimb sweeps)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    fsdp: bool = False                # shard non-TP weight dim over "data"
    moment_dtype: Any = jnp.float32   # bf16 halves optimizer HBM (100B+)
    remat: str = "none"               # none | full | dots
    accum_steps: int = 1
    seq_shard_cache: bool = True      # SP decode caches over "model"
    notes: str = ""


def plan_for(cfg: ModelConfig) -> ParallelPlan:
    """Default plan: FSDP + remat above 8B params; bf16 moments above 100B."""
    n = cfg.param_count()
    return ParallelPlan(
        fsdp=n > 8e9,
        moment_dtype=jnp.bfloat16 if n > 100e9 else jnp.float32,
        remat="full" if n > 2e9 else "none",
        notes=f"params={n / 1e9:.2f}B")


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------


def abstract_mesh(axis_sizes: Sequence[int],
                  axis_names: Sequence[str]):
    """A device-free ``jax.sharding.AbstractMesh`` for sharding rules."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _maybe(mesh: Mesh, axes, dim: int):
    """Use ``axes`` for a dim only if it divides evenly (no padding)."""
    if axes is None or dim % axis_size(mesh, axes) != 0:
        return None
    return axes


def _path_keys(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        else:
            out.append(str(k))
    return tuple(out)


# --------------------------------------------------------------------------
# Parameter rules
# --------------------------------------------------------------------------

_STACKED_ROOTS = ("blocks", "cross", "encoder")


def _param_spec(cfg: ModelConfig, mesh: Mesh, plan: ParallelPlan,
                keys: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
    tp = "model"
    fs = "data" if (plan.fsdp and "data" in mesh.axis_names) else None
    stacked = keys[0].startswith(_STACKED_ROOTS)
    core = shape[1:] if stacked else shape           # strip layer-stack dim
    lead: Tuple = (None,) if stacked else ()

    def spec(*axes) -> P:
        fixed = tuple(_maybe(mesh, a, d) for a, d in zip(axes, core))
        return P(*(lead + fixed))

    ks = set(keys)
    last2 = keys[-2:]

    # ---- embeddings / head -------------------------------------------------
    if last2 == ("embed", "table"):
        return P(_maybe(mesh, tp, shape[0]), _maybe(mesh, fs, shape[1]))
    if keys[-2] == "lm_head":
        return P(_maybe(mesh, fs, shape[0]), _maybe(mesh, tp, shape[1]))

    # ---- MoE ----------------------------------------------------------------
    if "moe" in ks:
        if keys[-1] in ("gate", "up") and len(core) == 3:   # (E, d, ff)
            if core[0] % axis_size(mesh, tp) == 0:          # EP
                return spec(tp, fs, None)
            return spec(None, fs, tp)                       # per-expert TP
        if keys[-1] == "down" and len(core) == 3:           # (E, ff, d)
            if core[0] % axis_size(mesh, tp) == 0:
                return spec(tp, None, fs)
            return spec(None, tp, fs)
        if "router" in ks:
            return spec(*(None,) * len(core))

    # ---- norms / small vectors ---------------------------------------------
    if keys[-1] in ("scale", "b", "w_bias", "mix", "cmix", "a_log",
                    "dt_bias", "d_skip", "conv"):
        if keys[-1] == "b" and len(core) == 1:              # projection bias
            return spec(tp)
        return spec(*(None,) * len(core))
    if keys[-1] == "bonus":                                 # (H, hd)
        return spec(tp, None)

    # ---- projections --------------------------------------------------------
    if len(core) == 2:
        d_in, d_out = core
        # "write back to residual" projections: shard the input dim over TP
        if keys[-2] in ("wo", "down", "cv", "out_proj"):
            return spec(tp, fs)
        # everything else reads the residual: shard the output dim over TP
        return spec(fs, tp)

    return P(*(None,) * len(shape))


def param_specs(cfg: ModelConfig, mesh: Mesh, plan: ParallelPlan,
                params_shape: Tree) -> Tree:
    """PartitionSpec tree matching ``jax.eval_shape(init)`` output."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [_param_spec(cfg, mesh, plan, _path_keys(p), tuple(l.shape))
             for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(cfg: ModelConfig, mesh: Mesh, plan: ParallelPlan,
                    params_shape: Tree) -> Tree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(cfg, mesh, plan, params_shape))


def opt_shardings(cfg: ModelConfig, mesh: Mesh, plan: ParallelPlan,
                  opt_state_shape) -> Any:
    """OptState(step, mu, nu): moments shard exactly like the params."""
    rep = NamedSharding(mesh, P())
    mu = param_shardings(cfg, mesh, plan, opt_state_shape.mu)
    nu = param_shardings(cfg, mesh, plan, opt_state_shape.nu)
    return type(opt_state_shape)(step=rep, mu=mu, nu=nu)


# --------------------------------------------------------------------------
# Batch / cache rules
# --------------------------------------------------------------------------


def batch_shardings(cfg: ModelConfig, mesh: Mesh, batch_shape: Tree) -> Tree:
    """Data batch: leading (global batch) dim over (pod, data)."""
    dp = dp_axes(mesh)

    def one(path, leaf):
        b = leaf.shape[0] if leaf.ndim else 1
        first = _maybe(mesh, dp, b)
        return NamedSharding(mesh, P(first, *(None,) * (leaf.ndim - 1)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


def cache_shardings(cfg: ModelConfig, mesh: Mesh, plan: ParallelPlan,
                    cache_shape: Tree) -> Tree:
    """Decode caches: (stack, B, ...) — B over (pod,data); attention KV
    sequence-sharded over "model" (SP / flash-decoding); recurrent-state
    head dim over "model"."""
    dp = dp_axes(mesh)
    tp = "model" if plan.seq_shard_cache else None

    def one(path, leaf):
        keys = _path_keys(path)
        nd = leaf.ndim
        if nd >= 2:
            b_ax = _maybe(mesh, dp, leaf.shape[1])
        else:
            return NamedSharding(mesh, P(*(None,) * nd))
        rest: Tuple = (None,) * (nd - 2)
        if keys[-1] in ("k", "v") and nd == 5:
            if "enc_kv" in keys:                       # whisper cross KV
                rest = (None, None, None)
            else:                                      # (L,B,S,kv,hd): SP on S
                rest = (_maybe(mesh, tp, leaf.shape[2]), None, None)
        elif keys[-1] in ("wkv", "ssm") and nd == 5:   # (L,B,H,dk,dv)
            rest = (_maybe(mesh, tp, leaf.shape[2]), None, None)
        elif keys[-1] == "conv" and nd == 4:           # (L,B,W-1,C)
            rest = (None, _maybe(mesh, tp, leaf.shape[3]))
        return NamedSharding(mesh, P(None, b_ax, *rest))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


# --------------------------------------------------------------------------
# Convenience: everything the dry-run needs for one cell
# --------------------------------------------------------------------------


def shardings_for_cell(cfg: ModelConfig, mesh: Mesh, plan: ParallelPlan,
                       kind: str, specs: Dict[str, Any],
                       params_shape: Tree,
                       opt_state_shape=None) -> Tuple[Tuple, Dict]:
    """(in_shardings, tree_of_input_specs) for jit(step).lower(...)."""
    p_sh = param_shardings(cfg, mesh, plan, params_shape)
    if kind == "train":
        o_sh = opt_shardings(cfg, mesh, plan, opt_state_shape)
        b_sh = batch_shardings(cfg, mesh, specs["batch"])
        return (p_sh, o_sh, b_sh)
    if kind == "prefill":
        b_sh = batch_shardings(cfg, mesh, specs["batch"])
        return (p_sh, b_sh)
    # decode
    t_sh = batch_shardings(cfg, mesh, specs["tokens"])
    c_sh = cache_shardings(cfg, mesh, plan, specs["cache"])
    l_sh = NamedSharding(mesh, P())
    return (p_sh, t_sh, c_sh, l_sh)
