"""Shared plan-solve + step-compile path (factored out of dryrun so the
conformance subsystem verifies the *same* code the dry-run tables use).

``solve_plan``       solve the tiling for an (arch × shape × mesh) cell,
                     with an on-disk record cache.
``compile_step``     build the sharded train / prefill / decode step for
                     a plan and ``.lower().compile()`` it on a mesh.
``input_specs``      ShapeDtypeStruct stand-ins for the cell's inputs.
``normalize_moe_plan``  pin MoE expert roles to the canonical
                     expert-parallel layout the shard_map dispatch supports.

Callers: launch/dryrun.py (production tables), repro/verify (conformance
cells — differential numerics + HLO calibration).
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeConfig
from ..core.builders import build_graph
from ..core.plan import ShardingPlan
from ..core.solver import MeshAxis, solve_mesh
from ..models.model import LM
from ..obs.tracing import span as _span
from ..models.sharding import CACHE_RULES, batch_pspec, tree_shardings
from ..optim.adamw import AdamWConfig, apply_updates, init_state
from .mesh import solver_axes

CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         ".cache", "plans")


# ---------------------------------------------------------------------------
# solver plan with on-disk cache
# ---------------------------------------------------------------------------

# what a cached plan record depends on besides the call's own arguments:
# the graph builders, cost model and solver (all of repro/core), the
# as-executed breakdown projections, and this module's record layout
_PLAN_SOURCES = ("core", "verify/train_cell.py", "verify/calibration.py",
                 "launch/compile.py")


@functools.lru_cache(maxsize=None)
def _plan_sources_digest() -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for rel in _PLAN_SOURCES:
        path = os.path.join(root, rel)
        files = ([os.path.join(path, f) for f in sorted(os.listdir(path))
                  if f.endswith(".py")] if os.path.isdir(path) else [path])
        for f in files:
            with open(f, "rb") as fh:
                h.update(f.encode() + b"\0" + fh.read())
    return h.hexdigest()


def plan_key(*inputs) -> str:
    """Digest of a plan's inputs (config, shape, solver axes, solve
    options) and of the sources that turn them into a plan: a record left
    by other inputs or older code can never be read back."""
    h = hashlib.sha256(_plan_sources_digest().encode())
    h.update(repr(inputs).encode())
    return h.hexdigest()[:16]


def plan_cache_path(arch: str, shape: str, mesh_name: str,
                    key: str) -> str:
    os.makedirs(CACHE_DIR, exist_ok=True)
    return os.path.join(CACHE_DIR,
                        f"{arch}_{shape}_{mesh_name}_{key}.json")


def _executed_breakdown(g, axes, per_axis, kind: str) -> Dict[str, Any]:
    """Predicted system-wide wire bytes of the *as-executed* projection
    of a solved tiling — grads/opt state follow what the compiled
    program can actually shard (the same projection the CONFORMANCE
    calibration cells price), split by collective kind and phase.  This
    is the drift gauge's predicted side (obs.drift), stored in the plan
    record so launches compare against it without re-solving."""
    # lazy: verify imports this module (cycle otherwise)
    if kind == "train":
        from ..verify.train_cell import train_faithful_assignments
        executed = train_faithful_assignments(g, per_axis)
    else:
        from ..verify.calibration import faithful_assignments
        executed = faithful_assignments(g, per_axis)
    from ..core.solver import solution_breakdown
    br = solution_breakdown(g, axes, executed)
    return {"total": br["total"], "by_kind": br["by_kind"],
            "by_phase": br["by_phase"]}


def solve_cell_plan(cfg: ArchConfig, shape: ShapeConfig,
                    axes: Sequence[MeshAxis],
                    mesh_name: str,
                    use_cache: bool = True,
                    capacity: bool = False,
                    beam="auto",
                    graph_kwargs: Optional[Dict[str, Any]] = None,
                    compute=None) -> Dict[str, Any]:
    """Solve (or load from cache) the tiling plan record for one cell on
    explicit solver axes.  ``graph_kwargs`` are forwarded to
    ``build_graph`` (the training engine solves with ``master_fp32`` /
    ``error_feedback`` matching its runtime policy — callers must fold
    the flags into ``mesh_name`` so cache entries stay distinct).

    ``compute``: optional core.costterms.ComputeConfig making the solve
    kernel-aware; its ``token()`` is folded into the cache key so plans
    solved under different compute configs never share an entry."""
    if compute is not None:
        mesh_name = f"{mesh_name}_{compute.token()}"
    key = plan_key(cfg, shape, list(axes), capacity, beam,
                   sorted((graph_kwargs or {}).items()), mesh_name)
    path = plan_cache_path(cfg.name, shape.name, mesh_name, key)
    if use_cache and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    g = build_graph(cfg, shape, **(graph_kwargs or {}))
    t0 = time.time()
    with _span("compile.solve_plan", arch=cfg.name, shape=shape.name,
               mesh=mesh_name):
        if capacity:
            from ..core.solver import solve_mesh_capacity
            sol = solve_mesh_capacity(g, axes, beam=beam,
                                      compute=compute)
        else:
            sol = solve_mesh(g, axes, beam=beam, compute=compute)
    plan = ShardingPlan.from_graph_solution(sol, g)
    rec = {
        "mesh_axes": list(plan.mesh_axis_names),
        "role_cuts": plan.role_cuts,
        "total_bytes": sol.total_bytes,
        "per_axis_bytes": sol.per_axis_bytes,
        "total_seconds": sol.total_seconds,
        "solve_time": time.time() - t0,
        "breakdown": _executed_breakdown(g, axes, sol.per_axis,
                                         shape.kind),
    }
    if compute is not None:
        from ..core.solver import solution_compute_seconds
        rec["compute_seconds"] = solution_compute_seconds(
            g, axes, sol.per_axis, compute)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def solve_observed_regime(cfg: ArchConfig, axes: Sequence[MeshAxis],
                          mesh_name: str, regime: str,
                          batch: int, seq_len: int,
                          use_cache: bool = True,
                          graph_kwargs: Optional[Dict[str, Any]] = None,
                          compute=None) -> Dict[str, Any]:
    """Re-solve the cell plan under an *observed* regime — the replan
    advisor's solver bridge (DESIGN.md §17).  ``regime`` maps to the
    cell kind whose cost structure now dominates: a serving run that
    turned decode-heavy is priced as a decode cell over the live slot
    count and KV length, prefill-heavy as a prefill cell over the live
    prompt shape, and training stays a train cell.  The mesh axes are
    whatever survives (the caller passes the current runtime mesh), and
    the record caches under a regime-suffixed name so advisories do not
    thrash the on-disk plan cache."""
    kind = {"decode-heavy": "decode", "prefill-heavy": "prefill",
            "train": "train"}.get(regime)
    if kind is None:
        raise ValueError(
            f"unknown regime {regime!r} (expected decode-heavy | "
            f"prefill-heavy | train)")
    shape = ShapeConfig(f"observed_{kind}_b{batch}_s{seq_len}",
                        seq_len, batch, kind)
    return solve_cell_plan(cfg, shape, axes, f"{mesh_name}_{regime}",
                           use_cache=use_cache,
                           graph_kwargs=graph_kwargs, compute=compute)


def solve_plan(cfg: ArchConfig, shape: ShapeConfig, multi_pod: bool,
               use_cache: bool = True,
               capacity: bool = False) -> Dict[str, Any]:
    """Production-mesh cell solve (the dry-run entry point)."""
    mesh_name = ("pod2" if multi_pod else "pod1") + \
        ("_cap" if capacity else "")
    return solve_cell_plan(cfg, shape, solver_axes(multi_pod=multi_pod),
                           mesh_name, use_cache, capacity)


def plan_from_record(rec: Dict[str, Any]) -> ShardingPlan:
    return ShardingPlan(tuple(rec["mesh_axes"]),
                        {r: dict(c) for r, c in rec["role_cuts"].items()})


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        if cfg.embed_stub:
            return {"tokens": jax.ShapeDtypeStruct((b, cfg.d_model),
                                                   jnp.bfloat16)}
        return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}
    specs: Dict[str, Any] = {}
    if cfg.embed_stub:
        specs["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                               jnp.bfloat16)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return specs


def expert_parallel_axis(cfg: ArchConfig,
                         axis: str = "model") -> Optional[str]:
    """Mesh axis the shard_map MoE dispatch shards the expert dim on, or
    None when experts stay replicated.  The single source of truth for
    the dispatch condition — verify/calibration pins the solver to the
    same layout so predicted and executed programs agree."""
    if cfg.moe is not None and cfg.moe.n_experts % 16 == 0:
        return axis
    return None


def normalize_moe_plan(plan: ShardingPlan, cfg: ArchConfig,
                       axis: str = "model") -> ShardingPlan:
    """The shard_map MoE dispatch supports expert-dim sharding on one
    axis (standard expert parallelism); pin the expert-weight roles to
    that canonical layout."""
    if cfg.moe is None:
        return plan
    full = {a: None for a in plan.mesh_axis_names}
    ep = dict(full)
    ep_axis = expert_parallel_axis(cfg, axis)
    if ep_axis is not None:
        ep[ep_axis] = "expert"
    for role in ("moe_up", "moe_down"):
        plan = plan.with_override(role, dict(ep))
    plan = plan.with_override("moe_gate", dict(full))
    return plan


# ---------------------------------------------------------------------------
# step compile
# ---------------------------------------------------------------------------

def compile_step(cfg: ArchConfig, shape: ShapeConfig, plan: ShardingPlan,
                 mesh, ins: Dict[str, Any], layer_loop: str = "scan",
                 attn_impl: str = "xla"):
    """Build the sharded step for the cell kind (train / prefill /
    decode), lower and compile it on ``mesh``.  Returns
    (compiled, lower_seconds, compile_seconds)."""
    t0 = time.time()
    p0 = time.perf_counter()
    model = LM(cfg, plan=plan, attn_impl=attn_impl, mesh=mesh,
               layer_loop=layer_loop)
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params_s = jax.eval_shape(model.init, key)
        params_sh = tree_shardings(plan, params_s, mesh)
        if shape.kind == "decode":
            cache_s = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch,
                                         shape.seq_len))
            cache_sh = tree_shardings(plan, cache_s, mesh,
                                      rules=CACHE_RULES)
            tok_sh = jax.sharding.NamedSharding(
                mesh, batch_pspec(plan, "decode"))

            def serve_step(params, cache, tokens):
                return model.decode_step(params, cache, tokens)

            jitted = jax.jit(serve_step,
                             in_shardings=(params_sh, cache_sh, tok_sh))
            lowered = jitted.lower(params_s, cache_s, ins["tokens"])
        elif shape.kind == "prefill":
            bsh = jax.sharding.NamedSharding(mesh,
                                             batch_pspec(plan, "prefill"))
            in_sh = (params_sh,
                     {k: bsh for k in ins})

            def prefill_step(params, batch):
                logits, _ = model.forward(params, batch.get("tokens"),
                                          batch.get("embeds"))
                return logits

            jitted = jax.jit(prefill_step, in_shardings=in_sh)
            lowered = jitted.lower(params_s, ins)
        else:
            opt_s = jax.eval_shape(init_state, params_s)
            opt_sh = tree_shardings(plan, opt_s, mesh)
            bspec = batch_pspec(plan, "train")
            b_sh = {k: jax.sharding.NamedSharding(
                        mesh, bspec["tokens"] if k != "embeds"
                        else batch_pspec(plan, "prefill"))
                    for k in ins}
            ocfg = AdamWConfig()

            def train_step(params, opt, batch):
                def loss_fn(p):
                    return model.loss(p, batch)
                loss, grads = jax.value_and_grad(loss_fn)(params)
                params2, opt2, gnorm = apply_updates(params, grads, opt,
                                                     ocfg)
                return params2, opt2, loss, gnorm

            jitted = jax.jit(train_step,
                             in_shardings=(params_sh, opt_sh, b_sh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_s, opt_s, ins)
        t_lower = time.time() - t0
        from ..obs.tracing import record as _record_span
        _record_span("compile.lower", p0, time.perf_counter(),
                     arch=cfg.name, kind=shape.kind)
        with _span("compile.xla", arch=cfg.name, kind=shape.kind):
            compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, t_lower, t_compile
