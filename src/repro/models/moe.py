"""Mixture-of-Experts layer with capacity-based scatter/gather routing.

Dispatch uses sort-free rank computation + scatter into an [E, C, D]
buffer (linear memory — the dense [T, E, C] dispatch einsum of
Mesh-TensorFlow would be O(T·E·C) and cannot scale to 1M-token batches).
Under a solver plan the expert dim is sharded on the model axis (expert
parallelism); GSPMD then lowers the scatter/gather into the all-to-all
that the tiling cost model predicts (route/combine custom ops)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import dense_init, shard


def init_moe(key, cfg: ArchConfig, dtype=jnp.bfloat16):
    m = cfg.moe
    d, e, f = cfg.d_model, m.n_experts, m.d_ff_expert
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (d, e), dtype=jnp.float32),
        "w_gate": dense_init(k2, (e, d, f), in_axis=1, dtype=dtype),
        "w_up": dense_init(k3, (e, d, f), in_axis=1, dtype=dtype),
        "w_down": dense_init(k4, (e, f, d), in_axis=1, dtype=dtype),
    }


def _capacity(tokens: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, min(c, tokens))


def moe_ffn_sharded(params, x, cfg: ArchConfig, plan, mesh
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SPMD MoE via shard_map: routing + capacity dispatch happen
    *locally* per data shard, and expert parallelism is an explicit
    lax.all_to_all over the expert axis.  GSPMD cannot partition the
    scatter/gather dispatch (it falls back to replicating the [E·C, D]
    buffer — a 256 GB all-reduce per layer in the 64-expert dry-run, see
    EXPERIMENTS §Perf), so we hand it the local program instead."""
    from jax.sharding import PartitionSpec as P

    x_spec = plan.pspec("x", ("batch", "seq", "d_model"))
    up_spec = plan.pspec("moe_up", ("expert", "d_model", "e_ff"))
    ep_axes = up_spec[0] if len(up_spec) and up_spec[0] else None
    if isinstance(ep_axes, str):
        ep_axes = (ep_axes,)

    def inner(params, x):
        y, aux = _moe_local(params, x, cfg, ep_axes)
        # aux is a local mean; average over all mesh axes for a global one
        for ax in mesh.axis_names:
            aux = jax.lax.pmean(aux, ax)
        return y, aux

    p_specs = {
        "router": P(),
        "w_gate": up_spec,
        "w_up": up_spec,
        "w_down": plan.pspec("moe_down", ("expert", "e_ff", "d_model")),
    }
    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=(p_specs, x_spec),
                       out_specs=(x_spec, P()),
                       check_vma=False)
    return fn(params, x)


def _moe_local(params, x, cfg: ArchConfig, ep_axes) -> Tuple[jnp.ndarray,
                                                             jnp.ndarray]:
    """Per-shard MoE: local routing/capacity; explicit all-to-all over
    ``ep_axes`` when experts are sharded there."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    cap = _capacity(t, cfg)
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ params["router"])  # [T, E]
    probs = jax.nn.softmax(logits, -1)
    gate, eid = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(0)
    ce = jnp.zeros(e).at[eid.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    flat_e = eid.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))
    ranks_sorted = jnp.arange(t * k) - starts[sorted_e]
    rank = jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)
    keep = rank < cap
    dest = jnp.where(keep, flat_e * cap + rank, e * cap)

    xk = jnp.repeat(xf, k, axis=0)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xk)
    xe = buf[: e * cap].reshape(e, cap, d)

    if ep_axes:
        for ax in ep_axes:
            # regroup: my local experts' tokens from every peer
            xe = jax.lax.all_to_all(xe, ax, split_axis=0, concat_axis=1,
                                    tiled=True)
    h = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    hh = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u
    ye = jnp.einsum("ecf,efd->ecd", hh, params["w_down"])
    if ep_axes:
        for ax in reversed(ep_axes):
            ye = jax.lax.all_to_all(ye, ax, split_axis=1, concat_axis=0,
                                    tiled=True)

    yb = jnp.concatenate([ye.reshape(e * cap, d),
                          jnp.zeros((1, d), x.dtype)], 0)
    yk = yb[dest] * (gate.reshape(-1, 1).astype(x.dtype)
                     * keep[:, None].astype(x.dtype))
    y = yk.reshape(t, k, d).sum(1)
    return y.reshape(b, s, d), aux


def moe_ffn(params, x, cfg: ArchConfig, plan=None, mesh=None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, D] -> (y [B, S, D], aux load-balancing loss)."""
    if plan is not None and mesh is not None:
        return moe_ffn_sharded(params, x, cfg, plan, mesh)
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    cap = _capacity(t, cfg)
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32) @ params["router"])  # [T, E]
    probs = jax.nn.softmax(logits, -1)
    gate, eid = jax.lax.top_k(probs, k)                   # [T, K]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    me = probs.mean(0)
    ce = jnp.zeros(e).at[eid.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # rank within expert, capacity drop.  Sort-based ranks: O(TK log TK)
    # — the one-hot cumsum alternative is O(TK·E) and dominated the
    # compute roofline term for 64-expert models (see EXPERIMENTS §Perf).
    flat_e = eid.reshape(-1)                              # [T*K]
    order = jnp.argsort(flat_e, stable=True)              # group by expert
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))    # [E]
    ranks_sorted = jnp.arange(t * k) - starts[sorted_e]
    rank = jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)
    keep = rank < cap
    dest = jnp.where(keep, flat_e * cap + rank, e * cap)  # overflow slot

    # dispatch: scatter tokens (replicated K ways) into [E*C+1, D]
    xk = jnp.repeat(xf, k, axis=0)                        # [T*K, D]
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xk)
    xe = buf[: e * cap].reshape(e, cap, d)
    xe = shard(xe, plan, "moe_h", ("expert", "tok_e", "d_model"))

    h = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    hh = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u
    ye = jnp.einsum("ecf,efd->ecd", hh, params["w_down"])

    # combine: gather + weighted sum over K
    yb = jnp.concatenate([ye.reshape(e * cap, d),
                          jnp.zeros((1, d), x.dtype)], 0)
    yk = yb[dest] * (gate.reshape(-1, 1).astype(x.dtype)
                     * keep[:, None].astype(x.dtype))
    y = yk.reshape(t, k, d).sum(1)
    return y.reshape(b, s, d), aux
