"""Reference interpreter for semantic graphs (core/graph.py), serial and
sharded.

Gives every op kind a concrete (linear, deterministic) semantics so a
graph is a runnable einsum program:

  einsum   out = jnp.einsum over the named dims (classes fall out of
           name identity, exactly as cost.py classifies them)
  ewise    out = Σ inputs, each input first sum-reduced over dims absent
           from the output, then broadcast-aligned to the output dims
  reduce   out = input summed over attrs["axis"]
  custom   not executable (builder-specific black box) — reject

The sharded path materializes a solved plan: leaf tensors are
device_put with the ``ShardingPlan`` PartitionSpec for their own name
(fuzz plans use tensor names as roles), every op output gets a
``with_sharding_constraint``, and the whole program is jit-compiled on
the mesh.  Serial vs sharded outputs agreeing is the execution leg of
the fuzz invariants.
"""
from __future__ import annotations

import string
from typing import Dict, List, Optional

from ..core.graph import Graph
from ..core.plan import ShardingPlan


def leaf_tensors(g: Graph) -> List[str]:
    """Tensors never produced by an op (the program's inputs/weights)."""
    produced = {op.output for op in g.ops}
    return [t for t in g.tensors if t not in produced]


def sink_tensors(g: Graph) -> List[str]:
    """Tensors produced but never consumed (the program's outputs)."""
    produced = {op.output for op in g.ops}
    consumed = {t for op in g.ops for t in op.inputs}
    return sorted(produced - consumed)


def random_values(g: Graph, seed: int = 0) -> Dict[str, object]:
    """f32 leaf values, deterministic in ``seed`` (executor math runs in
    f32 regardless of the cost model's bytes_per_elem)."""
    import jax
    import jax.numpy as jnp

    vals = {}
    key = jax.random.PRNGKey(seed)
    for name in leaf_tensors(g):
        key, sub = jax.random.split(key)
        ts = g.tensors[name]
        vals[name] = jax.random.normal(sub, tuple(ts.shape), jnp.float32)
    return vals


def _letters(dims) -> str:
    return "".join(dims)


def _dim_letters(g: Graph) -> Dict[str, str]:
    """One einsum letter per distinct dim name in the graph."""
    letters: Dict[str, str] = {}
    pool = iter(string.ascii_letters)
    for ts in g.tensors.values():
        for d in ts.dims:
            if d not in letters:
                letters[d] = next(pool)
    return letters


def execute(g: Graph, values: Dict[str, object],
            constrain=None) -> Dict[str, object]:
    """Run ops in graph order; returns all tensor values (inputs
    included).  ``values`` must cover :func:`leaf_tensors`.
    ``constrain(name, value)``: optional hook applied to every op output
    (the sharded path forces each tensor's planned sharding there)."""
    import jax.numpy as jnp

    let = _dim_letters(g)
    vals = dict(values)

    def align_to(x, src_dims, dst_dims):
        # sum out dims missing from dst, then broadcast-align to dst
        keep = [d for d in src_dims if d in dst_dims]
        sub = f"{''.join(let[d] for d in src_dims)}->" \
              f"{''.join(let[d] for d in keep)}"
        x = jnp.einsum(sub, x)
        expand = f"{''.join(let[d] for d in keep)}->" \
                 f"{''.join(let[d] for d in dst_dims if d in keep)}"
        x = jnp.einsum(expand, x)
        # insert singleton axes for dst dims the input lacks
        shape = [1] * len(dst_dims)
        it = iter(x.shape)
        for i, d in enumerate(dst_dims):
            if d in keep:
                shape[i] = next(it)
        return x.reshape(shape)

    for op in g.ops:
        ins = [vals[t] for t in op.inputs]
        out_ts = g.tensors[op.output]
        if op.kind == "einsum":
            lhs, rhs = (g.tensors[t] for t in op.inputs)
            sub = (f"{''.join(let[d] for d in lhs.dims)},"
                   f"{''.join(let[d] for d in rhs.dims)}->"
                   f"{''.join(let[d] for d in out_ts.dims)}")
            vals[op.output] = jnp.einsum(sub, *ins)
        elif op.kind == "ewise":
            acc = None
            for t, x in zip(op.inputs, ins):
                a = align_to(x, g.tensors[t].dims, out_ts.dims)
                acc = a if acc is None else acc + a
            vals[op.output] = jnp.broadcast_to(acc, tuple(out_ts.shape))
        elif op.kind == "reduce":
            src = g.tensors[op.inputs[0]]
            axis = src.dims.index(op.attrs["axis"])
            vals[op.output] = jnp.sum(ins[0], axis=axis)
        else:
            raise NotImplementedError(
                f"executor cannot run op kind {op.kind!r}")
        if constrain is not None:
            vals[op.output] = constrain(op.output, vals[op.output])
    return vals


def tensor_plan(g: Graph, sol) -> ShardingPlan:
    """ShardingPlan over a solved graph using tensor names as roles —
    every tensor gets its own cut row."""
    return ShardingPlan.from_solution(sol, {t: t for t in g.tensors})


def execute_sharded(g: Graph, values: Dict[str, object],
                    plan: ShardingPlan, mesh,
                    outputs: Optional[List[str]] = None):
    """jit-execute the graph on ``mesh`` with the plan's shardings forced
    on every tensor; returns {name: value} for ``outputs`` (default: the
    sink tensors)."""
    import jax
    from jax.sharding import NamedSharding


    outs = outputs if outputs is not None else sink_tensors(g)
    leaves = leaf_tensors(g)

    def pspec(t):
        return plan.pspec(t, g.tensors[t].dims)

    def constrain(name, x):
        try:
            return jax.lax.with_sharding_constraint(x, pspec(name))
        except (ValueError, RuntimeError):
            return x

    def program(leaf_vals):
        full = execute(g, dict(leaf_vals), constrain=constrain)
        return {t: full[t] for t in outs}

    with jax.set_mesh(mesh):
        placed = {t: jax.device_put(values[t],
                                    NamedSharding(mesh, pspec(t)))
                  for t in leaves}
        in_sh = {t: NamedSharding(mesh, pspec(t)) for t in leaves}
        res = jax.jit(program, in_shardings=(in_sh,))(placed)
    return {k: jax.device_get(v) for k, v in res.items()}
