"""Plan-driven pipeline-parallel stage runner (shard_map + lax.ppermute).

The solver's joint stage search (core/solver.py::solve_pipeline) picks
layer-range cuts and per-stage tilings; this module executes them: the
layer stack [L, ...] is split into S contiguous stages over a ``stage``
mesh axis, microbatches flow through the ring with a (n_micro + S - 1)-
step schedule, and params/activations sit under the solved tilings of
the *inner* mesh axes (``stage_tensor_spec`` maps a PipelineSolution's
tilings onto PartitionSpecs for the stacked runner arrays).

Boundary-sharding fix vs the seed executor: the seed shard_map used
``in_specs=(P(stage_axis), P())`` — activations entered replicated
across every non-stage axis, so each ``ppermute`` hop shipped the FULL
microbatch no matter what tiling the plan chose for the boundary tensor.
``x_spec`` now threads the solved boundary sharding into the shard_map
specs; each device permutes only its local shard, and the wire bytes
drop by the inner partition degree (regression-pinned in
tests/test_pipeline_parallel.py, gated against the solver's prediction
by verify/pipeline_cell.py).

``PipelineTrainer`` is the training-side runner.  With n_stages == 1 it
*delegates to train/engine.py::TrainEngine* (wrapping the layer stack as
a model), so the flat path reproduces the PR-5 engine trajectory
bit-for-bit — scan-accumulated microbatch gradients, AdamW
apply_updates, identical metrics.  With n_stages > 1 the same
accumulation semantics run through the pipeline schedule (mean of
per-microbatch losses; gradients arrive pre-summed by the schedule's
backward) and the update is the engine's apply_updates on the staged
param/opt pytrees.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.tracing import span as _span
from ..optim import adamw
from ..optim.adamw import AdamWConfig, apply_updates

PyTree = Any


def _join(stage_axis: Optional[str], spec: Optional[P]) -> P:
    """Prepend the stage axis to a per-stage/per-microbatch spec."""
    tail = tuple(spec) if spec is not None else ()
    return P(stage_axis, *tail)


def pipeline_forward(mesh: Optional[Mesh], stage_axis: str,
                     stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
                     params_staged: PyTree, x: jnp.ndarray,
                     n_micro: int,
                     x_spec: Optional[P] = None,
                     params_spec: Optional[PyTree] = None) -> jnp.ndarray:
    """Run ``stage_fn`` S times (once per stage) over microbatched ``x``.

    params_staged: leaves with leading [S] axis (one slice per stage).
    x: [B, ...] global batch; B % n_micro == 0.
    x_spec: PartitionSpec of one microbatch [mb, ...] over the mesh's
    *inner* (non-stage) axes — the solved boundary sharding.  Omitted =
    replicated (the seed behavior; ships the full microbatch per hop).
    params_spec: per-leaf specs of one stage's params [L/S, ...] over the
    inner axes (a single spec applies to every leaf).  Omitted =
    replicated within a stage group.
    Returns stage-(S-1) outputs re-assembled to [B, ...].
    """
    s = (mesh.shape[stage_axis]
         if mesh is not None and stage_axis in mesh.shape else 1)
    b = x.shape[0]
    assert b % n_micro == 0
    mb = b // n_micro
    xm = x.reshape(n_micro, mb, *x.shape[1:])

    if s == 1:
        # flat path: no schedule, no transfers — the microbatched serial
        # stack, bit-identical to the reference the tests pin against
        params_local = jax.tree_util.tree_map(lambda a: a[0],
                                              params_staged)

        def mb_body(_, xmb):
            return None, stage_fn(params_local, xmb)

        _, outs = jax.lax.scan(mb_body, None, xm)
        return outs.reshape(b, *x.shape[1:])

    def body(params_local, xm_local):
        # params_local: this stage's params (leading axis stripped)
        params_local = jax.tree_util.tree_map(
            lambda a: a[0], params_local)
        idx = jax.lax.axis_index(stage_axis)
        n_steps = n_micro + s - 1
        buf = jnp.zeros_like(xm_local[0])
        outs = jnp.zeros_like(xm_local)

        def step(carry, t):
            buf, outs = carry
            feed = jnp.where(t < n_micro,
                             xm_local[jnp.minimum(t, n_micro - 1)], 0.0)
            inp = jnp.where(idx == 0, feed, buf)
            out = stage_fn(params_local, inp)
            # last stage finishes microbatch t - (s-1) at step t
            mi = t - (s - 1)
            valid = (idx == s - 1) & (mi >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.maximum(mi, 0)].set(out),
                lambda o: o, outs)
            nxt = jax.lax.ppermute(
                out, stage_axis,
                [(i, (i + 1) % s) for i in range(s)])
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs),
                                      jnp.arange(n_steps))
        # broadcast final outputs from last stage to all (psum of masked)
        outs = jnp.where(idx == s - 1, outs, 0.0)
        outs = jax.lax.psum(outs, stage_axis)
        return outs

    if params_spec is None or isinstance(params_spec, P):
        p_specs = jax.tree_util.tree_map(
            lambda _: _join(stage_axis, params_spec), params_staged)
    else:
        p_specs = jax.tree_util.tree_map(
            functools.partial(_join, stage_axis), params_spec,
            is_leaf=lambda v: v is None or isinstance(v, P))
    x_full = _join(None, x_spec)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, x_full),
        out_specs=x_full,
        check_vma=False)
    outs = fn(params_staged, xm)
    return outs.reshape(b, *x.shape[1:])


def split_stages(params_stacked: PyTree, n_stages: int) -> PyTree:
    """[L, ...] layer stack -> [S, L/S, ...] staged stack."""
    def r(a):
        l = a.shape[0]
        assert l % n_stages == 0
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])
    return jax.tree_util.tree_map(r, params_stacked)


def make_stage_fn(layer_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray]
                  ) -> Callable[[PyTree, jnp.ndarray], jnp.ndarray]:
    """Stage = scan of L/S layers."""
    def stage(params_stage, x):
        def body(x, p):
            return layer_fn(p, x), None
        x, _ = jax.lax.scan(body, x, params_stage)
        return x
    return stage


def stage_tensor_spec(psol, tensor: str,
                      dims: Sequence[Optional[str]]) -> P:
    """PartitionSpec over the solved inner mesh axes for a physical array
    whose dims carry the given graph dim names (None entries for physical
    dims the graph does not know, e.g. the stacked-layer axis).

    The runner's shard_map takes ONE spec per leaf, so this projects the
    tiling of the first solved stage touching the tensor; homogeneous
    stacks solve every stage to the same tiling, which is the case the
    runner executes."""
    from ..core.tiling import Part

    entries = [[] for _ in dims]
    for st in psol.stages:
        if tensor not in st.graph.tensors:
            continue
        for ax, assign in zip(psol.inner_axes, st.per_axis):
            t = assign.get(tensor)
            if isinstance(t, Part) and t.dim in dims:
                i = dims.index(t.dim)
                if ax.name not in entries[i]:
                    entries[i].append(ax.name)
        break
    return P(*[tuple(e) if len(e) > 1 else (e[0] if e else None)
               for e in entries])


class _StackModel:
    """Adapter presenting a homogeneous layer stack as the LM-shaped duck
    TrainEngine expects (init/loss/plan/mesh) — the S=1 delegation."""

    plan = None
    mesh = None

    def __init__(self, layer_fn, loss_fn, params_stacked):
        self._layer_fn = layer_fn
        self._loss_fn = loss_fn
        self._params = params_stacked

    def init(self, key):
        del key
        # copy: the engine step donates its state — the caller's stack
        # must survive
        return jax.tree_util.tree_map(
            lambda p: jnp.array(p, copy=True), self._params)

    def loss(self, params, batch):
        def body(h, p):
            return self._layer_fn(p, h), None

        h, _ = jax.lax.scan(body, batch["x"], params)
        return self._loss_fn(h, batch["y"])


class PipelineTrainer:
    """Training runner for a solved pipeline over a homogeneous stack.

    n_stages == 1: wraps the stack in _StackModel and runs the actual
    PR-5 TrainEngine (microbatch scan accumulation, bucketed sync,
    apply_updates) — the flat-plan trajectory is the engine's by
    construction.  n_stages > 1: loss = mean of per-microbatch losses
    through pipeline_forward (matching the engine's lsum/n_micro), grads
    via jax.grad through the schedule (stage-local, no cross-stage sync
    needed), update via the engine's apply_updates."""

    def __init__(self, layer_fn, loss_fn, *, n_stages: int,
                 n_micro: int, mesh: Optional[Mesh] = None,
                 stage_axis: str = "stage",
                 optim: Optional[AdamWConfig] = None,
                 x_spec: Optional[P] = None,
                 y_spec: Optional[P] = None,
                 params_spec: Optional[PyTree] = None):
        self.layer_fn = layer_fn
        self.loss_fn = loss_fn
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.mesh = mesh
        self.stage_axis = stage_axis
        self.optim = optim or AdamWConfig()
        self.x_spec = x_spec
        self.y_spec = y_spec if y_spec is not None else x_spec
        self.params_spec = params_spec
        self._engine = None
        self._jit = None

    # -- S == 1: the engine IS the trainer ---------------------------------
    def _make_engine(self, params_stacked):
        from ..train.engine import EngineConfig, TrainEngine
        model = _StackModel(self.layer_fn, self.loss_fn, params_stacked)
        cfg = EngineConfig(microbatches=self.n_micro, master_fp32=False,
                           optim=self.optim)
        return TrainEngine(model, cfg, mesh=None)

    # -- state -------------------------------------------------------------
    def _state_shardings(self, state: PyTree) -> PyTree:
        spec_of = {}
        if isinstance(self.params_spec, P) or self.params_spec is None:
            p_specs = jax.tree_util.tree_map(
                lambda _: _join(self.stage_axis, self.params_spec),
                state["params"])
        else:
            p_specs = jax.tree_util.tree_map(
                functools.partial(_join, self.stage_axis),
                self.params_spec,
                is_leaf=lambda v: v is None or isinstance(v, P))
        spec_of = {
            "params": p_specs,
            "opt": {"step": P(), "m": p_specs, "v": p_specs},
        }
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), spec_of,
            is_leaf=lambda v: isinstance(v, P))

    def init(self, params_stacked: PyTree) -> PyTree:
        if self.n_stages == 1:
            self._engine = self._make_engine(params_stacked)
            return self._engine.init_state(jax.random.PRNGKey(0))
        staged = split_stages(jax.tree_util.tree_map(
            lambda p: jnp.array(p, copy=True), params_stacked),
            self.n_stages)
        state = {"params": staged, "opt": adamw.init_state(staged)}
        if self.mesh is not None:
            state = jax.device_put(state, self._state_shardings(state))
        return state

    # -- the step ----------------------------------------------------------
    def _pipe_loss(self, params, x, y):
        out = pipeline_forward(self.mesh, self.stage_axis,
                               make_stage_fn(self.layer_fn), params, x,
                               self.n_micro, x_spec=self.x_spec,
                               params_spec=self.params_spec)
        mb = x.shape[0] // self.n_micro
        outs_m = out.reshape(self.n_micro, mb, *out.shape[1:])
        ys_m = y.reshape(self.n_micro, mb, *y.shape[1:])
        losses = jax.vmap(self.loss_fn)(outs_m, ys_m)
        return jnp.mean(losses)

    def _make_step(self):
        def step_fn(state, x, y):
            loss, grads = jax.value_and_grad(self._pipe_loss)(
                state["params"], x, y)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)
            new_params, new_opt, gnorm = apply_updates(
                state["params"], grads, state["opt"], self.optim)
            return ({"params": new_params, "opt": new_opt},
                    {"loss": loss, "gnorm": gnorm})

        return jax.jit(step_fn, donate_argnums=(0,))

    def _jit_step(self):
        if self._jit is None:
            self._jit = self._make_step()
        return self._jit

    def step(self, state: PyTree, x, y):
        if self.n_stages == 1:
            assert self._engine is not None, "call init() first"
            return self._engine.step(state, {"x": x, "y": y})
        fn = self._jit_step()
        with _span("train.pipeline_step", n_stages=self.n_stages):
            if self.mesh is not None:
                with jax.set_mesh(self.mesh):
                    return fn(state, x, y)
            return fn(state, x, y)

    def lower_step(self, state_like, x_like, y_like):
        """Lower+compile the pipelined step on stand-ins — the verify
        pipeline cell measures stage-boundary collective-permute bytes
        from this HLO."""
        assert self.n_stages > 1
        fn = self._jit_step()
        if self.mesh is not None:
            with jax.set_mesh(self.mesh):
                return fn.lower(state_like, x_like, y_like).compile()
        return fn.lower(state_like, x_like, y_like).compile()
