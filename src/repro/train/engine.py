"""Plan-driven distributed training engine (replaces the seed
runtime/train_loop step).

The engine executes the *training* side of the solved tiling plan — the
paper's headline claim is a training speedup, and until now only the
forward/serving paths executed plans.  One jitted, donated step carries:

  - microbatch gradient accumulation (``lax.scan`` over microbatches;
    the f32 accumulator is carried in the solver-chosen gradient
    sharding via per-leaf constraints, so accumulation never gathers),
  - bucketed gradient synchronization (optim/compression.bucket_slices):
    per-bucket dependency chains let XLA's scheduler overlap a bucket's
    collective issue with the remaining backward work instead of hitting
    one monolithic sync barrier,
  - optional error-feedback int8 compressed sync (compress_bucketed —
    the sharding constraint sits between quantize and dequantize, so the
    reshard into the gradient/optimizer layout carries int8 wire bytes),
  - mixed precision: bf16 compute params, fp32 master weights + AdamW
    moments, each placed under its own solved tiling (roles
    ``<w>.master`` / ``<w>.opt`` / ``<w>.err`` from the optimizer-state
    graph extension — ZeRO-style partitioning is just another tiling the
    solver picks; see DESIGN.md §12).

Checkpointing goes through checkpoint/ckpt with a sharding_fn built from
the engine's own state shardings, so a run saved on one mesh restores
elastically onto another (4x2 -> 2x4) with optimizer state re-placed
under the new mesh's solved tilings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import ckpt
from ..models.model import LM
from ..models.sharding import batch_pspec, tree_pspecs
from ..obs.tracing import span as _span
from ..optim import adamw
from ..optim.adamw import AdamWConfig, apply_updates
from ..optim.compression import (bucket_slices, compress_bucketed,
                                 init_error)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    microbatches: int = 1          # gradient-accumulation factor
    buckets: int = 4               # gradient-sync buckets
    grad_compression: bool = False  # error-feedback int8 sync
    master_fp32: bool = True       # bf16 compute / f32 master weights
    optim: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    # "auto" | "xla" | "pallas" — SSD chunk-scan kernel in the microbatch
    # step (ssm/hybrid families); auto resolves to Pallas on TPU, XLA
    # elsewhere (CPU interpret mode is for parity tests, not throughput)
    kernels: str = "auto"


class TrainEngine:
    """One (model, plan, mesh) training executor.

    State layout (a plain pytree, checkpointable as-is):
      ``params``  bf16 compute weights   (plan weight roles)
      ``opt``     {step, m, v} fp32      (plan ``<w>.opt`` roles)
      ``master``  fp32 master weights    (plan ``<w>.master`` roles;
                                          present iff master_fp32)
      ``err``     fp32 residuals         (plan ``<w>.err`` roles;
                                          present iff grad_compression)
    """

    def __init__(self, model: LM, cfg: Optional[EngineConfig] = None,
                 mesh=None):
        self.cfg = cfg or EngineConfig()
        # duck-typed models (e.g. pipeline _StackModel) have no ssd_impl
        # and nothing to re-route — only re-dispatch real LMs
        model_impl = getattr(model, "ssd_impl", None)
        if model_impl is not None:
            ssd_impl = self.cfg.kernels
            if ssd_impl == "auto":
                ssd_impl = ("pallas" if jax.default_backend() == "tpu"
                            else model_impl)
            if ssd_impl != model_impl:
                model = dataclasses.replace(model, ssd_impl=ssd_impl)
        self.model = model
        self.mesh = mesh if mesh is not None else model.mesh
        self.plan = model.plan
        # continuous monitor (obs.monitor.Monitor), attached by the
        # harness; None costs one attribute check per step dispatch
        self.monitor = None
        self._jit = None
        self._jit_keys: Optional[Tuple[str, ...]] = None
        self._struct: Optional[PyTree] = None

    # ------------------------------------------------------------------
    # state construction & placement
    # ------------------------------------------------------------------
    def _build_state(self, key) -> PyTree:
        """Pure state constructor (no placement — jit/eval_shape safe)."""
        params = self.model.init(key)
        state: Dict[str, PyTree] = {
            "params": params,
            "opt": adamw.init_state(params),
        }
        if self.cfg.master_fp32:
            # jnp.array(copy=True): f32 param leaves (norm scales) must
            # not alias their master copy — the step donates both
            state["master"] = jax.tree_util.tree_map(
                lambda p: jnp.array(p, jnp.float32, copy=True), params)
        if self.cfg.grad_compression:
            state["err"] = init_error(params)
        return state

    def state_struct(self) -> PyTree:
        if self._struct is None:   # fixed per engine; tracing LM.init
            self._struct = jax.eval_shape(self._build_state,
                                          jax.random.PRNGKey(0))
        return self._struct

    def state_pspecs(self, state_like: PyTree) -> PyTree:
        """PartitionSpecs for every state leaf under the solved plan
        (params via weight roles; opt/master/err via their derived
        roles, falling back to the weight tiling)."""
        plan = self.plan
        specs = {
            "params": tree_pspecs(plan, state_like["params"]),
            "opt": tree_pspecs(plan, state_like["opt"],
                               suffixes=(".opt",)),
        }
        if "master" in state_like:
            specs["master"] = tree_pspecs(
                plan, state_like["master"], suffixes=(".master", ".opt"))
        if "err" in state_like:
            specs["err"] = tree_pspecs(
                plan, state_like["err"], suffixes=(".err", ".opt"))
        return specs

    def state_shardings(self, state_like: Optional[PyTree] = None) -> PyTree:
        if self.mesh is None:
            raise ValueError("state_shardings needs a mesh")
        if state_like is None:
            state_like = self.state_struct()
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            self.state_pspecs(state_like),
            is_leaf=lambda x: isinstance(x, P))

    def _batch_spec(self, key: str):
        """One input key's PartitionSpec under the plan (embeds are
        [B,S,D] activations; everything else rides the train batch
        spec).  The single source for the feed-side shardings AND the
        step's in_shardings — divergence would reshard every batch on
        step entry."""
        if self.plan is None:
            return None
        if key == "embeds":
            return batch_pspec(self.plan, "prefill")
        return batch_pspec(self.plan, "train")["tokens"]

    def batch_shardings(self, keys=("tokens", "labels")) -> Dict[str, Any]:
        """NamedShardings for the host batch (the data pipeline feeds
        device batches through these — data/pipeline.BatchFeed)."""
        if self.mesh is None:
            raise ValueError("batch_shardings needs a mesh")
        return {k: NamedSharding(self.mesh, self._batch_spec(k))
                for k in keys}

    def init_state(self, key) -> PyTree:
        if self.mesh is not None and self.plan is not None:
            with jax.set_mesh(self.mesh):
                sh = self.state_shardings()
                return jax.jit(self._build_state, out_shardings=sh)(key)
        return self._build_state(key)

    # ------------------------------------------------------------------
    # the step
    # ------------------------------------------------------------------
    def _constrain(self, x, spec):
        if self.mesh is None or spec is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def _sync_grads(self, grads: PyTree, err: Optional[PyTree],
                    grad_specs: PyTree) -> Tuple[PyTree, Optional[PyTree]]:
        """Bucketed gradient synchronization.  Uncompressed: per-leaf
        sharding constraints into the solver-chosen gradient layout,
        with each bucket's leaves fused into one scheduling unit via
        ``optimization_barrier`` — a bucket's collectives issue
        together and cannot be individually sunk past later work, so
        in-flight collective buffering is bounded per bucket instead of
        per whole-tree.  Compressed: error-feedback int8 with one
        shared scale per bucket and the constraint on the wire
        (between quantize and dequantize)."""
        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_spec = treedef.flatten_up_to(grad_specs)
        if self.cfg.grad_compression:
            grads, new_err = compress_bucketed(
                grads, err, self.cfg.buckets,
                on_wire=lambda i, q: self._constrain(q, flat_spec[i]))
            return grads, new_err
        flat_g = [self._constrain(g.astype(jnp.float32), s)
                  for g, s in zip(flat_g, flat_spec)]
        out = list(flat_g)
        for idxs in bucket_slices([g.size * 4 for g in flat_g],
                                  self.cfg.buckets):
            fused = jax.lax.optimization_barrier(
                tuple(out[i] for i in idxs))
            for i, v in zip(idxs, fused):
                out[i] = v
        return treedef.unflatten(out), err

    def _make_step(self, batch_keys: Tuple[str, ...]):
        cfg = self.cfg
        model = self.model
        plan = self.plan
        state_like = self.state_struct()
        pspecs = (self.state_pspecs(state_like)
                  if self.mesh is not None and plan is not None
                  else jax.tree_util.tree_map(lambda _: None, state_like))
        # accumulated grads are carried in the layout of the optimizer
        # state they update (the solver-chosen ZeRO tiling): the update
        # math then runs fully local in the stored m/v/master layout —
        # constraining to the raw ``.grad`` tiling instead forces GSPMD
        # to re-gather f32 state across axes where the grad cut and the
        # stored-state cut differ (measured 2x wire bytes)
        grad_specs = (tree_pspecs(plan, state_like["params"],
                                  suffixes=(".opt", ".grad"))
                      if self.mesh is not None and plan is not None
                      else jax.tree_util.tree_map(
                          lambda _: None, state_like["params"]))
        bspec = {k: self._batch_spec(k) for k in batch_keys}
        n_micro = cfg.microbatches

        def micro_grads(params, mb):
            loss, grads = jax.value_and_grad(model.loss)(params, mb)
            return loss, grads

        def step_fn(state, batch):
            params = state["params"]
            if n_micro == 1:
                loss, grads = micro_grads(params, batch)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
            else:
                mbs = jax.tree_util.tree_map(
                    lambda a: a.reshape(
                        (n_micro, a.shape[0] // n_micro) + a.shape[1:]),
                    batch)

                def body(carry, mb):
                    acc, lsum = carry
                    mb = {k: self._constrain(v, bspec[k])
                          for k, v in mb.items()}
                    loss, g = micro_grads(params, mb)
                    # accumulate in f32, carried in the solver-chosen
                    # gradient sharding — never gathered between micros
                    acc = jax.tree_util.tree_map(
                        lambda a, gi, sp: self._constrain(
                            a + gi.astype(jnp.float32), sp),
                        acc, g, grad_specs)
                    return (acc, lsum + loss), None

                zeros = jax.tree_util.tree_map(
                    lambda p, sp: self._constrain(
                        jnp.zeros(p.shape, jnp.float32), sp),
                    params, grad_specs)
                (acc, lsum), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros((), jnp.float32)), mbs)
                grads = jax.tree_util.tree_map(
                    lambda a: a / n_micro, acc)
                loss = lsum / n_micro

            grads, new_err = self._sync_grads(grads, state.get("err"),
                                              grad_specs)
            ref = state["master"] if cfg.master_fp32 else params
            new_ref, new_opt, gnorm = apply_updates(ref, grads,
                                                    state["opt"],
                                                    cfg.optim)
            new_state = dict(state)
            new_state["opt"] = jax.tree_util.tree_map(
                lambda x, sp: self._constrain(x, sp) if sp is not None
                else x, new_opt, pspecs["opt"])
            if cfg.master_fp32:
                new_state["master"] = jax.tree_util.tree_map(
                    lambda x, sp: self._constrain(x, sp),
                    new_ref, pspecs["master"])
                # cast-down to the bf16 compute weight; after a sharded
                # (ZeRO) update this is the all-gather that moves bf16,
                # not f32 — the graph extension prices exactly this.  The
                # intermediate constraint pins the convert *before* the
                # gather (GSPMD otherwise happily all-gathers the f32
                # master and converts afterwards, doubling wire bytes).
                def cast_down(m, p, msp, psp):
                    y = self._constrain(m.astype(p.dtype), msp)
                    return self._constrain(y, psp)

                new_params = jax.tree_util.tree_map(
                    cast_down, new_state["master"], params,
                    pspecs["master"], pspecs["params"])
            else:
                new_params = jax.tree_util.tree_map(
                    lambda x, sp: self._constrain(x, sp),
                    new_ref, pspecs["params"])
            new_state["params"] = new_params
            if new_err is not None:
                new_state["err"] = jax.tree_util.tree_map(
                    lambda x, sp: self._constrain(x, sp),
                    new_err, pspecs.get("err", grad_specs))
            metrics = {"loss": loss, "gnorm": gnorm}
            return new_state, metrics

        if self.mesh is not None and plan is not None:
            state_sh = self.state_shardings(state_like)
            batch_sh = {k: NamedSharding(self.mesh, bspec[k])
                        for k in batch_keys}
            return jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                           donate_argnums=(0,))
        return jax.jit(step_fn, donate_argnums=(0,))

    def _jit_for(self, batch_keys: Tuple[str, ...]):
        if self._jit is None or self._jit_keys != batch_keys:
            self._jit = self._make_step(batch_keys)
            self._jit_keys = batch_keys
        return self._jit

    def step(self, state: PyTree, batch: Dict[str, Any]
             ) -> Tuple[PyTree, Dict[str, Any]]:
        """One (donated) training step.  ``batch`` leaves may be numpy
        or device arrays; with a mesh, feed committed device batches
        (data/pipeline.BatchFeed) to skip the transfer."""
        fn = self._jit_for(tuple(sorted(batch.keys())))
        if self.monitor is None:
            with _span("train.step"):
                if self.mesh is not None:
                    with jax.set_mesh(self.mesh):
                        return fn(state, batch)
                return fn(state, batch)
        import time
        t0 = time.monotonic()
        with _span("train.step"):
            if self.mesh is not None:
                with jax.set_mesh(self.mesh):
                    out = fn(state, batch)
            else:
                out = fn(state, batch)
        # host time to enqueue the step: blocks when the dispatch queue
        # backs up, so sustained growth tracks device step time
        self.monitor.observe("dispatch", time.monotonic() - t0)
        return out

    def lower_step(self, batch_like: Dict[str, Any]):
        """Lower+compile the step on ShapeDtypeStruct stand-ins (no
        allocation) — the conformance cell measures the compiled HLO's
        collectives against ``solution_breakdown`` through this."""
        fn = self._jit_for(tuple(sorted(batch_like.keys())))
        ctx = jax.set_mesh(self.mesh) if self.mesh is not None else None
        with _span("train.lower_step"):
            if ctx is not None:
                with ctx:
                    return fn.lower(self.state_struct(),
                                    batch_like).compile()
            return fn.lower(self.state_struct(), batch_like).compile()

    # ------------------------------------------------------------------
    # checkpointing (elastic)
    # ------------------------------------------------------------------
    def save(self, directory: str, step: int, state: PyTree,
             extra: Optional[Dict[str, Any]] = None) -> str:
        with _span("train.ckpt_write", step=step):
            return ckpt.save(directory, step, state, extra=extra)

    def restore(self, directory: str, step: Optional[int] = None
                ) -> Optional[Tuple[PyTree, Dict[str, Any], int]]:
        """Restore the latest (or given) step's state, re-placed under
        THIS engine's mesh and solved shardings — the elastic-restart
        path: the saving run's mesh shape is irrelevant."""
        if step is None:
            step = ckpt.latest_step(directory)
        if step is None:
            return None
        like = self.state_struct()
        fn = None
        if self.mesh is not None and self.plan is not None:
            fn = ckpt.tree_sharding_fn(self.state_shardings(like))
        state, extra = ckpt.restore(directory, step, like, sharding_fn=fn)
        return state, extra, step


def params_of(state: PyTree) -> PyTree:
    """The bf16 compute params of an engine state."""
    return state["params"]
