"""End-to-end behaviour tests for the whole system."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_arch
from repro.core.builders import build_graph
from repro.core.plan import ShardingPlan
from repro.core.solver import (MeshAxis, composed_cost,
                               data_parallel_assignment, solve_mesh)
from repro.data.pipeline import DataConfig
from repro.models.model import LM
from repro.optim.adamw import AdamWConfig
from repro.runtime.serve import ServeConfig, Server
from repro.runtime.train_loop import TrainConfig, train


def test_sharding_constraint_skipped_only_without_mesh():
    """Outside a mesh a plan's constraint is a no-op; under one, a spec
    the mesh cannot take raises instead of leaving the tensor unsharded."""
    import pytest

    from repro.compat import make_compat_mesh
    from repro.models.common import shard
    plan = ShardingPlan(("model",), {"x": {"model": "d_model"}})
    x = jnp.ones((4, 8))
    assert shard(x, plan, "x", ("batch", "d_model")) is x
    mesh = make_compat_mesh((1,), ("data",))
    with jax.set_mesh(mesh), pytest.raises(Exception, match="model"):
        jax.jit(lambda v: shard(v, plan, "x", ("batch", "d_model")))(x)


def test_compile_cache_dir(monkeypatch):
    """The launchers' compile cache: a fixed ``<checkout>/.cache/jax``, or
    JAX's own ``JAX_COMPILATION_CACHE_DIR`` left untouched when set."""
    import os

    from repro import hostdev
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert hostdev.JAX_CACHE_DIR == os.path.join(root, ".cache", "jax")
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert hostdev.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert hostdev.enable_compile_cache() == hostdev.JAX_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == hostdev.JAX_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_train_end_to_end_loss_decreases():
    cfg = get_arch("llama3.2-3b").reduced()
    model = LM(cfg)
    out = train(model,
                DataConfig(seed=1, vocab=cfg.vocab, seq_len=32,
                           global_batch=4),
                TrainConfig(steps=20,
                            optim=AdamWConfig(lr=2e-3, warmup_steps=2,
                                              total_steps=1000)))
    h = out["history"]
    assert h[-1]["loss"] < h[0]["loss"] - 0.3


def test_serve_end_to_end():
    cfg = get_arch("musicgen-large").reduced()
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    srv = Server(model, params, ServeConfig(slots=2, max_len=64))
    srv.admit([1, 2, 3], 0)
    srv.admit([4, 5, 6], 1)
    outs = srv.generate(8)
    assert len(outs[0]) == 8 and len(outs[1]) == 8
    assert all(0 <= t < cfg.vocab for t in outs[0] + outs[1])


def test_solver_reduces_comm_for_every_assigned_arch():
    """The paper's core claim, on the assigned architectures: the solved
    tiling never exceeds pure data parallelism's communication volume."""
    axes = [MeshAxis("data", 16), MeshAxis("model", 16)]
    for arch in ("llama3.2-3b", "qwen2.5-32b", "zamba2-2.7b",
                 "moonshot-v1-16b-a3b", "xlstm-125m"):
        cfg = get_arch(arch)
        g = build_graph(cfg, SHAPES["decode_32k"])
        sol = solve_mesh(g, axes, beam=2000)
        dp = composed_cost(g, axes, [data_parallel_assignment(g)] * 2)
        assert sol.total_bytes <= dp * 1.001, arch


def test_plan_applies_to_real_model():
    """Solver plan drives with_sharding_constraint without error even on
    a single CPU device (constraints become no-ops)."""
    cfg = get_arch("qwen2-1.5b").reduced()
    g = build_graph(cfg, SHAPES["train_4k"])
    sol = solve_mesh(g, [MeshAxis("data", 4), MeshAxis("model", 2)],
                     beam=2000)
    plan = ShardingPlan.from_graph_solution(sol, g)
    model = LM(cfg, plan=plan)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    logits, _ = model.forward(params, toks)
    assert logits.shape == (2, 16, cfg.vocab)
