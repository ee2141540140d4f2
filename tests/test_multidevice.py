"""Multi-device behaviour (subprocess with forced host device count):
- solver-plan sharded train step == single-device numerics
- pipeline parallelism == serial stage execution
- elastic checkpoint reshard across mesh shapes
These run as subprocesses because the parent pytest process has already
initialized jax with 1 device."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# Subprocess tests (each spawns a forced-host-device jax): excluded from
# the default `-m "not slow"` tier-1 run; CI runs them in a dedicated job
# (`pytest -m multidevice`).
pytestmark = [pytest.mark.multidevice, pytest.mark.slow]

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestShardedTraining:
    def test_sharded_step_matches_single_device(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np, json
            from repro.compat import make_compat_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs import get_arch
            from repro.configs.base import ShapeConfig
            from repro.core.builders import transformer_graph
            from repro.core.plan import ShardingPlan
            from repro.core.solver import MeshAxis, solve_mesh
            from repro.models.model import LM
            from repro.models.sharding import tree_shardings, batch_pspec

            cfg = get_arch("llama3.2-3b").reduced()
            shape = ShapeConfig("t", 32, 8, "train")
            g = transformer_graph(cfg, shape)
            sol = solve_mesh(g, [MeshAxis("data", 4), MeshAxis("model", 2)],
                             beam=2000)
            plan = ShardingPlan.from_graph_solution(sol, g)
            mesh = make_compat_mesh((4, 2), ("data", "model"))

            key = jax.random.PRNGKey(0)
            toks = jax.random.randint(key, (8, 32), 0, cfg.vocab)
            batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}

            # single device reference
            m0 = LM(cfg)
            p0 = m0.init(key)
            l0 = float(m0.loss(p0, batch))

            # sharded
            m1 = LM(cfg, plan=plan)
            with jax.set_mesh(mesh):
                psh = tree_shardings(plan, jax.eval_shape(m1.init, key),
                                     mesh)
                p1 = jax.jit(m1.init, out_shardings=psh)(key)
                bspec = batch_pspec(plan, "train")
                b1 = {k: jax.device_put(v, NamedSharding(mesh, bspec[k]))
                      for k, v in batch.items()}
                l1 = float(jax.jit(m1.loss)(p1, b1))
            print(json.dumps({"l0": l0, "l1": l1}))
        """)
        r = json.loads(out.strip().splitlines()[-1])
        assert abs(r["l0"] - r["l1"]) < 0.05, r

    def test_grad_step_sharded_improves_loss(self):
        out = run_py("""
            import jax, jax.numpy as jnp, json
            from repro.compat import make_compat_mesh
            from repro.configs import get_arch
            from repro.configs.base import ShapeConfig
            from repro.core.builders import transformer_graph
            from repro.core.plan import ShardingPlan
            from repro.core.solver import MeshAxis, solve_mesh
            from repro.models.model import LM
            from repro.data.pipeline import DataConfig
            from repro.runtime.train_loop import TrainConfig, train
            from repro.optim.adamw import AdamWConfig

            cfg = get_arch("qwen2-1.5b").reduced()
            shape = ShapeConfig("t", 32, 8, "train")
            g = transformer_graph(cfg, shape)
            sol = solve_mesh(g, [MeshAxis("data", 4), MeshAxis("model", 2)],
                             beam=2000)
            plan = ShardingPlan.from_graph_solution(sol, g)
            mesh = make_compat_mesh((4, 2), ("data", "model"))
            model = LM(cfg, plan=plan)
            dcfg = DataConfig(seed=0, vocab=cfg.vocab, seq_len=32,
                              global_batch=8)
            with jax.set_mesh(mesh):
                out = train(model, dcfg, TrainConfig(
                    steps=12, optim=AdamWConfig(lr=2e-3, warmup_steps=2)))
            h = out["history"]
            print(json.dumps({"first": h[0]["loss"],
                              "last": h[-1]["loss"]}))
        """)
        r = json.loads(out.strip().splitlines()[-1])
        assert r["last"] < r["first"], r


class TestMoEShardMap:
    def test_sharded_moe_matches_local(self):
        out = run_py("""
            import jax, jax.numpy as jnp, json
            from repro.compat import make_compat_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs.base import ArchConfig, MoECfg
            from repro.models.moe import init_moe, moe_ffn
            from repro.core.plan import ShardingPlan

            cfg = ArchConfig(name="t", family="moe", n_layers=1,
                             d_model=16, n_heads=2, n_kv_heads=2, d_ff=32,
                             vocab=64, head_dim=8,
                             moe=MoECfg(n_experts=8, top_k=2,
                                        d_ff_expert=32,
                                        capacity_factor=8.0))
            key = jax.random.PRNGKey(0)
            params = init_moe(key, cfg, jnp.float32)
            x = jax.random.normal(key, (8, 4, 16))
            y_ref, _ = moe_ffn(params, x, cfg)

            mesh = make_compat_mesh((2, 4), ("data", "model"))
            plan = ShardingPlan(("data", "model"), {
                "x": {"data": "batch", "model": None},
                "moe_up": {"data": None, "model": "expert"},
                "moe_down": {"data": None, "model": "expert"}})
            with jax.set_mesh(mesh):
                xs = jax.device_put(x, NamedSharding(mesh, P("data")))
                ps = {k: jax.device_put(v, NamedSharding(
                          mesh, P("model") if k.startswith("w_") else P()))
                      for k, v in params.items()}
                y, _ = jax.jit(
                    lambda p, x: moe_ffn(p, x, cfg, plan, mesh))(ps, xs)
            err = float(jnp.max(jnp.abs(y - y_ref)))
            print(json.dumps({"err": err}))
        """)
        import json as _json
        r = _json.loads(out.strip().splitlines()[-1])
        assert r["err"] < 1e-4, r


class TestPipelineParallel:
    def test_pipeline_matches_serial(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np, json
            from repro.compat import make_compat_mesh
            from repro.runtime.pipeline_parallel import (
                make_stage_fn, pipeline_forward, split_stages)
            mesh = make_compat_mesh((4,), ("stage",))
            L, D, B = 8, 16, 12
            key = jax.random.PRNGKey(0)
            ws = jax.random.normal(key, (L, D, D)) * 0.3
            x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

            def layer(w, x):
                return jnp.tanh(x @ w)

            # serial reference
            ref = x
            for i in range(L):
                ref = layer(ws[i], ref)

            staged = split_stages(ws, 4)
            stage_fn = make_stage_fn(layer)
            y = pipeline_forward(mesh, "stage", stage_fn, staged, x,
                                 n_micro=4)
            err = float(jnp.max(jnp.abs(y - ref)))
            print(json.dumps({"err": err}))
        """, devices=4)
        r = json.loads(out.strip().splitlines()[-1])
        assert r["err"] < 1e-5, r

    def test_pipeline_differentiable(self):
        out = run_py("""
            import jax, jax.numpy as jnp, json
            from repro.compat import make_compat_mesh
            from repro.runtime.pipeline_parallel import (
                make_stage_fn, pipeline_forward, split_stages)
            mesh = make_compat_mesh((2,), ("stage",))
            L, D, B = 4, 8, 4
            ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.3
            x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
            layer = lambda w, x: jnp.tanh(x @ w)
            staged = split_stages(ws, 2)

            def loss(staged):
                y = pipeline_forward(mesh, "stage", make_stage_fn(layer),
                                     staged, x, n_micro=2)
                return jnp.sum(y ** 2)

            g = jax.grad(loss)(staged)
            ok = bool(jnp.all(jnp.isfinite(g)) & (jnp.max(jnp.abs(g)) > 0))
            print(json.dumps({"ok": ok}))
        """, devices=2)
        r = json.loads(out.strip().splitlines()[-1])
        assert r["ok"], r


class TestShardedPagedServing:
    def test_paged_pool_sharded_matches_single_device(self):
        """Solver-plan sharded *paged* serving on the 4x2 mesh: the
        block pool and the block table are placed by the plan (the
        table is a solver tensor role, sharded with the cache batch
        cut), and teacher-forced decode logits track the single-device
        linear engine within the decode numerics band."""
        out = run_py("""
            import jax, numpy as np, json
            from repro.compat import make_compat_mesh
            from repro.configs import get_arch
            from repro.configs.base import ShapeConfig
            from repro.core.builders import build_graph
            from repro.core.plan import ShardingPlan
            from repro.core.solver import MeshAxis, solve_mesh
            from repro.models.model import LM
            from repro.runtime.serve import ServeConfig, Server

            cfg = get_arch("qwen2-1.5b").reduced()
            g = build_graph(cfg, ShapeConfig("serve", 32, 4, "decode"))
            sol = solve_mesh(g, [MeshAxis("data", 4),
                                 MeshAxis("model", 2)], beam=2000)
            plan = ShardingPlan.from_graph_solution(sol, g)
            mesh = make_compat_mesh((4, 2), ("data", "model"))

            params = LM(cfg).init(jax.random.PRNGKey(0))
            rng = np.random.default_rng(0)
            prompts = [rng.integers(0, cfg.vocab,
                                    size=int(rng.integers(3, 12))).tolist()
                       for _ in range(4)]
            scfg = ServeConfig(slots=4, max_len=32, paged=True,
                               block_len=8)
            ref = Server(LM(cfg), params,
                         ServeConfig(slots=4, max_len=32))
            srd = Server(LM(cfg, plan=plan, mesh=mesh), params, scfg,
                         mesh=mesh)
            for s, p in enumerate(prompts):
                ref.admit(p, s)
                srd.admit(p, s)
            err = float(np.max(np.abs(ref.prefill_logits
                                      - srd.prefill_logits)))
            for _ in range(4):
                forced = ref.next_tok.copy()
                ref.decode_once(forced)
                srd.decode_once(forced)
                err = max(err, float(np.max(np.abs(
                    np.asarray(ref.last_logits)
                    - np.asarray(srd.last_logits)))))
            print(json.dumps({"err": err}))
        """)
        r = json.loads(out.strip().splitlines()[-1])
        assert r["err"] < 0.06, r


class TestElasticReshard:
    def test_checkpoint_restores_onto_different_mesh(self, tmp_path):
        out = run_py(f"""
            import jax, jax.numpy as jnp, numpy as np, json
            from repro.compat import make_compat_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.checkpoint import ckpt
            mesh8 = make_compat_mesh((8,), ("data",))
            sh8 = NamedSharding(mesh8, P("data"))
            x = jax.device_put(jnp.arange(64, dtype=jnp.float32), sh8)
            ckpt.save("{tmp_path}", 1, {{"x": x}})

            mesh4 = make_compat_mesh((4, 2), ("data", "model"))
            sh4 = NamedSharding(mesh4, P("model"))
            out, _ = ckpt.restore("{tmp_path}", 1, {{"x": x}},
                                  sharding_fn=lambda k, a: sh4)
            ok = bool(jnp.all(out["x"] == x)) and out["x"].sharding == sh4
            print(json.dumps({{"ok": ok}}))
        """, devices=8)
        r = json.loads(out.strip().splitlines()[-1])
        assert r["ok"], r
