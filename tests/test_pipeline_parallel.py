"""Chaos-grade pipeline runtime tests over a forced 8-device host mesh.

Three subprocess runs (subprocess because the parent pytest jax is
already initialized with one device):

  schedule   pipelined forward is BIT-identical (max |diff| == 0) to the
             serial layer stack for stage counts S in {1, 2, 4} x
             n_micro in {1, S, 2S} on (S, 8/S) stage x data meshes;
             backward through the ppermute schedule is bit-identical for
             the unmicrobatched flat case (S=1, n_micro=1) and pinned to
             an ulp-scale band otherwise (microbatch accumulation — in
             lax.scan's transpose or across the schedule — sums weight
             gradients in a different order than the full-batch matmul:
             same math, different float association)
  trainer    PipelineTrainer with n_stages=1 reproduces the PR-5
             TrainEngine loss/gnorm trajectory EXACTLY (it delegates to
             the real engine), and the S in {2, 4} pipelined trajectories
             track the flat engine to ulp-scale over 4 AdamW steps
  wire       regression for the seed boundary-sharding bug: with the
             solved boundary sharding (x_spec=P("data")) each
             collective-permute hop ships only the local shard — the
             compiled HLO's cp wire bytes are exactly 1/inner_degree of
             the replicated seed behavior (x_spec=None)
"""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


_PREAMBLE = """
    import jax, jax.numpy as jnp, json
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_compat_mesh
    from repro.runtime.pipeline_parallel import (
        PipelineTrainer, _StackModel, make_stage_fn, pipeline_forward,
        split_stages)

    L, D, B = 8, 16, 32
    ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    t = jax.random.normal(jax.random.PRNGKey(2), (B, D))

    def layer(w, h):
        return jnp.tanh(h @ w)

    def loss_fn(h, y):
        return jnp.mean((h - y) ** 2)

    def stage_mesh(s):
        if s == 1:
            return make_compat_mesh((8,), ("data",))
        return make_compat_mesh((s, 8 // s), ("stage", "data"))
"""


def test_pipeline_forward_bitwise_and_grads_vs_serial():
    out = run_py(_PREAMBLE + """
    ref = x
    for i in range(L):
        ref = layer(ws[i], ref)

    def serial_loss(staged, n_micro):
        h = staged.reshape(L, D, D)
        out = x
        for i in range(L):
            out = layer(h[i], out)
        mb = B // n_micro
        om = out.reshape(n_micro, mb, D)
        tm = t.reshape(n_micro, mb, D)
        return jnp.mean(jax.vmap(loss_fn)(om, tm))

    rec = {}
    for s in (1, 2, 4):
        mesh = stage_mesh(s)
        staged = split_stages(ws, s)
        stage_fn = make_stage_fn(layer)
        xs = P("data") if s > 1 else None
        for n_micro in sorted({1, s, 2 * s}):
            y = pipeline_forward(mesh, "stage", stage_fn, staged, x,
                                 n_micro=n_micro, x_spec=xs)
            rec[f"fwd_{s}_{n_micro}"] = float(jnp.max(jnp.abs(y - ref)))

            def pipe_loss(st_):
                o = pipeline_forward(mesh, "stage", stage_fn, st_, x,
                                     n_micro=n_micro, x_spec=xs)
                mb = B // n_micro
                om = o.reshape(n_micro, mb, D)
                tm = t.reshape(n_micro, mb, D)
                return jnp.mean(jax.vmap(loss_fn)(om, tm))

            gp = jax.grad(pipe_loss)(staged)
            gs = jax.grad(serial_loss)(staged, n_micro)
            err = float(jnp.max(jnp.abs(gp - gs)))
            scale = float(jnp.max(jnp.abs(gs)))
            rec[f"grad_{s}_{n_micro}"] = err
            rec[f"gscale_{s}_{n_micro}"] = scale
    print(json.dumps(rec))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    for s in (1, 2, 4):
        for n_micro in sorted({1, s, 2 * s}):
            # forward: bit-identical, exactly zero
            assert rec[f"fwd_{s}_{n_micro}"] == 0.0, (s, n_micro, rec)
            err, scale = rec[f"grad_{s}_{n_micro}"], \
                rec[f"gscale_{s}_{n_micro}"]
            # ulp-scale band: microbatch accumulation reassociates, and
            # even the S=1 program's backward is fused differently from
            # the serial one's
            assert err <= 5e-6 * max(scale, 1e-3), (s, n_micro, rec)


def test_trainer_s1_is_engine_and_s_gt1_tracks_flat():
    out = run_py(_PREAMBLE + """
    from repro.optim.adamw import AdamWConfig
    from repro.train.engine import EngineConfig, TrainEngine

    optim = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=100)
    n_micro, steps = 8, 4
    xs = [jax.random.normal(jax.random.PRNGKey(100 + i), (B, D))
          for i in range(steps)]
    ys = [jax.random.normal(jax.random.PRNGKey(200 + i), (B, D))
          for i in range(steps)]

    # reference: the raw PR-5 engine on the wrapped stack
    model = _StackModel(layer, loss_fn, ws)
    engine = TrainEngine(model, EngineConfig(microbatches=n_micro,
                                             master_fp32=False,
                                             optim=optim), mesh=None)
    est = engine.init_state(jax.random.PRNGKey(0))
    ref_losses, ref_gnorms = [], []
    for i in range(steps):
        est, m = engine.step(est, {"x": xs[i], "y": ys[i]})
        ref_losses.append(float(m["loss"]))
        ref_gnorms.append(float(m["gnorm"]))

    rec = {"ref_losses": ref_losses, "ref_gnorms": ref_gnorms}
    for s in (1, 2, 4):
        mesh = stage_mesh(s)
        tr = PipelineTrainer(layer, loss_fn, n_stages=s, n_micro=n_micro,
                             mesh=None if s == 1 else mesh,
                             optim=optim,
                             x_spec=None if s == 1 else P("data"))
        st = tr.init(ws)
        losses, gnorms = [], []
        for i in range(steps):
            st, m = tr.step(st, xs[i], ys[i])
            losses.append(float(m["loss"]))
            gnorms.append(float(m["gnorm"]))
        rec[f"losses_{s}"] = losses
        rec[f"gnorms_{s}"] = gnorms
    print(json.dumps(rec))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    ref = rec["ref_losses"]
    # S=1 delegates to the real engine: trajectory is the engine's,
    # bit-for-bit (same jaxpr, same arithmetic)
    assert rec["losses_1"] == ref, rec
    assert rec["gnorms_1"] == rec["ref_gnorms"], rec
    for s in (2, 4):
        for a, b in zip(rec[f"losses_{s}"], ref):
            assert abs(a - b) <= 1e-5 * max(abs(b), 1e-3), (s, rec)
        for a, b in zip(rec[f"gnorms_{s}"], rec["ref_gnorms"]):
            assert abs(a - b) <= 1e-4 * max(abs(b), 1e-3), (s, rec)
    # the trajectories actually train (loss decreases over the window)
    assert ref[-1] < ref[0]


def test_boundary_sharding_halves_permute_wire_bytes():
    """Satellite regression: the seed runner always permuted the FULL
    microbatch (replicated over inner axes).  With the solved boundary
    sharding each device ships its shard: cp wire bytes drop by exactly
    the inner partition degree."""
    out = run_py(_PREAMBLE + """
    from repro.analysis import hlo
    from repro.optim.adamw import AdamWConfig

    optim = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=100)
    s, n_micro = 4, 8
    mesh = stage_mesh(s)
    rec = {}
    for tag, xs in (("sharded", P("data")), ("replicated", None)):
        tr = PipelineTrainer(layer, loss_fn, n_stages=s, n_micro=n_micro,
                             mesh=mesh, optim=optim, x_spec=xs)
        st = tr.init(ws)
        comp = tr.lower_step(
            jax.eval_shape(lambda v: v, st),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D), jnp.float32))
        stats = hlo.collect(comp.as_text(), 8)
        rec[tag] = {"counts": stats.counts,
                    "cp": stats.wire_by_kind.get("collective-permute",
                                                 0.0)}
    print(json.dumps(rec))
    """)
    rec = json.loads(out.strip().splitlines()[-1])
    inner_degree = 2                       # (4, 2) stage x data mesh
    mb, d, itemsize = 32 // 8, 16, 4
    # one cp in the forward scan body, one in its transpose
    assert rec["sharded"]["counts"]["collective-permute"] == 2
    assert rec["replicated"]["counts"]["collective-permute"] == 2
    # solved boundary sharding ships 1/inner_degree of the bytes
    assert rec["sharded"]["cp"] * inner_degree == rec["replicated"]["cp"]
    assert rec["replicated"]["cp"] == 2 * mb * d * itemsize
    assert rec["sharded"]["cp"] == 2 * mb * d * itemsize // inner_degree
