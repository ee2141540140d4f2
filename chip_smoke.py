#!/usr/bin/env python3
"""Smoke run of the system's main paths on TPU v5e chips.

    python chip_smoke.py              # one chip: train phase + serve phase
    python chip_smoke.py --chips 4    # four chips: solved-plan training only

One chip:
  train  qwen2-1.5b at its published widths, depth cut 28 -> 8 layers,
         through ``repro.launch.train.main`` (LM + TrainEngine + BatchFeed,
         weights from --seed): 10 steps at batch 4 x 1024.  The loss must be
         finite and fall; the step's memory_analysis() must fit the chip.
  serve  qwen2-1.5b at full size (28 layers) through ``runtime.serve.Server``
         with the default attn_impl="auto", which must resolve to compiled
         Pallas prefill-offset and decode kernels.  The same requests run
         teacher-forced through attn_impl="xla" on the same weights; logits
         must agree within logit_rel_band().
Four chips (--chips 4):
  qwen2-1.5b at full depth, which does not fit one chip, trained on a
  (data 2, model 2) mesh under solve_cell_plan's plan; then the 8-layer
  config under its own solved plan against the same config on one device,
  loss trajectories within verify/train_cell.py's TRAIN_LOSS_ATOL.

The script runs in one process (a chip belongs to one process at a time)
and exits non-zero, printing no result line, unless JAX finds a TPU and
every phase passes.  The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2-1.5b"
TRAIN_LAYERS = 8          # 28 -> 8: the 28-layer step needs 26 GB (16 GB chip)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_WARMUP = 4, 1024, 10, 2
TRAIN_LR = 3e-3           # ramps over the optimizer's 100 warm-up steps
SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS, SERVE_MAX_LEN = 8, 64, 32, 128
FULL_BATCH, FULL_STEPS = 8, 4
CMP_STEPS = 5


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {msg}")


def logit_rel_band(n_layers: int) -> float:
    """Relative L2 band for kernel-vs-XLA logits.  The two programs differ
    only in attention; each path rounds its attention output to bf16 (2^-8
    relative), so each layer adds at most about one bf16 ulp of error, with
    a sign independent of the other layers'.  Through n_layers residual
    layers that grows as sqrt(n_layers) * 2^-8 per path; two paths -> x2."""
    return 2.0 * math.sqrt(n_layers) * 2.0 ** -8


class CacheCounter:
    """Counts JAX persistent compilation cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        self.saved_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved_s += secs


def gb(n: float) -> str:
    return f"{n / 1e9:.3f} GB"


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def train_phase(cfg, out_dir: str, seed: int, batch: int, seq: int,
                steps: int, warmup: int, lr: float) -> None:
    import jax

    from repro.configs.base import ShapeConfig
    from repro.launch import train as train_cli
    from repro.launch.compile import input_specs
    from repro.models.model import LM
    from repro.optim.adamw import AdamWConfig
    from repro.train.engine import EngineConfig, TrainEngine

    dev = jax.devices()[0]
    # the same engine the launcher builds (EngineConfig defaults, the
    # launcher's optimizer settings), compiled ahead to read its memory
    engine = TrainEngine(LM(cfg), EngineConfig(
        optim=AdamWConfig(lr=lr, total_steps=steps)))
    ma = engine.lower_step(input_specs(
        cfg, ShapeConfig("smoke", seq, batch, "train"))).memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)
    limit = dev.memory_stats()["bytes_limit"]
    log(f"train: {cfg.name} {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.hd}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}; batch {batch} x seq {seq}")
    log(f"train: memory_analysis arguments {gb(ma.argument_size_in_bytes)}, "
        f"temporaries {gb(ma.temp_size_in_bytes)}, outputs "
        f"{gb(ma.output_size_in_bytes)} (aliased "
        f"{gb(ma.alias_size_in_bytes)}); needs {gb(need)} of {gb(limit)}")
    check(need <= limit, "the train step does not fit the chip")
    del engine
    gc.collect()

    out = os.path.join(out_dir, "train_1chip.json")
    rc = train_cli.main([
        "--arch", cfg.name, "--layers", str(cfg.n_layers),
        "--batch", str(batch), "--seq", str(seq), "--steps", str(steps),
        "--warmup", str(warmup), "--log-every", "1", "--lr", str(lr),
        "--seed", str(seed), "--json-out", out])
    check(rc == 0, f"repro.launch.train exited {rc}")
    with open(out) as f:
        rec = json.load(f)
    losses = rec["losses"]
    log(f"train: losses {[round(x, 4) for x in losses]}")
    check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    check(all(math.isfinite(x) for x in losses), "non-finite loss")
    check(losses[-1] < losses[0],
          f"loss did not fall ({losses[0]} -> {losses[-1]})")
    stats = dev.memory_stats()
    log(f"train: step time after {warmup} warm-up steps "
        f"{rec['mean_step_s'] * 1e3:.1f} ms (block_until_ready each step, "
        f"{rec['tokens_per_s']:.0f} tokens/s); peak_bytes_in_use "
        f"{gb(stats['peak_bytes_in_use'])}")


def _compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def serve_phase(cfg, seed: int, n_req: int, prompt_len: int, new_tokens: int,
                max_len: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import _default_interpret
    from repro.models.model import LM
    from repro.runtime.serve import ServeConfig, Server

    model = LM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"serve: {cfg.name} {cfg.n_layers} layers, {n_params / 1e9:.3f}B "
        f"params bf16; {n_req} requests x {prompt_len} prompt tokens, "
        f"{new_tokens} new tokens each")
    scfg = ServeConfig(slots=n_req, max_len=max_len, prefill_chunk=prompt_len)
    kern = Server(model, params, scfg)                  # attn_impl="auto"
    ref = Server(model, params, dataclasses.replace(scfg, attn_impl="xla"))
    check(kern.model.attn_impl == "pallas",
          f"attn_impl auto resolved to {kern.model.attn_impl!r}")
    check(not _default_interpret(), "Pallas kernels in interpret mode")

    rng = np.random.default_rng(seed)
    for p in rng.integers(0, cfg.vocab, (n_req, prompt_len)):
        kern.submit(p.tolist(), new_tokens)
        ref.submit(p.tolist(), new_tokens)

    band = logit_rel_band(cfg.n_layers)
    errs = []

    def compare(a, b, rows):
        a = np.asarray(a, np.float32)[rows]
        b = np.asarray(b, np.float32)[rows]
        check(np.isfinite(a).all() and np.isfinite(b).all(),
              "non-finite logits")
        errs.extend(np.linalg.norm(a - b, axis=-1)
                    / np.linalg.norm(b, axis=-1))

    t0 = time.perf_counter()
    kern.admit_waiting()
    ref.admit_waiting()
    compare(kern.prefill_logits, ref.prefill_logits, np.arange(n_req))
    steps = 0
    while kern.active.any():
        rows = np.nonzero(kern.active)[0]
        feed = kern.next_tok.copy()
        kern.decode_once()
        ref.decode_once(forced_tokens=feed)      # teacher-forced
        compare(kern.last_logits, ref.last_logits, rows)
        steps += 1
    wall = time.perf_counter() - t0
    check(not ref.active.any() and not kern.waiting, "requests left over")

    agree = total = 0
    for rid, toks in kern.outputs.items():
        check(len(toks) == new_tokens, f"request {rid}: {len(toks)} tokens")
        agree += sum(int(a == b) for a, b in zip(toks, ref.outputs[rid]))
        total += len(toks)
    log(f"serve: {steps} decode steps, {kern.prefill_dispatches} prefill "
        f"dispatches, {wall:.2f} s wall for both engines (compiles "
        f"included)")
    log(f"serve: logits kernel vs xla, relative L2 max {max(errs):.4e}, "
        f"mean {float(np.mean(errs)):.4e} over {len(errs)} rows "
        f"(band {band:.5f} = 2 sqrt({cfg.n_layers}) 2^-8)")
    log(f"serve: greedy tokens agreeing {agree}/{total} "
        f"({agree / total:.4f})")
    check(max(errs) <= band, "kernel logits outside the band")

    # the programs that ran hold the compiled kernels
    chunk = jnp.zeros((prompt_len,), jnp.int32)
    vec = jnp.zeros((n_req,), jnp.int32)
    prefill_txt = _compiled_text(kern._prefill, kern.params, kern.cache,
                                 chunk, 0, prompt_len)
    decode_txt = _compiled_text(kern._decode, kern.params, kern.cache, vec,
                                vec, vec, jnp.ones((n_req,), bool))
    n_pre = prefill_txt.count("tpu_custom_call")
    n_dec = decode_txt.count("tpu_custom_call")
    log(f"serve: tpu_custom_call in compiled prefill {n_pre}, decode {n_dec}")
    check(n_pre > 0 and n_dec > 0, "a serving program holds no Pallas kernel")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def full_depth_phase(cfg, out_dir: str, seed: int, batch: int, seq: int,
                     steps: int) -> None:
    import jax

    from repro.launch import train as train_cli

    out = os.path.join(out_dir, "train_4chip_full.json")
    rc = train_cli.main([
        "--arch", cfg.name, "--mesh", "2x2", "--plan", "auto",
        "--batch", str(batch), "--seq", str(seq), "--steps", str(steps),
        "--warmup", "1", "--log-every", "1", "--lr", str(TRAIN_LR),
        "--seed", str(seed), "--json-out", out])
    check(rc == 0, f"repro.launch.train exited {rc}")
    with open(out) as f:
        rec = json.load(f)
    losses = rec["losses"]
    log(f"full depth: {cfg.n_layers} layers on (data 2, model 2), "
        f"losses {[round(x, 4) for x in losses]}, step "
        f"{rec['mean_step_s'] * 1e3:.1f} ms after 1 warm-up step")
    check(all(math.isfinite(x) for x in losses), "non-finite loss")
    drift = rec["drift"]
    check(drift is not None, "no drift record")
    log(f"full depth: drift predicted {drift['predicted_wire_bytes']:.6g} B, "
        f"measured {drift['measured_wire_bytes']:.6g} B, ratio "
        f"{drift['ratio']:.4f} (band {drift['band']})")
    peaks = []
    for d in jax.devices():
        st = d.memory_stats()
        peaks.append(st["peak_bytes_in_use"])
        log(f"full depth: device {d.id} peak_bytes_in_use "
            f"{gb(st['peak_bytes_in_use'])}, bytes_in_use "
            f"{gb(st['bytes_in_use'])} of {gb(st['bytes_limit'])}")
    state_bytes = 14 * cfg.param_count()   # bf16 + f32 master, m, v
    log(f"full depth: training state {gb(state_bytes)} in all")
    check(all(p > 0 for p in peaks), "a device held nothing")
    check(max(peaks) < state_bytes, "one device held the whole state")


def compare_phase(cfg, seed: int, batch: int, seq: int, steps: int) -> None:
    import jax

    from repro.compat import make_compat_mesh
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, host_batch
    from repro.launch.compile import plan_from_record, solve_cell_plan
    from repro.launch.mesh import mesh_to_solver_axes
    from repro.models.model import LM
    from repro.optim.adamw import AdamWConfig
    from repro.train.engine import EngineConfig, TrainEngine
    from repro.verify.train_cell import TRAIN_LOSS_ATOL

    ecfg = EngineConfig(optim=AdamWConfig(lr=2e-3, warmup_steps=2,
                                          total_steps=1000))
    dcfg = DataConfig(seed=seed, vocab=cfg.vocab, seq_len=seq,
                      global_batch=batch)
    key = jax.random.PRNGKey(seed)

    def trajectory(engine):
        state = engine.init_state(key)
        losses = []
        for step in range(steps):
            state, m = engine.step(state, host_batch(dcfg, step))
            losses.append(float(m["loss"]))
        return losses

    ref = trajectory(TrainEngine(LM(cfg), ecfg))        # one device
    gc.collect()
    mesh = make_compat_mesh((2, 2), ("data", "model"))
    shape = ShapeConfig(f"train{batch}x{seq}", seq, batch, "train")
    rec = solve_cell_plan(cfg, shape, mesh_to_solver_axes(mesh),
                          mesh_name="host2x2_mp",
                          graph_kwargs={"master_fp32": True,
                                        "error_feedback": False})
    plan = plan_from_record(rec)
    sharded = trajectory(TrainEngine(LM(cfg, plan=plan, mesh=mesh), ecfg,
                                     mesh=mesh))
    d = max(abs(a - b) for a, b in zip(ref, sharded))
    log(f"compare: {cfg.n_layers} layers, batch {batch} x {seq}: one device "
        f"{[round(x, 4) for x in ref]}")
    log(f"compare: solved plan on (data 2, model 2) "
        f"{[round(x, 4) for x in sharded]}")
    log(f"compare: max |dloss| {d:.5f} (band {TRAIN_LOSS_ATOL})")
    check(all(math.isfinite(x) for x in ref + sharded), "non-finite loss")
    check(d < TRAIN_LOSS_ATOL, "sharded trajectory outside the band")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip solved-plan phases")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".cache",
                                                  "chip_smoke"),
                    help="directory for the phases' JSON records")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {src}")
    sys.path.insert(0, src)

    import jax

    from repro.configs.base import get_arch
    from repro.hostdev import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform!r})")
    check(len(devs) >= args.chips,
          f"{len(devs)} device(s), --chips {args.chips}")
    log(f"device: {devs[0].device_kind} x {len(devs)}; jax {jax.__version__}")
    os.makedirs(args.out, exist_ok=True)

    t0 = time.perf_counter()
    full = get_arch(ARCH)
    cut = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    if args.chips == 1:
        train_phase(cut, args.out, args.seed, TRAIN_BATCH, TRAIN_SEQ,
                    TRAIN_STEPS, TRAIN_WARMUP, TRAIN_LR)
        gc.collect()
        serve_phase(full, args.seed, SERVE_REQUESTS, PROMPT_LEN, NEW_TOKENS,
                    SERVE_MAX_LEN)
    else:
        full_depth_phase(full, args.out, args.seed, FULL_BATCH, TRAIN_SEQ,
                         FULL_STEPS)
        gc.collect()
        compare_phase(cut, args.seed, TRAIN_BATCH, TRAIN_SEQ, CMP_STEPS)
    log(f"compile cache {cache_dir}: {cache.hits} hits, {cache.misses} "
        f"misses, {cache.saved_s:.1f} s of compile saved; total "
        f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
