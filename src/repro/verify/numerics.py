"""Differential numerics: a solved plan's sharded step must compute the
same numbers as the single-device serial program.

For each conformance cell the *same parameter values* (same PRNG key)
run through:
  serial    LM(cfg) with no plan, jit on one device
  sharded   LM(cfg, plan=...) with plan shardings on the forced-host
            mesh (params/optimizer/cache device_put per the plan)

train cells compare the scalar loss; prefill cells the full logits;
decode cells the per-step logits over several steps (exercising KV / SSM
/ xLSTM state sharding).  bf16 models on different device layouts
re-associate reductions, so tolerances are bands, not equality — see
DESIGN.md §9 for the declared values.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# declared numerics tolerance bands (DESIGN.md §9)
LOSS_ATOL = 0.05          # scalar loss, bf16 model
LOGITS_ATOL = 0.25        # max-abs over logits, bf16 model

DECODE_STEPS = 4


def _batch(cfg, shape, key):
    import jax
    import jax.numpy as jnp

    b, s = shape.global_batch, shape.seq_len
    if cfg.embed_stub:
        emb = jax.random.normal(key, (b, s, cfg.d_model), jnp.float32)
        labels = jax.random.randint(key, (b, s), 0, cfg.vocab)
        return {"embeds": emb, "labels": labels}
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}


def run_numerics(cfg, shape, plan, mesh) -> Dict[str, object]:
    """Returns a record with serial/sharded values, the observed error
    and the pass verdict for this cell's kind."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from ..models.model import LM
    from ..models.sharding import (CACHE_RULES, batch_pspec,
                                   tree_shardings)

    key = jax.random.PRNGKey(0)
    serial = LM(cfg)
    params = serial.init(key)
    batch = _batch(cfg, shape, key)
    rec: Dict[str, object] = {"kind": shape.kind}

    sharded = LM(cfg, plan=plan, mesh=mesh)
    with jax.set_mesh(mesh):
        psh = tree_shardings(plan, jax.eval_shape(serial.init, key), mesh)
        p1 = jax.device_put(params, psh)

        if shape.kind == "train":
            l0 = float(jax.jit(serial.loss)(params, batch))
            bspec = batch_pspec(plan, "train")
            # embed_stub batches carry [B,S,D] "embeds" instead of
            # [B,S] "tokens" — same convention as compile.py
            b1 = {k: jax.device_put(v, NamedSharding(
                      mesh, batch_pspec(plan, "prefill")
                      if k == "embeds" else bspec["tokens"]))
                  for k, v in batch.items()}
            l1 = float(jax.jit(sharded.loss)(p1, b1))
            err = abs(l0 - l1)
            rec.update(serial_loss=l0, sharded_loss=l1, abs_err=err,
                       tol=LOSS_ATOL, ok=bool(err < LOSS_ATOL))
            return rec

        if shape.kind == "prefill":
            logits0, _ = jax.jit(serial.forward)(
                params, batch.get("tokens"), batch.get("embeds"))
            bspec = batch_pspec(plan, "prefill")
            toks = {k: jax.device_put(v, NamedSharding(mesh, bspec))
                    for k, v in batch.items() if k != "labels"}
            logits1, _ = jax.jit(sharded.forward)(
                p1, toks.get("tokens"), toks.get("embeds"))
            err = float(jnp.max(jnp.abs(
                logits0.astype(jnp.float32) -
                logits1.astype(jnp.float32))))
            rec.update(max_abs_err=err, tol=LOGITS_ATOL,
                       logit_scale=float(jnp.max(jnp.abs(
                           logits0.astype(jnp.float32)))),
                       ok=bool(err < LOGITS_ATOL))
            return rec

        # decode: step-by-step against the serial stepper
        b = shape.global_batch
        cache0 = serial.init_cache(b, shape.seq_len)
        cache1 = jax.device_put(
            sharded.init_cache(b, shape.seq_len),
            tree_shardings(plan, jax.eval_shape(
                lambda: serial.init_cache(b, shape.seq_len)), mesh,
                rules=CACHE_RULES))
        tok_sh = NamedSharding(mesh, batch_pspec(plan, "decode"))
        step0 = jax.jit(serial.decode_step)
        step1 = jax.jit(sharded.decode_step)
        if cfg.embed_stub:
            toks = jax.random.normal(key, (DECODE_STEPS, b, cfg.d_model),
                                     jnp.float32)
        else:
            toks = jax.random.randint(key, (DECODE_STEPS, b), 0,
                                      cfg.vocab)
        max_err = 0.0
        scale = 0.0
        for i in range(DECODE_STEPS):
            lg0, cache0 = step0(params, cache0, toks[i])
            lg1, cache1 = step1(p1, cache1,
                                jax.device_put(toks[i], tok_sh))
            a = np.asarray(lg0, np.float32)
            bb = np.asarray(lg1, np.float32)
            max_err = max(max_err, float(np.max(np.abs(a - bb))))
            scale = max(scale, float(np.max(np.abs(a))))
        rec.update(steps=DECODE_STEPS, max_abs_err=max_err,
                   logit_scale=scale, tol=LOGITS_ATOL,
                   ok=bool(max_err < LOGITS_ATOL))
        return rec
