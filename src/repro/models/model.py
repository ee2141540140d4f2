"""The LM model family: dense GQA / MoE / Mamba2-SSM / zamba-hybrid /
xLSTM / VLM & audio backbones — one functional implementation, stacked
layer params scanned with per-layer remat.

Params are plain nested dicts of jnp arrays.  Layer stacks carry a
leading [L] axis and run under jax.lax.scan so the compiled HLO is one
layer body regardless of depth (essential for the 80-layer dry-runs)."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import attention as attention_mod
from .attention import (attend_cache, attend_paged, attention,
                        flash_attention_xla)
from .common import (dense_init, embed_init, rms_norm, rope, shard,
                     softmax_cross_entropy)
from .mamba import (init_mamba, init_mamba_state, mamba_forward, mamba_step)
from .moe import init_moe, moe_ffn
from .xlstm import (init_mlstm, init_mlstm_state, init_slstm,
                    init_slstm_state, mlstm_forward, mlstm_step,
                    slstm_forward, slstm_step)

PyTree = Any


# ---------------------------------------------------------------------------
# per-slot cache surgery (used by the serving engine, runtime/serve.py)
# ---------------------------------------------------------------------------
# Cache pytrees have exactly one rank-1 [B] leaf ("pos"); every other
# leaf carries a leading layer-stack axis with batch at axis 1 (see
# init_cache).  These helpers slice / merge / reset one slot's row so
# admission and chunked prefill touch only that request's state.

def _cache_batch_axis(path) -> int:
    last = path[-1]
    key = getattr(last, "key", getattr(last, "idx", last))
    # rank-1 "pos" and the paged block table are indexed [slot, ...];
    # every other leaf stacks layers first with batch at axis 1.  The
    # paged block *pool* has no batch axis at all — slot_slice/slot_merge
    # are meaningless there (reset_slot short-circuits for paged caches).
    return 0 if str(key) in ("pos", "block_table") else 1


def slot_slice(cache: PyTree, slot) -> PyTree:
    """Batch-1 view of one slot's cache row (batch axis kept)."""
    return jax.tree_util.tree_map_with_path(
        lambda p, a: jax.lax.dynamic_slice_in_dim(
            a, slot, 1, _cache_batch_axis(p)), cache)


def slot_merge(cache: PyTree, sub: PyTree, slot) -> PyTree:
    """Write a batch-1 cache back into ``slot``'s row of the pool."""
    return jax.tree_util.tree_map_with_path(
        lambda p, a, b: jax.lax.dynamic_update_slice_in_dim(
            a, b.astype(a.dtype), slot, _cache_batch_axis(p)), cache, sub)


def prefill_parallel_ok(cfg: ArchConfig) -> bool:
    """Whether LM.prefill_chunk can run a chunk in parallel (offset
    flash attention against a linear KV cache): the decode dense branch
    with no ring-buffer SWA cache.  Recurrent families (ssm / xlstm /
    hybrid) scan the single-token step instead.  The one source of
    truth — benchmarks pick their per-path gates through this."""
    return (not (cfg.family == "hybrid" and cfg.attn_every)
            and cfg.xlstm is None and cfg.family != "ssm"
            and cfg.swa_window is None)


def paged_ok(cfg: ArchConfig) -> bool:
    """Whether the paged block-pool KV layout applies: the dense
    full-attention decode branch (same precondition as parallel prefill —
    recurrent state has no sequence axis to page, and a ring-buffer SWA
    cache is already O(window))."""
    return prefill_parallel_ok(cfg)


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.hd
    h, kv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _init_mlp(key, cfg: ArchConfig, dtype, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "wg": dense_init(ks[0], (d, f), dtype=dtype),
        "wu": dense_init(ks[1], (d, f), dtype=dtype),
        "wd": dense_init(ks[2], (f, d), dtype=dtype),
    }


def _init_dense_layer(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
         "ln2": jnp.ones((cfg.d_model,), jnp.float32),
         "attn": _init_attn(k1, cfg, dtype)}
    if cfg.moe is not None:
        p["moe"] = init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = _init_mlp(k2, cfg, dtype)
    return p


def _stack(key, n: int, fn):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


# ---------------------------------------------------------------------------
# forward blocks
# ---------------------------------------------------------------------------

def _attn_forward(p, x, cfg: ArchConfig, positions, plan, impl):
    b, s, d = x.shape
    hd, h, kv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard(q, plan, "wq.out", ("batch", "seq", "heads"))
    q = rope(q.reshape(b, s, h, hd), positions, cfg.rope_theta)
    k = rope(k.reshape(b, s, kv, hd), positions, cfg.rope_theta)
    v = v.reshape(b, s, kv, hd)
    with jax.named_scope("attn"):
        o = attention(q, k, v, causal=True, window=cfg.swa_window,
                      impl=impl)
    return o.reshape(b, s, h * hd) @ p["wo"]


def _mlp_forward(p, x):
    with jax.named_scope("mlp"):
        g = jax.nn.silu((x @ p["wg"]).astype(jnp.float32)).astype(x.dtype)
        return (g * (x @ p["wu"])) @ p["wd"]


def _dense_layer_forward(p, x, cfg: ArchConfig, positions, plan, impl,
                         mesh=None):
    # constrain the *post-norm* activations too: their f32 cotangents
    # otherwise lose sharding and GSPMD all-gathers them into the
    # weight-gradient dots (8.5 GB/layer in the dry-run — §Perf)
    xn1 = shard(rms_norm(x, p["ln1"], cfg.norm_eps), plan, "x",
                ("batch", "seq", "d_model"))
    h = _attn_forward(p["attn"], xn1, cfg, positions, plan, impl)
    x = x + h
    x = shard(x, plan, "x", ("batch", "seq", "d_model"))
    xn = shard(rms_norm(x, p["ln2"], cfg.norm_eps), plan, "x",
               ("batch", "seq", "d_model"))
    if cfg.moe is not None:
        y, aux = moe_ffn(p["moe"], xn, cfg, plan, mesh)
    else:
        y, aux = _mlp_forward(p["mlp"], xn), 0.0
    x = x + y
    return shard(x, plan, "x", ("batch", "seq", "d_model")), aux


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LM:
    cfg: ArchConfig
    plan: Any = None                 # ShardingPlan or None
    attn_impl: str = "xla"           # "xla" | "pallas"
    ssd_impl: str = "xla"            # "xla" | "pallas" (ssm/hybrid scan)
    mesh: Any = None                 # needed for shard_map MoE dispatch
    # "scan": lax.scan over stacked layers (production; one-layer HLO).
    # "unrolled": python loop — used by the dry-run cost probes because
    # XLA cost_analysis counts a while body once (see analysis/roofline).
    layer_loop: str = "scan"

    def _fold(self, body, x, stacked):
        """scan-or-unroll over the leading layer axis; body returns
        (carry, per-layer-out).  The carry ``x`` may be any pytree (the
        decode step carries (activations, stacked KV cache))."""
        if self.layer_loop == "scan":
            return jax.lax.scan(body, x, stacked)
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        outs = []
        for i in range(n):
            p = jax.tree_util.tree_map(lambda a: a[i], stacked)
            x, o = body(x, p)
            outs.append(o)
        if outs and outs[0] is not None:
            outs = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *outs)
        else:
            outs = None
        return x, outs

    # -- init ------------------------------------------------------------
    def init(self, key) -> PyTree:
        cfg = self.cfg
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        k_embed, k_layers, k_head, k_extra = jax.random.split(key, 4)
        params: Dict[str, PyTree] = {
            "embed": embed_init(k_embed, (cfg.vocab, cfg.d_model), dtype),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(
                k_head, (cfg.d_model, cfg.vocab), dtype=dtype)
        L = cfg.n_layers
        if cfg.family == "hybrid" and cfg.attn_every:
            params["mamba"] = _stack(
                k_layers, L, lambda k: dict(
                    init_mamba(k, cfg, dtype),
                    ln=jnp.ones((cfg.d_model,), jnp.float32)))
            params["shared"] = _init_dense_layer(k_extra, cfg, dtype)
        elif cfg.xlstm is not None:
            k1, k2 = jax.random.split(k_layers)
            params["slstm"] = _stack(
                k1, L // 2, lambda k: dict(
                    init_slstm(k, cfg, dtype),
                    ln=jnp.ones((cfg.d_model,), jnp.float32)))
            params["mlstm"] = _stack(
                k2, L // 2, lambda k: dict(
                    init_mlstm(k, cfg, dtype),
                    ln=jnp.ones((cfg.d_model,), jnp.float32)))
        elif cfg.family == "ssm":
            params["mamba"] = _stack(
                k_layers, L, lambda k: dict(
                    init_mamba(k, cfg, dtype),
                    ln=jnp.ones((cfg.d_model,), jnp.float32)))
        else:
            params["layers"] = _stack(
                k_layers, L, lambda k: _init_dense_layer(k, cfg, dtype))
        return params

    # -- embedding -------------------------------------------------------
    def _embed(self, params, tokens=None, embeds=None):
        if embeds is not None:
            x = embeds.astype(params["embed"].dtype)
        else:
            x = params["embed"][tokens]
        return shard(x, self.plan, "x",
                     ("batch", "seq", "d_model")[:x.ndim - 1] + ("d_model",))

    def _head(self, params, x):
        w = (params["embed"].T if self.cfg.tie_embeddings
             else params["lm_head"])
        with jax.named_scope("lm_head"):
            logits = x @ w
        dims = ("batch", "seq", "vocab") if x.ndim == 3 else ("batch", "vocab")
        return shard(logits, self.plan, "logits", dims)

    # -- forward (train / prefill) ----------------------------------------
    def forward(self, params, tokens=None, embeds=None) -> Tuple[jnp.ndarray,
                                                                 jnp.ndarray]:
        """-> (logits [B,S,V], aux_loss scalar)."""
        cfg = self.cfg
        x = self._embed(params, tokens, embeds)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        aux_total = jnp.zeros((), jnp.float32)

        if cfg.family == "hybrid" and cfg.attn_every:
            period = cfg.attn_every

            def mamba_body(x, p):
                xn = shard(rms_norm(x, p["ln"], cfg.norm_eps), self.plan,
                           "x", ("batch", "seq", "d_model"))
                y = mamba_forward(p, xn, cfg, self.plan,
                                  impl=self.ssd_impl, mesh=self.mesh)
                return shard(x + y, self.plan, "x",
                             ("batch", "seq", "d_model"))

            mb = jax.tree_util.tree_map(
                lambda a: a.reshape((cfg.n_layers // period, period)
                                    + a.shape[1:]), params["mamba"])

            def outer(x, pgrp):
                def inner(xc, p):
                    return jax.checkpoint(mamba_body)(xc, p), None
                x, _ = jax.lax.scan(inner, x, pgrp)
                x, aux = jax.checkpoint(
                    lambda xx: _dense_layer_forward(
                        params["shared"], xx, cfg, positions, self.plan,
                        self.attn_impl, self.mesh))(x)
                return x, aux

            x, auxs = self._fold(outer, x, mb)
            aux_total += jnp.sum(auxs)
        elif cfg.xlstm is not None:
            def pair_body(x, ps):
                ps_s, ps_m = ps
                x = x + slstm_forward(ps_s, rms_norm(x, ps_s["ln"],
                                                     cfg.norm_eps), cfg)
                x = x + mlstm_forward(ps_m, rms_norm(x, ps_m["ln"],
                                                     cfg.norm_eps), cfg)
                return shard(x, self.plan, "x", ("batch", "seq", "d_model"))

            def scan_fn(x, ps):
                return jax.checkpoint(pair_body)(x, ps), None

            x, _ = self._fold(scan_fn, x,
                              (params["slstm"], params["mlstm"]))
        elif cfg.family == "ssm":
            def body(x, p):
                xn = shard(rms_norm(x, p["ln"], cfg.norm_eps), self.plan,
                           "x", ("batch", "seq", "d_model"))
                y = mamba_forward(p, xn, cfg, self.plan,
                                  impl=self.ssd_impl, mesh=self.mesh)
                return shard(x + y, self.plan, "x",
                             ("batch", "seq", "d_model"))

            def scan_fn(x, p):
                return jax.checkpoint(body)(x, p), None

            x, _ = self._fold(scan_fn, x, params["mamba"])
        else:
            def body(x, p):
                return _dense_layer_forward(p, x, cfg, positions, self.plan,
                                            self.attn_impl, self.mesh)

            def scan_fn(x, p):
                x, aux = jax.checkpoint(body)(x, p)
                return x, aux

            x, auxs = self._fold(scan_fn, x, params["layers"])
            aux_total += jnp.sum(auxs)

        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return self._head(params, x), aux_total

    def loss(self, params, batch) -> jnp.ndarray:
        logits, aux = self.forward(params, batch.get("tokens"),
                                   batch.get("embeds"))
        ce = softmax_cross_entropy(logits, batch["labels"], self.cfg.vocab)
        return ce + 0.01 * aux

    # -- decode ------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> PyTree:
        cfg = self.cfg
        hd, kv = cfg.hd, cfg.n_kv_heads
        L = cfg.n_layers

        def kvc(n, length):
            return {
                "k": jnp.zeros((n, batch, length, kv, hd), jnp.bfloat16),
                "v": jnp.zeros((n, batch, length, kv, hd), jnp.bfloat16),
            }

        cache: Dict[str, PyTree] = {
            "pos": jnp.zeros((batch,), jnp.int32)}
        if cfg.family == "hybrid" and cfg.attn_every:
            n_shared = cfg.n_layers // cfg.attn_every
            win = min(max_len, (cfg.swa_window or 4096)
                      if max_len > 65536 else max_len)
            cache["mamba"] = jax.tree_util.tree_map(
                lambda a: jnp.stack([a] * L),
                init_mamba_state(cfg, batch))
            cache["shared"] = kvc(n_shared, win)
        elif cfg.xlstm is not None:
            cache["slstm"] = jax.tree_util.tree_map(
                lambda a: jnp.stack([a] * (L // 2)),
                init_slstm_state(cfg, batch))
            cache["mlstm"] = jax.tree_util.tree_map(
                lambda a: jnp.stack([a] * (L // 2)),
                init_mlstm_state(cfg, batch))
        elif cfg.family == "ssm":
            cache["mamba"] = jax.tree_util.tree_map(
                lambda a: jnp.stack([a] * L),
                init_mamba_state(cfg, batch))
        else:
            cache["kv"] = kvc(L, min(max_len,
                                     cfg.swa_window or max_len)
                              if cfg.swa_window else max_len)
        return cache

    def init_cache_paged(self, batch: int, max_len: int, n_blocks: int,
                         block_len: int) -> PyTree:
        """Paged serving cache: one block *pool* per layer — no per-slot
        max_len reservation — plus a per-slot block table mapping logical
        block index -> pool block id.  Block 0 is the host allocator's
        reserved null sink (zeroed table rows point at it).  Dense
        full-attention families only (``paged_ok``)."""
        cfg = self.cfg
        if not paged_ok(cfg):
            raise ValueError(
                f"paged KV cache unsupported for {cfg.name} (recurrent "
                "state or ring-buffer SWA cache)")
        if max_len % block_len:
            raise ValueError(
                f"block_len={block_len} must divide max_len={max_len} "
                "(keeps the gathered per-slot view the same length as "
                "the linear cache — the bit-equality invariant)")
        hd, kv, L = cfg.hd, cfg.n_kv_heads, cfg.n_layers
        mb = max_len // block_len
        return {
            "pos": jnp.zeros((batch,), jnp.int32),
            "block_table": jnp.zeros((batch, mb), jnp.int32),
            "pages": {
                "k": jnp.zeros((L, n_blocks, block_len, kv, hd),
                               jnp.bfloat16),
                "v": jnp.zeros((L, n_blocks, block_len, kv, hd),
                               jnp.bfloat16),
            },
        }

    def _attn_decode(self, p, x, kv, l, pos, cfg, win, active=None):
        """x: [B, D]; kv: {"k","v"} the stacked [L, B, S, KV, hd] cache,
        carried through the layer scan; ``l``: this layer's index.  The
        token's K/V lands with one scatter at [l, row, slot] of the
        carried buffer (in place inside the loop), and attention reads
        layer ``l`` where it lies — no per-layer slice, restack or copy
        of the cache.  ``active`` [B] bool (optional): rows marked
        inactive drop their K/V write (index pushed out of range,
        scatter mode="drop") so an idle slot's cache row cannot be
        disturbed between requests."""
        b, d = x.shape
        hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
        q = x @ p["wq"]
        k = x @ p["wk"]
        v = x @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = rope(q.reshape(b, 1, h, hd), pos[:, None],
                 cfg.rope_theta)[:, 0]
        k = rope(k.reshape(b, 1, kvh, hd), pos[:, None],
                 cfg.rope_theta)[:, 0]
        v = v.reshape(b, kvh, hd)
        S = kv["k"].shape[2]
        slot = pos % S if win else pos
        if active is not None:
            slot = jnp.where(active, slot, S)      # OOB -> dropped
        rows = jnp.arange(b)
        with jax.named_scope("kv_write"):
            kc = kv["k"].at[l, rows, slot].set(k.astype(jnp.bfloat16),
                                               mode="drop")
            vc = kv["v"].at[l, rows, slot].set(v.astype(jnp.bfloat16),
                                               mode="drop")
        length = jnp.minimum(pos + 1, S)
        with jax.named_scope("attn"):
            o = attend_cache(q, kc, vc, length, layer=l, window=None,
                             impl=self.attn_impl, mesh=self.mesh,
                             plan=self.plan)
        return (o.reshape(b, h * hd) @ p["wo"],
                {"k": kc, "v": vc})

    def _attn_decode_paged(self, p, x, pool, table, pos, cfg,
                           active=None):
        """x: [B, D]; pool: {"k","v"} [NB, BL, KV, hd] for ONE layer;
        table: [B, MB] pool block ids.  The new K/V scatters through the
        slot's block table (rows past their table or marked inactive are
        dropped), then attention runs against the table-gathered view —
        masked positions beyond ``pos`` hold garbage from other requests'
        retired blocks, but the NEG_INF mask underflows their softmax
        weight to exactly 0.0, so the result is bit-equal to the linear
        cache (see attend_cache / DESIGN.md §15)."""
        b, d = x.shape
        hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
        bl = pool["k"].shape[1]
        mb = table.shape[1]
        q = x @ p["wq"]
        k = x @ p["wk"]
        v = x @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = rope(q.reshape(b, 1, h, hd), pos[:, None],
                 cfg.rope_theta)[:, 0]
        k = rope(k.reshape(b, 1, kvh, hd), pos[:, None],
                 cfg.rope_theta)[:, 0]
        v = v.reshape(b, kvh, hd)
        nb = pool["k"].shape[0]
        bidx = pos // bl
        blk = jnp.take_along_axis(
            table, jnp.minimum(bidx, mb - 1)[:, None], axis=1)[:, 0]
        ok = bidx < mb
        if active is not None:
            ok &= active
        # positive-OOB sentinel: jnp wraps NEGATIVE indices (NumPy
        # semantics) before the mode="drop" bounds check, so -1 would
        # scatter into live block NB-1 instead of being dropped
        wblk = jnp.where(ok, blk, nb)              # OOB -> dropped
        with jax.named_scope("kv_write"):
            kc = pool["k"].at[wblk, pos % bl].set(
                k.astype(jnp.bfloat16), mode="drop")
            vc = pool["v"].at[wblk, pos % bl].set(
                v.astype(jnp.bfloat16), mode="drop")
        length = jnp.minimum(pos + 1, mb * bl)
        with jax.named_scope("attn"):
            o = attend_paged(q, kc, vc, table, length,
                             impl=self.attn_impl, mesh=self.mesh,
                             plan=self.plan)
        return (o.reshape(b, h * hd) @ p["wo"],
                {"k": kc, "v": vc})

    def decode_step(self, params, cache, tokens,
                    active=None) -> Tuple[jnp.ndarray, PyTree]:
        """tokens: [B] int32 (or [B, D] embeds for stub frontends).
        Returns (logits [B, V], new cache).

        ``active`` [B] bool (optional): inactive rows freeze — their
        cache position does not advance and their attention K/V write is
        dropped, so a long-idle free slot cannot drift past max_len
        between requests (the pool always dispatches full-width).
        Recurrent per-row state still churns for inactive rows; it is
        zeroed by reset_slot at the next admission."""
        cfg = self.cfg
        pos = cache["pos"]
        if tokens.ndim == 2:
            x = tokens.astype(params["embed"].dtype)
        else:
            x = params["embed"][tokens]
        x = shard(x, self.plan, "x", ("batch", "d_model"))
        new_cache = dict(cache)

        if cfg.family == "hybrid" and cfg.attn_every:
            period = cfg.attn_every
            n_shared = cfg.n_layers // cfg.attn_every
            mamba_groups = jax.tree_util.tree_map(
                lambda a: a.reshape((n_shared, period) + a.shape[1:]),
                params["mamba"])
            mstate = jax.tree_util.tree_map(
                lambda a: a.reshape((n_shared, period) + a.shape[1:]),
                cache["mamba"])

            def outer(carry, inp):
                x, kv = carry
                pgrp, sgrp, l = inp

                def inner(xc, pin):
                    p, st = pin
                    y, st2 = mamba_step(p, rms_norm(xc, p["ln"],
                                                    cfg.norm_eps),
                                        st, cfg, self.plan)
                    return xc + y, st2

                x, st_new = jax.lax.scan(inner, x, (pgrp, sgrp))
                ps = params["shared"]
                h, kv = self._attn_decode(
                    ps["attn"], rms_norm(x, ps["ln1"], cfg.norm_eps),
                    kv, l, pos, cfg, win=True, active=active)
                x = x + h
                x = x + _mlp_forward(ps["mlp"],
                                     rms_norm(x, ps["ln2"], cfg.norm_eps))
                return (x, kv), st_new

            (x, kv_new), mstate_new = self._fold(
                outer, (x, cache["shared"]),
                (mamba_groups, mstate, jnp.arange(n_shared)))
            new_cache["mamba"] = jax.tree_util.tree_map(
                lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]),
                mstate_new)
            new_cache["shared"] = kv_new
        elif cfg.xlstm is not None:
            def pair(x, inp):
                ps_s, ps_m, st_s, st_m = inp
                y, st_s2 = slstm_step(ps_s, rms_norm(x, ps_s["ln"],
                                                     cfg.norm_eps),
                                      st_s, cfg)
                x = x + y
                y, st_m2 = mlstm_step(ps_m, rms_norm(x, ps_m["ln"],
                                                     cfg.norm_eps),
                                      st_m, cfg)
                return x + y, (st_s2, st_m2)

            x, (st_s, st_m) = self._fold(
                pair, x, (params["slstm"], params["mlstm"],
                          cache["slstm"], cache["mlstm"]))
            new_cache["slstm"], new_cache["mlstm"] = st_s, st_m
        elif cfg.family == "ssm":
            def body(x, inp):
                p, st = inp
                y, st2 = mamba_step(p, rms_norm(x, p["ln"], cfg.norm_eps),
                                    st, cfg, self.plan)
                return x + y, st2

            x, st_new = self._fold(body, x,
                                   (params["mamba"], cache["mamba"]))
            new_cache["mamba"] = st_new
        elif "pages" in cache:
            table = cache["block_table"]

            def body(x, inp):
                p, pool = inp
                h, pool_new = self._attn_decode_paged(
                    p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                    pool, table, pos, cfg, active=active)
                x = x + h
                xn = rms_norm(x, p["ln2"], cfg.norm_eps)
                if cfg.moe is not None:
                    y, _ = moe_ffn(p["moe"], xn[:, None, :], cfg, self.plan)
                    y = y[:, 0]
                else:
                    y = _mlp_forward(p["mlp"], xn)
                return x + y, pool_new

            x, pool_new = self._fold(body, x,
                                     (params["layers"], cache["pages"]))
            new_cache["pages"] = pool_new
        else:
            # the cache rides the carry, not xs/ys: scanning it would
            # slice every layer out, restack the outputs and copy the
            # result — three passes over the whole cache per step
            def body(carry, inp):
                x, kv = carry
                p, l = inp
                h, kv = self._attn_decode(
                    p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                    kv, l, pos, cfg, win=cfg.swa_window is not None,
                    active=active)
                x = x + h
                xn = rms_norm(x, p["ln2"], cfg.norm_eps)
                if cfg.moe is not None:
                    y, _ = moe_ffn(p["moe"], xn[:, None, :], cfg, self.plan)
                    y = y[:, 0]
                else:
                    y = _mlp_forward(p["mlp"], xn)
                return (x + y, kv), None

            (x, new_cache["kv"]), _ = self._fold(
                body, (x, cache["kv"]),
                (params["layers"], jnp.arange(cfg.n_layers)))

        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        if active is None:
            new_cache["pos"] = pos + 1
        else:
            new_cache["pos"] = pos + active.astype(pos.dtype)
        return self._head(params, x), new_cache

    # -- serving: per-slot reset + chunked prefill -------------------------
    def reset_slot(self, cache, slot) -> PyTree:
        """Zero one slot's cache row (KV / recurrent state / pos).
        Admission into a freed slot must never see the previous
        request's state (stale-cache leakage).  For a paged cache only
        the slot's pos and block-table row are cleared — the pool blocks
        themselves are recycled by the host allocator, and a zeroed
        table row points at the reserved null block."""
        if "pages" in cache:
            new = dict(cache)
            new["pos"] = cache["pos"].at[slot].set(0)
            new["block_table"] = cache["block_table"].at[slot].set(0)
            return new
        sub = jax.tree_util.tree_map(jnp.zeros_like,
                                     slot_slice(cache, slot))
        return slot_merge(cache, sub, slot)

    def prefill_chunk(self, params, cache, tokens, slot, n_valid,
                      impl: str = "auto") -> Tuple[jnp.ndarray, PyTree]:
        """Chunked prefill for ONE slot: consume ``tokens`` [C] int32
        (first ``n_valid`` real, rest padding) starting at the slot's
        current cache position.  Returns (f32 logits [V] for the last
        valid token, new pool cache).

        Full-attention families with a linear KV cache run the whole
        chunk in parallel (flash attention against the cache with a
        causal position offset); recurrent families (ssm / xlstm /
        hybrid) and ring-buffer SWA caches scan ``decode_step`` over the
        chunk.  Either way one chunk is ONE device dispatch touching ONE
        slot — the seed admit loop paid a pool-wide dispatch per prompt
        token.

        ``impl``: "auto" picks per family; "scan" forces the sequential
        path (bit-identical to the decode_step loop — the parallel path
        re-associates the softmax under bf16); "parallel" forces the
        offset-attention path (full-attention linear caches only)."""
        cfg = self.cfg
        if "pages" in cache:
            # paged pool: no slot_slice (the pool has no batch axis) —
            # writes route through the slot's block-table row instead
            if impl == "scan":
                return self._prefill_chunk_paged_scan(
                    params, cache, tokens, slot, n_valid)
            return self._prefill_chunk_attn_paged(params, cache, tokens,
                                                  slot, n_valid)
        sub = slot_slice(cache, slot)
        parallel_ok = prefill_parallel_ok(cfg)
        if impl == "parallel" and not parallel_ok:
            raise ValueError(
                f"parallel prefill unsupported for {cfg.name} "
                "(recurrent state or ring-buffer SWA cache)")
        if parallel_ok and impl != "scan":
            logits, sub = self._prefill_chunk_attn(params, sub, tokens,
                                                   n_valid)
        else:
            logits, sub = self._prefill_chunk_scan(params, sub, tokens,
                                                   n_valid)
        return logits, slot_merge(cache, sub, slot)

    def _prefill_chunk_scan(self, params, sub, tokens, n_valid):
        """Fallback chunk prefill: scan the single-token decode step over
        the chunk (batch-1 cache), masking the padded tail."""
        c = tokens.shape[0]

        def body(carry, inp):
            sub, lg = carry
            tok, i = inp
            lg2, sub2 = self.decode_step(params, sub, tok[None])
            keep = i < n_valid
            sub = jax.tree_util.tree_map(
                lambda a, b: jnp.where(keep, a, b), sub2, sub)
            lg = jnp.where(i == n_valid - 1,
                           lg2[0].astype(jnp.float32), lg)
            return (sub, lg), None

        lg0 = jnp.zeros((self.cfg.vocab,), jnp.float32)
        (sub, logits), _ = jax.lax.scan(body, (sub, lg0),
                                        (tokens, jnp.arange(c)))
        return logits, sub

    def _attn_prefill(self, p, x, kv_cache, positions, cfg):
        """x: [1, C, D]; kv_cache: {"k","v"} [1, S, KV, hd] (one layer).
        Writes the chunk's K/V at absolute ``positions`` and attends the
        chunk's queries against the whole cache with a causal offset.
        Padded rows write past the valid region (dropped when out of
        range; otherwise overwritten by later decode writes at the same
        index, and never attended thanks to the length mask)."""
        b, c, d = x.shape
        hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
        q = x @ p["wq"]
        k = x @ p["wk"]
        v = x @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = rope(q.reshape(b, c, h, hd), positions, cfg.rope_theta)
        k = rope(k.reshape(b, c, kvh, hd), positions, cfg.rope_theta)
        v = v.reshape(b, c, kvh, hd)
        idx = positions[0]
        with jax.named_scope("kv_write"):
            kc = kv_cache["k"].at[:, idx].set(k.astype(jnp.bfloat16),
                                              mode="drop")
            vc = kv_cache["v"].at[:, idx].set(v.astype(jnp.bfloat16),
                                              mode="drop")
        # Pallas offset kernel only unsharded: the prefill jit is GSPMD-
        # partitioned when a mesh is present, and pallas_call has no
        # partitioning rule there (decode goes through shard_map instead).
        impl = self.attn_impl if self.mesh is None else "xla"
        with jax.named_scope("attn"):
            o = attention(q, kc, vc, causal=True,
                          q_offset=positions[0, 0], impl=impl)
        return o.reshape(b, c, h * hd) @ p["wo"], {"k": kc, "v": vc}

    def _prefill_chunk_attn(self, params, sub, tokens, n_valid):
        """Parallel chunk prefill for the full-attention families (the
        decode_step dense branch, seq-form, with offset attention)."""
        cfg = self.cfg
        pos0 = sub["pos"][0]
        x = params["embed"][tokens][None]          # [1, C, D]
        x = shard(x, self.plan, "x", ("batch", "seq", "d_model"))
        c = tokens.shape[0]
        positions = (pos0 + jnp.arange(c))[None, :]

        def body(x, inp):
            p, kvi = inp
            h, kv_new = self._attn_prefill(
                p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), kvi,
                positions, cfg)
            x = x + h
            xn = rms_norm(x, p["ln2"], cfg.norm_eps)
            if cfg.moe is not None:
                y, _ = moe_ffn(p["moe"], xn, cfg, self.plan)
            else:
                y = _mlp_forward(p["mlp"], xn)
            return x + y, kv_new

        x, kv_new = self._fold(body, x, (params["layers"], sub["kv"]))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = self._head(params, x)[0]          # [C, V]
        last = jax.lax.dynamic_index_in_dim(logits, n_valid - 1, 0,
                                            keepdims=False)
        new_sub = dict(sub)
        new_sub["kv"] = kv_new
        new_sub["pos"] = sub["pos"] + n_valid
        return last.astype(jnp.float32), new_sub

    # -- paged serving: block-pool prefill / rescore -----------------------
    def _attn_prefill_paged(self, p, x, pool, row_table, positions,
                            n_valid, cfg):
        """x: [1, C, D]; pool: {"k","v"} [NB, BL, KV, hd] (one layer);
        row_table: [MB] the slot's block-table row.  The chunk's K/V
        scatters through the table at absolute ``positions`` (padded
        rows masked out — unlike the linear path they would land in real
        pool blocks), then offset flash attention runs against the
        table-gathered per-slot view."""
        b, c, d = x.shape
        hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
        bl = pool["k"].shape[1]
        mb = row_table.shape[0]
        q = x @ p["wq"]
        k = x @ p["wk"]
        v = x @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = rope(q.reshape(b, c, h, hd), positions, cfg.rope_theta)
        k = rope(k.reshape(b, c, kvh, hd), positions, cfg.rope_theta)
        v = v.reshape(b, c, kvh, hd)
        nb = pool["k"].shape[0]
        abs_pos = positions[0]                     # [C]
        bidx = abs_pos // bl
        blk = row_table[jnp.minimum(bidx, mb - 1)]
        # positive-OOB sentinel, not -1: negative indices wrap before
        # the mode="drop" bounds check and would hit live block NB-1
        wblk = jnp.where((jnp.arange(c) < n_valid) & (bidx < mb),
                         blk, nb)                  # OOB -> dropped
        with jax.named_scope("kv_write"):
            kc = pool["k"].at[wblk, abs_pos % bl].set(
                k[0].astype(jnp.bfloat16), mode="drop")
            vc = pool["v"].at[wblk, abs_pos % bl].set(
                v[0].astype(jnp.bfloat16), mode="drop")
        # same GSPMD caveat as the linear path: no pallas partitioning
        # rule under a mesh
        impl = self.attn_impl if self.mesh is None else "xla"
        with jax.named_scope("attn"):
            kview = kc[row_table].reshape(1, mb * bl, kvh, hd)
            vview = vc[row_table].reshape(1, mb * bl, kvh, hd)
            o = attention(q, kview, vview, causal=True,
                          q_offset=positions[0, 0], impl=impl)
        return o.reshape(b, c, h * hd) @ p["wo"], {"k": kc, "v": vc}

    def _prefill_chunk_attn_paged(self, params, cache, tokens, slot,
                                  n_valid):
        """Parallel chunk prefill through the paged pool (whole cache in,
        whole cache out — only ``slot``'s table row and pos change)."""
        cfg = self.cfg
        table = cache["block_table"]
        pos0 = cache["pos"][slot]
        c = tokens.shape[0]
        x = params["embed"][tokens][None]          # [1, C, D]
        x = shard(x, self.plan, "x", ("batch", "seq", "d_model"))
        positions = (pos0 + jnp.arange(c))[None, :]
        row_table = jax.lax.dynamic_index_in_dim(table, slot, 0,
                                                 keepdims=False)

        def body(x, inp):
            p, pool = inp
            h, pool_new = self._attn_prefill_paged(
                p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), pool,
                row_table, positions, n_valid, cfg)
            x = x + h
            xn = rms_norm(x, p["ln2"], cfg.norm_eps)
            if cfg.moe is not None:
                y, _ = moe_ffn(p["moe"], xn, cfg, self.plan)
            else:
                y = _mlp_forward(p["mlp"], xn)
            return x + y, pool_new

        x, pool_new = self._fold(body, x, (params["layers"],
                                           cache["pages"]))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = self._head(params, x)[0]          # [C, V]
        last = jax.lax.dynamic_index_in_dim(logits, n_valid - 1, 0,
                                            keepdims=False)
        new_cache = dict(cache)
        new_cache["pages"] = pool_new
        new_cache["pos"] = cache["pos"].at[slot].add(n_valid)
        return last.astype(jnp.float32), new_cache

    def _prefill_chunk_paged_scan(self, params, cache, tokens, slot,
                                  n_valid):
        """Sequential reference prefill for the paged pool: scan the
        pool-wide decode step with a one-hot active mask (only ``slot``
        advances; every other row is frozen by the mask) — bit-identical
        to feeding the prompt through decode_step token by token."""
        cfg = self.cfg
        b = cache["pos"].shape[0]
        onehot = jnp.arange(b) == slot

        def body(carry, inp):
            cache, lg = carry
            tok, i = inp
            feed = jnp.where(onehot, tok, 0).astype(jnp.int32)
            act = onehot & (i < n_valid)
            lg2, cache2 = self.decode_step(params, cache, feed,
                                           active=act)
            row = jax.lax.dynamic_index_in_dim(lg2, slot, 0,
                                               keepdims=False)
            lg = jnp.where(i == n_valid - 1, row.astype(jnp.float32), lg)
            return (cache2, lg), None

        lg0 = jnp.zeros((cfg.vocab,), jnp.float32)
        (cache, logits), _ = jax.lax.scan(
            body, (cache, lg0), (tokens, jnp.arange(tokens.shape[0])))
        return logits, cache

    def decode_rescore(self, params, cache, tokens, rows, positions):
        """Read-only batched re-score for speculative verification:
        logits for feeding ``tokens`` [N] at cache ``positions`` [N] of
        pool rows ``rows`` [N].  The cache (linear or paged, dense
        families only) already holds the drafted K/V — including each
        token's own position, written by the draft pass — so no cache
        write happens here and the attended state per (row, position)
        matches what the sequential decode step saw."""
        cfg = self.cfg
        paged = "pages" in cache
        table = cache.get("block_table")
        n = tokens.shape[0]
        hd, h, kvh = cfg.hd, cfg.n_heads, cfg.n_kv_heads
        x = params["embed"][tokens]                # [N, D]

        def attn(p, xn, kvi):
            q = xn @ p["wq"]
            if cfg.qkv_bias:
                q = q + p["bq"]
            q = rope(q.reshape(n, 1, h, hd), positions[:, None],
                     cfg.rope_theta)[:, 0]
            if paged:
                mb = table.shape[1]
                bl = kvi["k"].shape[1]
                kc = kvi["k"][table[rows]].reshape(n, mb * bl, kvh, hd)
                vc = kvi["v"][table[rows]].reshape(n, mb * bl, kvh, hd)
            else:
                kc = kvi["k"][rows]
                vc = kvi["v"][rows]
            o = attend_cache(q, kc, vc, positions + 1, window=None,
                             impl="xla")
            return o.reshape(n, h * hd) @ p["wo"]

        def body(x, inp):
            p, kvi = inp
            x = x + attn(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                         kvi)
            xn = rms_norm(x, p["ln2"], cfg.norm_eps)
            if cfg.moe is not None:
                y, _ = moe_ffn(p["moe"], xn[:, None, :], cfg, self.plan)
                y = y[:, 0]
            else:
                y = _mlp_forward(p["mlp"], xn)
            return x + y, None

        x, _ = self._fold(body, x, (params["layers"],
                                    cache["pages"] if paged
                                    else cache["kv"]))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        return self._head(params, x)
