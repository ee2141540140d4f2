"""GQA attention: XLA chunked (flash-style online-softmax) path used for
training/prefill and the CPU dry-run; the Pallas TPU kernel in
repro.kernels is selected with impl="pallas" (validated in interpret mode
— Pallas-TPU cannot compile on the CPU backend, see DESIGN.md)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import causal_mask

NEG_INF = -1e30

# dry-run probe mode: a single KV chunk removes the kv lax.scan so XLA
# cost_analysis counts attention flops exactly (see analysis/roofline)
DEFAULT_K_CHUNK = 1024
DEFAULT_UNROLL = False


def _gqa_expand(q, kv_heads):
    """view q [B,S,H,hd] as [B,S,KV,G,hd] (G = H // KV)."""
    b, s, h, hd = q.shape
    g = h // kv_heads
    return q.reshape(b, s, kv_heads, g, hd)


def flash_attention_xla(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        q_offset: int = 0, k_chunk: Optional[int] = None,
                        scale: Optional[float] = None):
    """Online-softmax attention, scanning KV chunks (O(S·kc) memory).

    q: [B, Sq, H, hd]; k, v: [B, Sk, KV, hd] with H % KV == 0.
    Returns [B, Sq, H, hd].
    """
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    qf = _gqa_expand(q, kv).astype(jnp.float32) * scale

    k_chunk = min(k_chunk or DEFAULT_K_CHUNK, sk)
    n_chunks = (sk + k_chunk - 1) // k_chunk
    pad = n_chunks * k_chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, k_chunk, kv, hd)
    vc = v.reshape(b, n_chunks, k_chunk, kv, hd)

    def step(carry, inp):
        m, l, acc = carry
        ki, vi, idx = inp
        # scores: [B, Sq, KV, G, kc]
        s = jnp.einsum("bsKgd,bcKd->bsKgc", qf, ki.astype(jnp.float32))
        k_off = idx * k_chunk
        mask = causal_mask(sq, k_chunk, q_offset, k_off,
                           window)[None, :, None, None, :]
        valid = (k_off + jnp.arange(k_chunk) < sk)[None, None, None, None, :]
        if causal:
            s = jnp.where(mask & valid, s, NEG_INF)
        else:
            s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bsKgc,bcKd->bsKgd", p, vi.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kv, g), jnp.float32)
    a0 = jnp.zeros((b, sq, kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(n_chunks)),
        unroll=n_chunks if DEFAULT_UNROLL else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _spec_entries(pspec, n):
    """Normalize a PartitionSpec to exactly n entries (None-padded)."""
    e = tuple(pspec)
    return e + (None,) * (n - len(e))


def _axes_degree(mesh, entry) -> int:
    names = entry if isinstance(entry, tuple) else (entry,)
    d = 1
    for nm in names:
        d *= int(dict(mesh.shape)[nm])
    return d


def attend_cache_pallas(q, k_cache, v_cache, length, layer, *,
                        window: Optional[int] = None,
                        scale: Optional[float] = None,
                        mesh=None, plan=None):
    """Pallas decode kernel path over layer ``layer`` of the stacked
    caches [L, B, S, KV, hd].  With a mesh + plan the kernel runs under
    shard_map with the plan's solved kv_cache sharding (batch and
    kv_heads dims; the layer axis and the index stay whole); a seq_kv
    cut — which would split the softmax — or a non-dividing degree
    falls back to the XLA path rather than computing a partial
    reduction."""
    from ..kernels import ops as kops

    if mesh is None or plan is None:
        return kops.flash_attention_decode(q, k_cache, v_cache, length,
                                           layer, window=window,
                                           scale=scale)

    from functools import partial

    from jax.sharding import PartitionSpec as P

    b, h, hd = q.shape
    kv = k_cache.shape[3]
    cspec = _spec_entries(
        plan.pspec("kv_cache", ("batch", "seq_kv", "kv_heads", "hd")), 4)
    bs, ss, hs, ds = cspec
    ok = (ss is None and ds is None
          and (bs is None or b % _axes_degree(mesh, bs) == 0
               and length.shape[0] % _axes_degree(mesh, bs) == 0)
          and (hs is None or kv % _axes_degree(mesh, hs) == 0
               and h % _axes_degree(mesh, hs) == 0))
    if not ok:
        return attend_cache(q, k_cache, v_cache, length, layer=layer,
                            window=window, scale=scale)
    fn = jax.shard_map(
        partial(kops.flash_attention_decode, window=window, scale=scale),
        mesh=mesh,
        in_specs=(P(bs, hs, None), P(None, bs, None, hs, None),
                  P(None, bs, None, hs, None), P(bs), P()),
        out_specs=P(bs, hs, None),
        check_vma=False)
    return fn(q, k_cache, v_cache, length, layer)


def attend_cache(q, k_cache, v_cache, length, *, layer=None,
                 window: Optional[int] = None,
                 scale: Optional[float] = None,
                 impl: str = "xla", mesh=None, plan=None):
    """Decode attention: q [B, H, hd] against caches [B, S, KV, hd], or
    against layer ``layer`` of stacked caches [L, B, S, KV, hd] (the
    decode step's, read in place); ``length`` [B] = number of valid
    cache entries (new token already written at position length-1).
    impl="pallas" routes a stacked cache through the fused decode
    kernel (shard_map-wrapped when mesh/plan are given)."""
    if impl == "pallas":
        return attend_cache_pallas(q, k_cache, v_cache, length, layer,
                                   window=window, scale=scale,
                                   mesh=mesh, plan=plan)
    if layer is not None:
        k_cache, v_cache = k_cache[layer], v_cache[layer]
    b, h, hd = q.shape
    _, s, kv, _ = k_cache.shape
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    qf = (q.reshape(b, kv, g, hd)).astype(jnp.float32) * scale
    sc = jnp.einsum("bKgd,bcKd->bKgc", qf, k_cache.astype(jnp.float32))
    pos = jnp.arange(s)[None, :]
    valid = pos < length[:, None]
    if window is not None:
        valid &= pos >= (length[:, None] - window)
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bKgc,bcKd->bKgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


def attend_paged_pallas(q, k_pool, v_pool, table, length, *,
                        scale: Optional[float] = None,
                        mesh=None, plan=None):
    """Pallas paged-decode kernel path: the kernel gathers KV blocks
    through the scalar-prefetched block table (no materialized per-slot
    view).  With a mesh + plan the kernel runs under shard_map with the
    plan's block_table batch cut (pool replicated per data shard) and
    the kv_cache kv_heads cut; any cut the kernel cannot honor (blocks /
    block_len / hd on the pool, blocks on the table, non-dividing
    degrees) falls back to the XLA gather path."""
    from ..kernels import ops as kops

    if mesh is None or plan is None:
        return kops.flash_attention_paged_decode(q, k_pool, v_pool,
                                                 table, length,
                                                 scale=scale)

    from functools import partial

    from jax.sharding import PartitionSpec as P

    b, h, hd = q.shape
    kv = k_pool.shape[2]
    nbs, bls, hs, ds = _spec_entries(
        plan.pspec("kv_cache", ("blocks", "block_len", "kv_heads", "hd")),
        4)
    bs, tbs = _spec_entries(
        plan.pspec("block_table", ("batch", "blocks")), 2)
    ok = (nbs is None and bls is None and ds is None and tbs is None
          and (bs is None or b % _axes_degree(mesh, bs) == 0
               and length.shape[0] % _axes_degree(mesh, bs) == 0)
          and (hs is None or kv % _axes_degree(mesh, hs) == 0
               and h % _axes_degree(mesh, hs) == 0))
    if not ok:
        return attend_paged(q, k_pool, v_pool, table, length, scale=scale)
    fn = jax.shard_map(
        partial(kops.flash_attention_paged_decode, scale=scale),
        mesh=mesh,
        in_specs=(P(bs, hs, None), P(None, None, hs, None),
                  P(None, None, hs, None), P(bs, None), P(bs)),
        out_specs=P(bs, hs, None),
        check_vma=False)
    return fn(q, k_pool, v_pool, table, length)


def attend_paged(q, k_pool, v_pool, table, length, *,
                 scale: Optional[float] = None,
                 impl: str = "xla", mesh=None, plan=None):
    """Paged decode attention: q [B, H, hd] against block pools
    [NB, BL, KV, hd] through a per-slot block ``table`` [B, MB];
    ``length`` [B] = valid cache entries.  The XLA path materializes the
    per-slot view by gathering table rows (positions >= length mask to
    NEG_INF and underflow to exactly 0 after softmax, so garbage in
    unowned/stale blocks cannot leak — bit-equal to a linear cache of
    the same MB*BL length).  impl="pallas" gathers inside the kernel
    via scalar-prefetched block indices instead."""
    if impl == "pallas":
        return attend_paged_pallas(q, k_pool, v_pool, table, length,
                                   scale=scale, mesh=mesh, plan=plan)
    b, mb = table.shape
    nb, bl, kv, hd = k_pool.shape
    kc = k_pool[table].reshape(b, mb * bl, kv, hd)
    vc = v_pool[table].reshape(b, mb * bl, kv, hd)
    return attend_cache(q, kc, vc, length, window=None, scale=scale)


def attention(q, k, v, *, impl: str = "xla", **kw):
    if impl == "pallas":
        from ..kernels import ops as kops
        # The fused kernel scans all of k; the XLA path's k_chunk is a
        # scan-tiling knob with no kernel equivalent — drop it.
        kw.pop("k_chunk", None)
        q_offset = kw.pop("q_offset", 0)
        unknown = set(kw) - {"causal", "window", "scale"}
        if unknown:
            raise TypeError(
                f"attention(impl='pallas') got unsupported kwargs "
                f"{sorted(unknown)}")
        causal = kw.get("causal", True)
        window = kw.get("window")
        scale = kw.get("scale")
        static_zero = isinstance(q_offset, int) and q_offset == 0
        if static_zero:
            return kops.flash_attention(q, k, v, causal, window, scale)
        # traced / nonzero offset: forward-only offset kernel (chunked
        # prefill never differentiates)
        return kops.flash_attention_offset(q, k, v, q_offset,
                                           causal=causal, window=window,
                                           scale=scale)
    return flash_attention_xla(q, k, v, **kw)
