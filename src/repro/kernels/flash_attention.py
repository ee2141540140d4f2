"""Flash attention for TPU in Pallas (forward + backward kernels).

TPU adaptation (vs the CUDA flash algorithm): the grid's innermost
dimension iterates *sequentially* on a TensorCore, so the online-softmax
running state (m, l, acc) lives in VMEM scratch that persists across KV
tiles — no atomics or shared-memory staging as on GPU.  Block shapes are
(block_q × head_dim) / (block_k × head_dim) tiles sized for VMEM with the
MXU's 128-lane alignment.

Layout: q [B, Sq, H, hd] is processed per (b, h) with GQA mapping
h -> kv_head = h // (H // KV).  Forward emits the softmax logsumexp for
the backward kernels (dq and dk/dv), which recompute p tile-by-tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _row_mask(start, block, limit):
    """[block, 1] bool: which rows of a padded tile are in-bounds (a 2-D
    column, since Mosaic lowers no 1-D iota)."""
    return start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) < limit


def _clean(x, valid):
    """Zero padded rows (pallas pads OOB tiles with undefined values;
    0 * NaN = NaN would otherwise poison the accumulators)."""
    return jnp.where(valid, x, 0.0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_tile(q_ref, k_ref, v_ref, o_ref, lse_ref,
              m_scr, l_scr, acc_scr, *, q_off,
              scale, causal, window, block_q, block_k, sq, sk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kvalid = _row_mask(ki * block_k, block_k, sk)
    qvalid = _row_mask(qi * block_q, block_q, sq)
    q = _clean(q_ref[...].astype(jnp.float32), qvalid) * scale  # [bq, hd]
    k = _clean(k_ref[...].astype(jnp.float32), kvalid)          # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]

    # q_row is chunk-local (validity vs the padded tile); q_pos is the
    # absolute sequence position (causal/window), offset by q_off when the
    # query block is a prefill chunk appended at cache position q_off.
    q_row = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (k_pos < sk) & (q_row < sq)
    q_pos = q_row + q_off
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                       # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + p.sum(-1, keepdims=True)
    v = _clean(v_ref[...].astype(jnp.float32), kvalid)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, **kw):
    _fwd_tile(q_ref, k_ref, v_ref, o_ref, lse_ref,
              m_scr, l_scr, acc_scr, q_off=0, **kw)


def _fwd_kernel_off(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                    m_scr, l_scr, acc_scr, **kw):
    # scalar-prefetch variant: off_ref is an SMEM [1] int32 with the
    # (possibly traced) absolute position of query row 0.
    _fwd_tile(q_ref, k_ref, v_ref, o_ref, lse_ref,
              m_scr, l_scr, acc_scr, q_off=off_ref[0], **kw)


def _check_gqa(h: int, kv: int):
    if kv <= 0 or h % kv != 0:
        raise ValueError(
            f"GQA head mapping needs q_heads divisible by kv_heads, got "
            f"h={h} kv={kv}")


def flash_attention_fwd(q, k, v, *, causal=True, window=None,
                        scale=None, q_offset=None,
                        block_q=128, block_k=128, interpret=False):
    """Forward flash attention; ``q_offset`` (None | int | traced scalar)
    shifts the queries' absolute positions for chunked prefill, with the
    offset fed through scalar prefetch so it may be a traced value."""
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    _check_gqa(h, kv)
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    kw = dict(scale=scale, causal=causal, window=window,
              block_q=block_q, block_k=block_k, sq=sq, sk=sk)
    # lse leaves the kernel as [B, H, Sq, 1]: a squeezed head axis may not
    # sit second-to-last in a TPU block, a trailing unit axis may.
    out_shapes = (
        jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
    )
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, hd), jnp.float32),
    ]
    ins = (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
           v.transpose(0, 2, 1, 3))

    if q_offset is None:
        o, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, **kw),
            name="flash_fwd",
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((None, None, block_q, hd),
                             lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
                pl.BlockSpec((None, None, block_k, hd),
                             lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0)),
                pl.BlockSpec((None, None, block_k, hd),
                             lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0)),
            ],
            out_specs=(
                pl.BlockSpec((None, None, block_q, hd),
                             lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
                pl.BlockSpec((None, None, block_q, 1),
                             lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            ),
            scratch_shapes=scratch,
            out_shape=out_shapes,
            interpret=interpret,
        )(*ins)
        return o.transpose(0, 2, 1, 3), lse[..., 0]

    off = jnp.asarray(q_offset, jnp.int32).reshape((1,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bb, hh, qi, ki, off: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, qi, ki, off, g=g:
                         (bb, hh // g, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, qi, ki, off, g=g:
                         (bb, hh // g, ki, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bb, hh, qi, ki, off: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bb, hh, qi, ki, off: (bb, hh, qi, 0)),
        ),
        scratch_shapes=scratch,
    )
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_off, **kw),
        name="flash_fwd_offset",
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(off, *ins)
    return o.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# decode (one query token per slot against the serving engine's KV cache)
# ---------------------------------------------------------------------------
#
# The caches keep the kv-head axis second-to-last ([.., tokens, KV, hd]),
# so a TPU block cannot squeeze it to one head: each grid step takes the
# whole [block, KV, hd] tile and walks the kv heads in a static loop.

def _decode_tile(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 k_start, length, window, scale, kv_heads):
    """One KV tile of the online softmax for every kv head of one slot.
    q_ref [KV, g, hd], k_ref/v_ref [bk, KV, hd]; positions >= ``length``
    (tile padding, recycled or null blocks) never enter the softmax."""
    ki = pl.program_id(1)
    bk = k_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def live(shape, dim):
        pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, dim)
        ok = pos < length
        if window is not None:
            ok &= pos >= length - window
        return ok

    col = live((bk, 1), 0)                                 # rows of k/v
    row = live((1, bk), 1)                                 # columns of s
    for j in range(kv_heads):
        q = q_ref[j].astype(jnp.float32) * scale               # [g, hd]
        k = _clean(k_ref[:, j, :].astype(jnp.float32), col)    # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [g, bk]
        s = jnp.where(row, s, NEG_INF)
        m_prev = m_scr[j]                                      # [g, 1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[j] = l_scr[j] * corr + p.sum(-1, keepdims=True)
        v = _clean(v_ref[:, j, :].astype(jnp.float32), col)
        acc_scr[j] = acc_scr[j] * corr + jax.lax.dot(p, v)
        m_scr[j] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def _decode_kernel(len_ref, lyr_ref, q_ref, k_ref, v_ref, o_ref, *scr,
                   **kw):
    # the BlockSpec index_map already used lyr_ref to route (k_ref,
    # v_ref) at the layer's tiles of the stacked cache
    bb = pl.program_id(0)
    _decode_tile(q_ref, k_ref, v_ref, o_ref, *scr,
                 k_start=pl.program_id(1) * k_ref.shape[0],
                 length=len_ref[bb], **kw)


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         *scr, **kw):
    # the BlockSpec index_map already used tbl_ref to route this grid
    # step's (k_ref, v_ref) at the right pool block, so the body only
    # needs the slot's length
    bb = pl.program_id(0)
    _decode_tile(q_ref, k_ref, v_ref, o_ref, *scr,
                 k_start=pl.program_id(1) * k_ref.shape[0],
                 length=len_ref[bb], window=None, **kw)


def _decode_scratch(kv, g, hd):
    return [pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32)]


def flash_attention_decode(q, k_cache, v_cache, lengths, layer, *,
                           window=None, scale=None, block_k=128,
                           interpret=False):
    """One decode step of one layer: q [B, H, hd] against that layer of
    the stacked slot cache [L, B, S, KV, hd], with per-slot valid
    ``lengths`` [B] (the serving engine's slot semantics: positions >=
    length are dead, an optional sliding ``window`` keeps only the last
    ``window`` of them).  ``layer`` (int32 scalar) rides scalar prefetch
    next to ``lengths``, so the BlockSpec index_map reads the layer's
    tiles straight out of the stacked buffer and no per-layer slice is
    ever made in HBM; a caller holding one layer passes ``cache[None]``
    and layer 0.  GQA is blocked like attend_cache: head h belongs to kv
    group h // g."""
    b, h, hd = q.shape
    _, _, s, kv, _ = k_cache.shape
    _check_gqa(h, kv)
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    block_k = min(block_k, s)
    nk = pl.cdiv(s, block_k)
    qg = q.reshape(b, kv, g, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((None, kv, g, hd),
                         lambda bb, ki, L, lyr: (bb, 0, 0, 0)),
            pl.BlockSpec((None, None, block_k, kv, hd),
                         lambda bb, ki, L, lyr: (lyr[0], bb, ki, 0, 0)),
            pl.BlockSpec((None, None, block_k, kv, hd),
                         lambda bb, ki, L, lyr: (lyr[0], bb, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, kv, g, hd),
                               lambda bb, ki, L, lyr: (bb, 0, 0, 0)),
        scratch_shapes=_decode_scratch(kv, g, hd),
    )
    o = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          kv_heads=kv),
        name="flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, k_cache, v_cache)
    return o.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# paged decode (block-pool KV cache gathered through a block table)
# ---------------------------------------------------------------------------

def flash_attention_paged_decode(q, k_pool, v_pool, table, lengths, *,
                                 scale=None, interpret=False):
    """One decode step against a paged KV pool: q [B, H, hd], pools
    [NB, BL, KV, hd], per-slot block ``table`` [B, MB] and valid
    ``lengths`` [B].  The table rides scalar prefetch so the BlockSpec
    index_map can route each (slot, logical-block) grid step straight at
    its pool block — the gather never materializes in HBM.  Unowned
    table entries point at the allocator's reserved null block; the
    length mask keeps whatever lives there out of the softmax."""
    b, h, hd = q.shape
    nb, bl, kv, _ = k_pool.shape
    mb = table.shape[1]
    _check_gqa(h, kv)
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(b, kv, g, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((None, kv, g, hd),
                         lambda bb, ki, tbl, L: (bb, 0, 0, 0)),
            pl.BlockSpec((None, bl, kv, hd),
                         lambda bb, ki, tbl, L: (tbl[bb, ki], 0, 0, 0)),
            pl.BlockSpec((None, bl, kv, hd),
                         lambda bb, ki, tbl, L: (tbl[bb, ki], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, kv, g, hd),
                               lambda bb, ki, tbl, L: (bb, 0, 0, 0)),
        scratch_shapes=_decode_scratch(kv, g, hd),
    )
    o = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, kv_heads=kv),
        name="flash_decode_paged",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      qg, k_pool, v_pool)
    return o.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *,
                   scale, causal, window, block_q, block_k, sq, sk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    kvalid = _row_mask(ki * block_k, block_k, sk)
    qvalid = _row_mask(qi * block_q, block_q, sq)
    q = _clean(q_ref[...].astype(jnp.float32), qvalid) * scale
    k = _clean(k_ref[...].astype(jnp.float32), kvalid)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (k_pos < sk) & (q_pos < sq)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    p = jnp.where(mask, jnp.exp(s - lse_ref[...]), 0.0)
    do = _clean(do_ref[...].astype(jnp.float32), qvalid)
    v = _clean(v_ref[...].astype(jnp.float32), kvalid)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta_ref[...])
    dq_scr[...] += jax.lax.dot(ds, k) * scale

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, causal, window, block_q, block_k, sq, sk):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    kvalid = _row_mask(ki * block_k, block_k, sk)
    qvalid = _row_mask(qi * block_q, block_q, sq)
    qraw = _clean(q_ref[...].astype(jnp.float32), qvalid)
    q = qraw * scale
    k = _clean(k_ref[...].astype(jnp.float32), kvalid)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (k_pos < sk) & (q_pos < sq)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    p = jnp.where(mask, jnp.exp(s - lse_ref[...]), 0.0)
    do = _clean(do_ref[...].astype(jnp.float32), qvalid)
    v = _clean(v_ref[...].astype(jnp.float32), kvalid)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta_ref[...])
    dk_scr[...] += jax.lax.dot(ds.T, qraw) * scale
    dv_scr[...] += jax.lax.dot(p.T, do)

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=None,
                        scale=None, block_q=128, block_k=128,
                        interpret=False):
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    _check_gqa(h, kv)
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    # lse/delta enter as [B, H, Sq, 1] (the forward's kernel layout)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[..., None]
    lse = lse[..., None]

    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    doT = do.transpose(0, 2, 1, 3)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q,
                          block_k=block_k, sq=sq, sk=sk),
        name="flash_bwd_dq",
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0)),
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        interpret=interpret,
    )(qT, kT, vT, doT, lse, delta)

    # dk/dv: accumulate over q-heads of the same kv group sequentially via
    # the h grid axis mapping h -> kv head (output revisited g times).
    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q,
                          block_k=block_k, sq=sq, sk=sk),
        name="flash_bwd_dkv",
        grid=(b, kv, nk, nq),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bb, hh, ki, qi: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, ki, qi: (bb, hh, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, ki, qi: (bb, hh, ki, 0)),
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bb, hh, ki, qi: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bb, hh, ki, qi: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bb, hh, ki, qi: (bb, hh, qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, ki, qi: (bb, hh, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, ki, qi: (bb, hh, ki, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        out_shape=(jax.ShapeDtypeStruct((b, kv, sk, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b, kv, sk, hd), jnp.float32)),
        interpret=interpret,
    )
    # run dkv once per q-head-group member, summing (keeps kernel simple
    # and the per-call grid dense); g is small (<= H/KV).
    dk = jnp.zeros((b, kv, sk, hd), jnp.float32)
    dv = jnp.zeros((b, kv, sk, hd), jnp.float32)
    for gi in range(g):
        qg = qT[:, gi::g][:, :kv]
        dog = doT[:, gi::g][:, :kv]
        lseg = lse[:, gi::g][:, :kv]
        deltag = delta[:, gi::g][:, :kv]
        dki, dvi = dkv(qg, kT, vT, dog, lseg, deltag)
        dk = dk + dki
        dv = dv + dvi
    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3).astype(k.dtype),
            dv.transpose(0, 2, 1, 3).astype(v.dtype))
