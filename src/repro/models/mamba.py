"""Mamba2 (SSD) block — chunked matmul formulation (TPU-friendly: the
sequential recurrence only crosses chunk boundaries; within a chunk all
work is batched matmuls that map onto the MXU).

State-space:  h_t = a_t * h_{t-1} + dt_t * x_t ⊗ B_t ;  y_t = C_t · h_t
with a_t = exp(dt_t * A) per head (A < 0), B/C shared across heads
(single group), head channels P, state N.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import dense_init, rms_norm, shard


def init_mamba(key, cfg: ArchConfig, dtype=jnp.bfloat16):
    d = cfg.d_model
    di = cfg.d_inner
    n = cfg.ssm.state_dim
    p = cfg.ssm.head_dim
    h = di // p
    cd = cfg.ssm.conv_dim
    ks = jax.random.split(key, 4)
    return {
        # main in-projection [z (di), x (di)]; the small B/C/dt projection
        # is a separate param so the big matrix stays evenly shardable
        # on the 'inner' dim (2*di is a multiple of the SSM head size)
        "w_in": dense_init(ks[0], (d, 2 * di), dtype=dtype),
        "w_bcdt": dense_init(ks[2], (d, 2 * n + h), dtype=dtype),
        "conv_w": (jnp.zeros((cd, di + 2 * n), jnp.float32)
                   .at[-1].set(1.0).astype(dtype)),   # identity-ish init
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.ones((di,), jnp.float32),
        "w_out": dense_init(ks[1], (di, d), dtype=dtype),
    }


def _split_proj(cfg, zx, bcdt):
    di, n = cfg.d_inner, cfg.ssm.state_dim
    z = zx[..., :di]
    xs = zx[..., di:]
    bb = bcdt[..., :n]
    cc = bcdt[..., n:2 * n]
    dt = bcdt[..., 2 * n:]
    return z, xs, bb, cc, dt


def _causal_conv(x, w):
    """Depthwise causal conv.  x: [B, S, C]; w: [K, C]."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i:i + x.shape[1], :].astype(jnp.float32) \
            * w[i].astype(jnp.float32)
    return out.astype(x.dtype)


def ssd_scan(xh, a_log, bb, cc, chunk: int):
    """Chunked SSD.  xh: [B, S, H, P] (dt already folded in), a_log:
    [B, S, H] per-step log decay (<= 0), bb/cc: [B, S, N].
    Returns y: [B, S, H, P] and final state [B, H, P, N]."""
    b, s, h, p = xh.shape
    n = bb.shape[-1]
    q = min(chunk, s)
    nc = (s + q - 1) // q
    pad = nc * q - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a_log = jnp.pad(a_log, ((0, 0), (0, pad), (0, 0)))
        bb = jnp.pad(bb, ((0, 0), (0, pad), (0, 0)))
        cc = jnp.pad(cc, ((0, 0), (0, pad), (0, 0)))
    xh = xh.reshape(b, nc, q, h, p).astype(jnp.float32)
    al = a_log.reshape(b, nc, q, h).astype(jnp.float32)
    bb = bb.reshape(b, nc, q, n).astype(jnp.float32)
    cc = cc.reshape(b, nc, q, n).astype(jnp.float32)

    cum = jnp.cumsum(al, axis=2)                      # [B,nc,Q,H]
    # intra-chunk: scores[q,t] = (C_q·B_t)·exp(cum_q - cum_t), t <= q
    cb = jnp.einsum("bcqn,bctn->bcqt", cc, bb)        # [B,nc,Q,Q]
    dec = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [B,nc,Q,T,H]
    mask = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])
    w = jnp.where(mask[None, None, :, :, None],
                  jnp.exp(jnp.clip(dec, -60.0, 0.0)), 0.0)
    y_intra = jnp.einsum("bcqt,bcqth,bcthp->bcqhp", cb, w, xh)

    # chunk-local end states: S_local = sum_t exp(cumQ - cum_t) x_t ⊗ B_t
    decay_tail = jnp.exp(jnp.clip(cum[:, :, -1:, :] - cum, -60.0, 0.0))
    s_local = jnp.einsum("bcth,bcthp,bctn->bchpn",
                         decay_tail, xh, bb)          # [B,nc,H,P,N]

    # carry states across chunks
    chunk_decay = jnp.exp(jnp.clip(cum[:, :, -1, :], -60.0, 0.0))  # [B,nc,H]

    def step(carry, inp):
        s_prev = carry
        dchunk, sloc = inp
        s_new = s_prev * dchunk[..., None, None] + sloc
        return s_new, s_prev

    s0 = jnp.zeros((b, h, p, n), jnp.float32)
    s_final, s_prevs = jax.lax.scan(
        step, s0, (chunk_decay.swapaxes(0, 1), s_local.swapaxes(0, 1)))
    s_prevs = s_prevs.swapaxes(0, 1)                  # [B,nc,H,P,N]

    # inter-chunk contribution: y_q += exp(cum_q) * C_q · S_prev
    decay_in = jnp.exp(jnp.clip(cum, -60.0, 0.0))     # [B,nc,Q,H]
    y_inter = jnp.einsum("bcqn,bchpn,bcqh->bcqhp",
                         cc, s_prevs, decay_in)
    y = (y_intra + y_inter).reshape(b, nc * q, h, p)[:, :s]
    return y, s_final


def _ssd_pallas_impl(xh, a_log, bb, cc, chunk):
    from ..kernels import ops as kops
    return kops.ssd_chunk_scan(xh, a_log, bb, cc, chunk=chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ssd_pallas(xh, a_log, bb, cc, chunk):
    """Pallas SSD chunk-scan with the XLA chunked formulation as the
    backward (no hand-written bwd kernel yet; ``ssd_scan`` recomputes the
    forward under jax.vjp, so gradients are exact w.r.t. the XLA math and
    agree with the kernel to its fwd parity tolerance)."""
    return _ssd_pallas_impl(xh, a_log, bb, cc, chunk)


def _ssd_pallas_fwd(xh, a_log, bb, cc, chunk):
    return _ssd_pallas_impl(xh, a_log, bb, cc, chunk), (xh, a_log, bb, cc)


def _ssd_pallas_bwd(chunk, res, dy):
    xh, a_log, bb, cc = res
    _, vjp = jax.vjp(lambda *t: ssd_scan(*t, chunk)[0], xh, a_log, bb, cc)
    return vjp(dy.astype(jnp.float32))


_ssd_pallas.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)


def _ssd_dispatch(xh, a_log, bb, cc, chunk: int, impl: str,
                  plan=None, mesh=None):
    """Route the SSD scan: impl="pallas" pads the sequence to a chunk
    multiple (the kernel grid wants S % chunk == 0) and runs the Pallas
    kernel, under shard_map on the plan's batch sharding when a mesh is
    present (pallas_call has no GSPMD partitioning rule).  Returns y
    only; the XLA path stays the source of the final state."""
    if impl != "pallas":
        return ssd_scan(xh, a_log, bb, cc, chunk)[0]
    b, s, h, p = xh.shape
    q = min(chunk, s)
    nc = (s + q - 1) // q
    pad = nc * q - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a_log = jnp.pad(a_log, ((0, 0), (0, pad), (0, 0)))
        bb = jnp.pad(bb, ((0, 0), (0, pad), (0, 0)))
        cc = jnp.pad(cc, ((0, 0), (0, pad), (0, 0)))
    if mesh is None or plan is None:
        return _ssd_pallas(xh, a_log, bb, cc, q)[:, :s]

    from jax.sharding import PartitionSpec as P

    from .attention import _axes_degree, _spec_entries

    bs = _spec_entries(plan.pspec("ssm_h", ("batch", "seq", "inner")), 3)[0]
    if bs is not None and b % _axes_degree(mesh, bs) != 0:
        bs = None
    fn = jax.shard_map(
        lambda x_, a_, b_, c_: _ssd_pallas(x_, a_, b_, c_, q), mesh=mesh,
        in_specs=(P(bs, None, None, None), P(bs, None, None),
                  P(bs, None, None), P(bs, None, None)),
        out_specs=P(bs, None, None, None),
        check_vma=False)
    return fn(xh, a_log, bb, cc)[:, :s]


def mamba_forward(params, x, cfg: ArchConfig, plan=None, *,
                  impl: str = "xla", mesh=None):
    """x: [B, S, D] -> [B, S, D] (training / prefill; returns no state)."""
    b, s, d = x.shape
    di, n = cfg.d_inner, cfg.ssm.state_dim
    p = cfg.ssm.head_dim
    h = di // p
    zx = x @ params["w_in"]
    zx = shard(zx, plan, "ssm_h", ("batch", "seq", "inner"))
    z, xs, bb, cc, dt = _split_proj(cfg, zx, x @ params["w_bcdt"])
    conv_in = jnp.concatenate([xs, bb, cc], -1)
    conv_out = jax.nn.silu(
        _causal_conv(conv_in, params["conv_w"]).astype(jnp.float32))
    xs = conv_out[..., :di]
    bb = conv_out[..., di:di + n]
    cc = conv_out[..., di + n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"])          # [B,S,H]
    a = -jnp.exp(params["A_log"])                      # [H]
    a_log = dt * a                                     # [B,S,H]
    xh = xs.reshape(b, s, h, p) * dt[..., None]
    y = _ssd_dispatch(xh, a_log, bb, cc, cfg.ssm.chunk, impl,
                      plan=plan, mesh=mesh)
    y = y + params["D"][None, None, :, None] * xs.reshape(b, s, h, p)
    y = y.reshape(b, s, di)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(x.dtype), params["norm"], cfg.norm_eps)
    return y @ params["w_out"]


def init_mamba_state(cfg: ArchConfig, batch: int):
    di, n = cfg.d_inner, cfg.ssm.state_dim
    p = cfg.ssm.head_dim
    h = di // p
    cd = cfg.ssm.conv_dim
    return {
        "ssm": jnp.zeros((batch, h, p, n), jnp.float32),
        "conv": jnp.zeros((batch, cd - 1, di + 2 * n), jnp.bfloat16),
    }


def mamba_step(params, x, state, cfg: ArchConfig, plan=None):
    """Single decode step.  x: [B, D] -> (y [B, D], new state)."""
    b, d = x.shape
    di, n = cfg.d_inner, cfg.ssm.state_dim
    p = cfg.ssm.head_dim
    h = di // p
    zx = x @ params["w_in"]
    z, xs, bb, cc, dt = _split_proj(cfg, zx, x @ params["w_bcdt"])
    conv_in = jnp.concatenate([xs, bb, cc], -1)        # [B, di+2N]
    hist = jnp.concatenate([state["conv"],
                            conv_in[:, None, :]], 1)   # [B, cd, C]
    conv_out = jnp.einsum("bkc,kc->bc", hist.astype(jnp.float32),
                          params["conv_w"].astype(jnp.float32))
    conv_out = jax.nn.silu(conv_out)
    xs = conv_out[:, :di]
    bb = conv_out[:, di:di + n]
    cc = conv_out[:, di + n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = jnp.exp(dt * -jnp.exp(params["A_log"]))        # [B,H]
    xh = xs.reshape(b, h, p) * dt[..., None]
    s_new = (state["ssm"] * a[..., None, None]
             + jnp.einsum("bhp,bn->bhpn", xh, bb))
    y = jnp.einsum("bhpn,bn->bhp", s_new, cc)
    y = y + params["D"][None, :, None] * xs.reshape(b, h, p)
    y = y.reshape(b, di) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(x.dtype), params["norm"], cfg.norm_eps)
    new_state = {"ssm": s_new, "conv": hist[:, 1:].astype(jnp.bfloat16)}
    return y @ params["w_out"], new_state
