"""Mamba2 SSD chunk-scan kernel for TPU in Pallas.

TPU adaptation: the chunk axis is the innermost (sequential) grid
dimension; the running SSM state S [P, N] lives in VMEM scratch across
chunk iterations.  Within a chunk everything is (Q×Q)/(Q×N) matmuls on
the MXU — the CUDA version's warp-level scan has no TPU analogue and is
replaced by this matmul-plus-carried-state decomposition (see DESIGN.md).

Grid: (B, H, n_chunks).  Per-head inputs; B/C are shared across heads
(Mamba2 single group) and indexed by (b, chunk)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xh_ref, al_ref, b_ref, c_ref, y_ref, s_scr, *,
                chunk, nstate):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    xh = xh_ref[...].astype(jnp.float32)        # [Q, P]
    al = al_ref[...].astype(jnp.float32)        # [1, Q] log decay
    bb = b_ref[...].astype(jnp.float32)         # [Q, N]
    cc = c_ref[...].astype(jnp.float32)         # [Q, N]

    # inclusive prefix sums of the log decay, as a column and as a row
    # (Mosaic lowers no cumsum): cum_q = sum_{t<=q} al_t.  The row comes
    # from a triangular matmul at HIGHEST precision, since a one-pass
    # bf16 matmul would round the decays to 8 bits
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = row >= col                                     # [q, t]: t <= q
    cum_c = jnp.sum(jnp.where(mask, al, 0.0), -1, keepdims=True)  # [Q, 1]
    cum_r = jax.lax.dot(al, (row <= col).astype(jnp.float32),   # [1, Q]
                        precision=jax.lax.Precision.HIGHEST)
    total = jnp.sum(al, -1, keepdims=True)                # [1, 1]

    # intra-chunk: y_q += sum_{t<=q} (C_q·B_t) exp(cum_q - cum_t) x_t
    cb = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())))  # [Q, Q]
    dec = cum_c - cum_r
    w = jnp.where(mask, jnp.exp(jnp.clip(dec, -60.0, 0.0)), 0.0)
    y_intra = jax.lax.dot(cb * w, xh)           # [Q, P]

    # inter-chunk: y_q += exp(cum_q) C_q · S_prev
    s_prev = s_scr[...]                         # [P, N]
    decay_in = jnp.exp(jnp.clip(cum_c, -60.0, 0.0))
    y_inter = jax.lax.dot_general(
        cc, s_prev, (((1,), (1,)), ((), ()))) * decay_in
    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S = exp(cum_Q) S_prev + sum_t exp(cum_Q - cum_t) x_t B_t
    tail = jnp.exp(jnp.clip(total - cum_c, -60.0, 0.0))   # [Q, 1]
    s_local = jax.lax.dot_general(
        xh * tail, bb, (((0,), (0,)), ((), ())))          # [P, N]
    s_scr[...] = s_prev * jnp.exp(jnp.clip(total, -60.0, 0.0)) + s_local


def ssd_chunk_scan(xh, a_log, bb, cc, *, chunk: int = 128,
                   interpret: bool = False):
    """xh: [B,S,H,P], a_log: [B,S,H], bb/cc: [B,S,N] -> y [B,S,H,P].

    Pallas TPU kernel; matches kernels.ref.ssd_ref (which also returns
    the final state — the kernel keeps it in scratch only)."""
    b, s, h, p = xh.shape
    n = bb.shape[-1]
    chunk = min(chunk, s)
    nc = pl.cdiv(s, chunk)
    assert s % chunk == 0, "pad seq to a chunk multiple"

    xhT = xh.transpose(0, 2, 1, 3)              # [B,H,S,P]
    alT = a_log.transpose(0, 2, 1)[:, :, None]  # [B,H,1,S]: lane-major

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, nstate=n),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda bb_, hh, ci: (bb_, hh, ci, 0)),
            pl.BlockSpec((None, None, 1, chunk),
                         lambda bb_, hh, ci: (bb_, hh, 0, ci)),
            pl.BlockSpec((None, chunk, n),
                         lambda bb_, hh, ci: (bb_, ci, 0)),
            pl.BlockSpec((None, chunk, n),
                         lambda bb_, hh, ci: (bb_, ci, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, p),
                               lambda bb_, hh, ci: (bb_, hh, ci, 0)),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
        interpret=interpret,
    )(xhT, alT, bb, cc)
    return y.transpose(0, 2, 1, 3)
