"""jit'd public wrappers for the Pallas kernels.

`flash_attention` carries a custom_vjp wired to the Pallas backward
kernels.  On a CPU backend the kernels execute in interpret mode
(Pallas-TPU cannot compile to CPU); on a TPU they always run compiled.

The mode is resolved ONCE (cached) so every call in a compiled program
agrees.  `REPRO_PALLAS_INTERPRET=0` forces compiled kernels on a CPU
host, for ahead-of-time compiles against a described TPU topology;
asking for interpret mode (=1) on a TPU backend is an error, so no run
on the chip can fall back to the interpreter.  Tests that flip the env
var must call ``_default_interpret.cache_clear()``.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import flash_attention as fa
from . import ssd as ssd_mod

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


@functools.lru_cache(maxsize=None)
def _default_interpret() -> bool:
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    on_tpu = jax.default_backend() == "tpu"
    if env in _TRUTHY:
        if on_tpu:
            raise RuntimeError(
                "REPRO_PALLAS_INTERPRET asks for interpret-mode kernels on "
                "a TPU backend; the kernels run compiled there")
        return True
    if env in _FALSY:
        return False
    return not on_tpu


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None):
    o, _ = fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                  scale=scale,
                                  interpret=_default_interpret())
    return o


def _fa_fwd(q, k, v, causal, window, scale):
    o, lse = fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                    scale=scale,
                                    interpret=_default_interpret())
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, window, scale, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = fa.flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, window=window, scale=scale,
        interpret=_default_interpret())
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_offset(q, k, v, q_offset, *, causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None):
    """Forward-only flash attention with a (possibly traced) query
    offset — the chunked-prefill path, where the q block sits at cache
    position ``q_offset`` against keys 0..sk.  No vjp: prefill/decode
    serving never differentiates, and the offset being a traced value
    rules out the nondiff_argnums route the trainable kernel uses."""
    o, _ = fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                  scale=scale, q_offset=q_offset,
                                  interpret=_default_interpret())
    return o


def flash_attention_decode(q, k_cache, v_cache, lengths, layer, *,
                           window: Optional[int] = None,
                           scale: Optional[float] = None):
    """One decode step of layer ``layer`` against the serving engine's
    stacked slot cache [L, B, S, KV, hd] (per-slot ``lengths``, optional
    sliding window); the kernel reads the layer's tiles in place.
    Forward-only."""
    return fa.flash_attention_decode(q, k_cache, v_cache, lengths, layer,
                                     window=window, scale=scale,
                                     interpret=_default_interpret())


def flash_attention_paged_decode(q, k_pool, v_pool, table, lengths, *,
                                 scale: Optional[float] = None):
    """One decode step against the paged block-pool KV cache, gathering
    blocks through the scalar-prefetched ``table``.  Forward-only."""
    return fa.flash_attention_paged_decode(q, k_pool, v_pool, table,
                                           lengths, scale=scale,
                                           interpret=_default_interpret())


def ssd_chunk_scan(xh, a_log, bb, cc, chunk: int = 128):
    return ssd_mod.ssd_chunk_scan(xh, a_log, bb, cc, chunk=chunk,
                                  interpret=_default_interpret())
