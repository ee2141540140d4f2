"""Shared model components (pure-functional JAX, pytree params)."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def shard(x, plan, role: str, phys_dims: Sequence[str]):
    """Apply a solver-derived sharding constraint; no-op without a plan or
    outside a mesh context (``jax.set_mesh``).  Under a mesh, a spec the
    mesh cannot take is an error, never a silently unsharded tensor."""
    if plan is None:
        return x
    if not plan.has_role(role):
        # unknown role: do NOT constrain (P() would force replication!)
        return x
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, plan.pspec(role, phys_dims))


def rms_norm(x, gamma, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(dt)


def rope(x, positions, theta: float = 1e4):
    """Rotary embedding. x: [..., seq, heads, hd]; positions: [..., seq]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) *
                    jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    if 2 * half != hd:  # odd head_dim tail passes through
        rot = jnp.concatenate([rot, x[..., 2 * half:]], -1)
    return rot.astype(x.dtype)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.bfloat16):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(jnp.maximum(fan_in, 1))).astype(dtype)


def embed_init(key, shape, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def keygen(key):
    while True:
        key, sub = jax.random.split(key)
        yield sub


def softmax_cross_entropy(logits, labels, vocab: int):
    """Token-mean CE; stable logsumexp over (possibly vocab-sharded) logits."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def causal_mask(sq: int, sk: int, q_off, k_off, window: Optional[int] = None):
    """[sq, sk] boolean mask (True = attend) for absolute offsets."""
    qi = q_off + jnp.arange(sq)[:, None]
    ki = k_off + jnp.arange(sk)[None, :]
    m = ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m
