"""Plain reference of the Qwen2 decoder (arXiv:2407.10671; the Hugging
Face ``Qwen2ForCausalLM``), written from the published description and
importing nothing of the program under test.

Layer equations, per token x of width d:
    h = x + Wo . attn(rope(Wq n1(x) + bq), rope(Wk n1(x) + bk), Wv n1(x) + bv)
    y = h + Wd . (silu(Wg n2(h)) * (Wu n2(h)))
with RMS norms n1, n2 (scale only), grouped-query causal softmax attention
over head_dim-wide heads, rotary embedding on the two halves of each head
(``rotate_half``), a final RMS norm and an LM head (the transposed
embedding when tied).  Everything is computed in float32 at the highest
matmul precision; the weights are the values the program is given.

``make_params`` draws those weights from a seed, in the pytree layout the
program takes (stacked layers first), so that the benchmark can hand the
program its weights and this module can draw the same ones again.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any
F32 = jnp.float32


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return {"d": d, "h": h, "kv": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // h, "f": c["intermediate_size"],
            "v": c["vocab_size"], "L": c["num_hidden_layers"]}


def seed_key(seed: int):
    """A PRNG key from any non-negative seed below 2**64 (PRNGKey alone
    keeps only the low 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def param_layout(c: Dict[str, Any]) -> Dict[str, tuple]:
    """name -> (shape, dtype, init kind, scale), in a fixed order; the
    order numbers each leaf's key, so it never changes for a leaf."""
    m = dims(c)
    d, h, kv, hd, f, v, L = (m[k] for k in ("d", "h", "kv", "hd", "f",
                                            "v", "L"))
    bf = jnp.bfloat16
    out = {
        "embed": ((v, d), bf, "normal", 0.02),
        "ln_f": ((d,), F32, "norm", 0.1),
        "layers.ln1": ((L, d), F32, "norm", 0.1),
        "layers.ln2": ((L, d), F32, "norm", 0.1),
        "layers.attn.wq": ((L, d, h * hd), bf, "normal", d ** -0.5),
        "layers.attn.wk": ((L, d, kv * hd), bf, "normal", d ** -0.5),
        "layers.attn.wv": ((L, d, kv * hd), bf, "normal", d ** -0.5),
        "layers.attn.wo": ((L, h * hd, d), bf, "normal", (h * hd) ** -0.5),
        "layers.attn.bq": ((L, h * hd), bf, "normal", 0.1),
        "layers.attn.bk": ((L, kv * hd), bf, "normal", 0.1),
        "layers.attn.bv": ((L, kv * hd), bf, "normal", 0.1),
        "layers.mlp.wg": ((L, d, f), bf, "normal", d ** -0.5),
        "layers.mlp.wu": ((L, d, f), bf, "normal", d ** -0.5),
        "layers.mlp.wd": ((L, f, d), bf, "normal", f ** -0.5),
    }
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), bf, "normal", d ** -0.5)
    return out


def _leaf(key, shape, dtype, kind, scale):
    z = jax.random.normal(key, shape, F32)
    if kind == "norm":
        return (1.0 + scale * z).astype(dtype)
    return (scale * z).astype(dtype)


def _nest(flat: Dict[str, Any]) -> PyTree:
    out: Dict[str, Any] = {}
    for name, val in flat.items():
        node = out
        *path, last = name.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = val
    return out


def leaf_value(c: Dict[str, Any], key, name: str):
    """One weight leaf as build_params draws it from ``key`` (traceable;
    for reading one leaf's change without holding a second copy of every
    weight)."""
    names = list(param_layout(c))
    shape, dtype, kind, scale = param_layout(c)[name]
    return _leaf(jax.random.fold_in(key, names.index(name)),
                 shape, dtype, kind, scale)


def build_params(c: Dict[str, Any], key) -> PyTree:
    """All weights from ``key`` (traceable; make_params jits it), in the
    dtype they are served and trained in (bf16 matrices, f32 norm
    scales)."""
    return _nest({n: _leaf(jax.random.fold_in(key, i), *spec)
                  for i, (n, spec) in enumerate(param_layout(c).items())})


def make_params(c: Dict[str, Any], seed: int):
    """All weights from ``seed``, made on the device in one jitted call."""
    return jax.jit(functools.partial(build_params, c))(seed_key(seed))


def flat_names(c: Dict[str, Any]):
    return list(param_layout(c))


def get_leaf(tree: PyTree, name: str):
    for p in name.split("."):
        tree = tree[p]
    return tree


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def fp8_round(x):
    """Control precision: float8 e4m3 storage with one scale per tensor
    (amax mapped to 448, the format's largest normal).  The backward pass
    goes straight through the rounding, so gradients are taken at the
    rounded values without rounding the cotangents to nought."""
    x = x.astype(F32)
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / 448.0)
    r = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(r - x)


def _mm(a, b, q: Optional[Callable]):
    if q is not None:
        a, b = q(a), q(b)
    return a @ b


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x [S, n, hd]; rotate_half convention, frequencies theta^(-2i/hd)."""
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-np.arange(0, half, dtype=np.float64) * 2.0 / hd)
    ang = pos[:, None].astype(F32) * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(c, p, x, q=None):
    """One decoder layer on one sequence x [S, d] (float32)."""
    m = dims(c)
    h, kv, hd = m["h"], m["kv"], m["hd"]
    s = x.shape[0]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(s)
    xn = rms_norm(x, p["ln1"], eps)
    a = p["attn"]
    qh = rope((_mm(xn, a["wq"], q) + a["bq"]).reshape(s, h, hd), pos, theta)
    kh = rope((_mm(xn, a["wk"], q) + a["bk"]).reshape(s, kv, hd), pos, theta)
    vh = (_mm(xn, a["wv"], q) + a["bv"]).reshape(s, kv, hd)
    g = h // kv
    qg = qh.reshape(s, kv, g, hd)
    if q is not None:
        qg, kh, vh = q(qg), q(kh), q(vh)
    sc = jnp.einsum("sKgd,tKd->Kgst", qg, kh) * hd ** -0.5
    mask = pos[:, None] >= pos[None, :]
    sc = jnp.where(mask, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    if q is not None:
        pr = q(pr)
    o = jnp.einsum("Kgst,tKd->sKgd", pr, vh).reshape(s, h * hd)
    x = x + _mm(o, a["wo"], q)
    xn = rms_norm(x, p["ln2"], eps)
    mp = p["mlp"]
    y = jax.nn.silu(_mm(xn, mp["wg"], q)) * _mm(xn, mp["wu"], q)
    return x + _mm(y, mp["wd"], q)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def hidden(c, params, tokens, q=None):
    """Final normed hidden states [S, d] of one sequence; layers run one
    at a time (scan), each recomputed in the backward pass."""
    x = params["embed"][tokens].astype(F32)

    def body(x, p):
        return jax.checkpoint(lambda xx, pp: layer(c, _f32(pp), xx, q))(
            x, p), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["ln_f"].astype(F32), c["rms_norm_eps"])


def head_weight(c, params):
    w = (params["embed"].T if c["tie_word_embeddings"]
         else params["lm_head"])
    return w.astype(F32)


def logits(c, params, tokens, q=None, rows=None):
    """Logits of one sequence [S, V], or at positions ``rows`` only."""
    with jax.default_matmul_precision("highest"):
        x = hidden(c, params, tokens, q)
        if rows is not None:
            x = x[rows]
        return _mm(x, head_weight(c, params), q)


def loss(c, params, tokens, labels, q=None, rows: int = 512,
         half: bool = False):
    """Mean next-token cross-entropy over a batch [B, S]; the head and
    the loss are taken in blocks of ``rows`` rows, each recomputed in the
    backward pass, so the [S, V] logits are never held whole.  ``half``
    plants a fault: the mean is taken over the first half of the tokens
    alone."""
    w = head_weight(c, params)

    def seq_loss(tok, lab):
        x = hidden(c, params, tok, q)
        if half:
            x, lab = x[:x.shape[0] // 2], lab[:x.shape[0] // 2]
        s = x.shape[0]
        r = int(np.gcd(s, rows))
        xb = x.reshape(s // r, r, -1)
        lb = lab.reshape(s // r, r)

        def blk(acc, inp):
            xi, li = inp

            def ce(xi, w):
                lg = _mm(xi, w, q)
                lse = jax.nn.logsumexp(lg, -1)
                gold = jnp.take_along_axis(lg, li[:, None], -1)[:, 0]
                return jnp.sum(lse - gold)

            return acc + jax.checkpoint(ce)(xi, w), None

        tot, _ = jax.lax.scan(blk, jnp.zeros((), F32), (xb, lb))
        return tot

    with jax.default_matmul_precision("highest"):
        tot = sum(seq_loss(tokens[i], labels[i])
                  for i in range(tokens.shape[0]))
    return tot / (tokens.size // 2 if half else tokens.size)


# ---------------------------------------------------------------------------
# AdamW, as the configuration states it
# ---------------------------------------------------------------------------

def lr_at(o: Dict[str, Any], t):
    """Learning rate of update number t (1-based, traced): linear warm-up
    over ``warmup_steps`` (reaching lr at update warmup_steps - 1), then
    cosine decay to ``min_lr_frac`` * lr at ``total_steps``."""
    t = jnp.asarray(t, F32)
    warm = jnp.minimum(1.0, (t + 1) / max(1, o["warmup_steps"]))
    prog = jnp.clip((t - o["warmup_steps"])
                    / max(1, o["total_steps"] - o["warmup_steps"]), 0.0, 1.0)
    frac = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * warm * frac


def adamw(o: Dict[str, Any], t: int, p, g, m, v):
    """One AdamW update (update number t) with global-norm clipping and
    decoupled weight decay on every leaf of rank >= 2 as stored."""
    leaves = jax.tree_util.tree_leaves(g)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    scale = jnp.minimum(1.0, o["clip_norm"] / (gn + 1e-9))
    b1, b2, lr = o["beta1"], o["beta2"], lr_at(o, t)
    t = jnp.asarray(t, F32)

    def upd(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
        wd = o["weight_decay"] if p.ndim >= 2 else 0.0
        return p - lr * (mh / (jnp.sqrt(vh) + o["eps"]) + wd * p), m, v

    flat_p, tdef = jax.tree_util.tree_flatten(p)
    out = [upd(*a) for a in zip(flat_p, tdef.flatten_up_to(g),
                                tdef.flatten_up_to(m), tdef.flatten_up_to(v))]
    p, m, v = (tdef.unflatten([o[i] for o in out]) for i in range(3))
    return p, m, v, scale


@functools.partial(jax.jit, static_argnums=(0, 1, 3, 4),
                   donate_argnums=(5,))
def train_step(c_items, o_items, t, q, half, state, tokens, labels):
    """One reference training step (update number ``t``, traced, so that
    every step runs one program) on f32 state {p, m, v}; returns the
    new state, the loss and each leaf's clipped gradient norm.  ``q``
    rounds matmul operands (the control precision); ``half`` plants the
    half-batch fault."""
    c, o = dict(c_items), dict(o_items)
    lval, g = jax.value_and_grad(
        lambda p: loss(c, p, tokens, labels, q, half=half))(state["p"])
    p, m, v, scale = adamw(o, t, state["p"], g, state["m"], state["v"])
    gn = jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(x * x)) * scale,
                                g)
    return {"p": p, "m": m, "v": v}, lval, gn


def freeze(d: Dict[str, Any]) -> tuple:
    """A hashable form of a flat dict, for static jit arguments."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))
