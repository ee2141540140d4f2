"""Operations and bytes that the algorithm needs, from shapes alone.

These count the work of the mathematics, whatever program performs it:
a kernel that reads more than the valid part of a cache, or a step that
recomputes activations, does not raise them.  ``c`` is a configuration's
``config`` block (the published config.json keys, as run).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2


def _d(c: Dict) -> Tuple[int, int, int, int, int, int, int]:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    return (d, h, c["num_key_value_heads"], hd, c["intermediate_size"],
            c["vocab_size"], c["num_hidden_layers"])


def layer_matmul_params(c: Dict) -> int:
    """Weights of one decoder layer that take part in a matmul."""
    d, h, kv, hd, f, _, _ = _d(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def param_count(c: Dict) -> int:
    """All weights: matrices, biases, norm scales, embedding (and the LM
    head when untied)."""
    d, h, kv, hd, f, v, L = _d(c)
    per = layer_matmul_params(c) + (h + 2 * kv) * hd + 2 * d
    emb = v * d * (1 if c["tie_word_embeddings"] else 2)
    return L * per + emb + d


def kv_bytes_per_token(c: Dict) -> int:
    """Bytes of keys and values one token leaves in a bf16 cache, all
    layers (qwen2-1.5b: 28 x 2 x 2 x 128 x 2 = 28,672)."""
    _, _, kv, hd, _, _, L = _d(c)
    return L * 2 * kv * hd * BF16


def attn_flops(c: Dict, q_pos: Iterable[int]) -> float:
    """Causal attention of queries at absolute positions ``q_pos``
    (0-based) over keys 0..pos, all layers: QK^T and PV, 2 FLOP per
    multiply-add each."""
    _, h, _, hd, _, _, L = _d(c)
    return float(L * 4 * h * hd * sum(p + 1 for p in q_pos))


def causal_attn_flops_span(c: Dict, start: int, n: int) -> float:
    """attn_flops for the n positions start .. start+n-1, in closed form."""
    _, h, _, hd, _, _, L = _d(c)
    ctx = n * start + n * (n + 1) // 2
    return float(L * 4 * h * hd * ctx)


def head_flops(c: Dict, tokens: int) -> float:
    d, _, _, _, _, v, _ = _d(c)
    return 2.0 * d * v * tokens


def forward_flops(c: Dict, tokens: int, attn: float,
                  head_tokens: int) -> float:
    """Forward FLOPs: every layer matmul for ``tokens`` tokens, the given
    attention FLOPs, the LM head for ``head_tokens`` tokens."""
    _, _, _, _, _, _, L = _d(c)
    return (2.0 * L * layer_matmul_params(c) * tokens + attn
            + head_flops(c, head_tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward and backward (3x forward) per token of causal training at
    sequence length ``seq``; recomputation is not counted.
    qwen2-1.5b at 8 layers, 4096: 6 x 607.9 M + 0.302 G = 3.95 G."""
    attn = causal_attn_flops_span(c, 0, seq) / seq
    return 3.0 * forward_flops(c, 1, attn, 1)


def prefill_chunk_work(c: Dict, start: int, n: int) -> Dict[str, float]:
    """One prefill chunk of n valid tokens at cache offset ``start``.
    ``attn_*``: the causal attention over keys 0..start+n-1 (queries,
    keys and values read once, output written once, bf16); ``flops``:
    the layers' matmuls and that attention (the LM head is counted once
    per prompt, in prompt_head_flops)."""
    _, h, kv, hd, _, _, L = _d(c)
    a = causal_attn_flops_span(c, start, n)
    attn_bytes = L * BF16 * hd * (2 * n * h + 2 * (start + n) * kv)
    return {"attn_flops": a, "attn_bytes": float(attn_bytes),
            "flops": forward_flops(c, n, a, 0)}


def prompt_head_flops(c: Dict) -> float:
    """The LM head for the one position whose logits a prompt needs."""
    return head_flops(c, 1)


def decode_step_work(c: Dict, lengths: Iterable[int]) -> Dict[str, float]:
    """One pool-wide decode step; ``lengths``: each active slot's valid
    cache length after this token is written.  Attention reads each
    slot's valid keys and values once; the step reads every weight once
    (the embedding only for the rows it looks up)."""
    d, h, kv, hd, _, v, L = _d(c)
    lengths = list(lengths)
    b = len(lengths)
    ctx = sum(lengths)
    attn_f = float(L * 4 * h * hd * ctx)
    kv_bytes = float(L * 2 * kv * hd * BF16 * ctx)
    qo_bytes = float(L * 2 * b * h * hd * BF16)
    # a tied table is read whole by the head; an untied embedding only
    # for the b rows looked up
    weights = (param_count(c) - (0 if c["tie_word_embeddings"]
                                 else (v - b) * d)) * BF16
    return {"attn_flops": attn_f, "attn_bytes": kv_bytes + qo_bytes,
            "flops": forward_flops(c, b, attn_f, b),
            "bytes": float(weights) + kv_bytes}


def roofline_s(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tf = flops / peak["bf16_flops"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
