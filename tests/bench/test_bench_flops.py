"""Operation and byte counts of bench/flops.py against hand counts at
qwen2-1.5b widths, and the peak table."""
import json
import os

import pytest

from conftest import REPO

from bench import flops, peaks


def _cfg(name):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)["config"]


def test_kv_bytes_per_token_qwen2_1p5b():
    # 28 layers x (k, v) x 2 kv heads x 128 x 2 bytes
    assert flops.kv_bytes_per_token(_cfg("qwen2-1.5b")) == 28_672


def test_param_count_8_layers():
    c = _cfg("qwen2-1.5b-8l")
    per_layer = (1536 * 1536 * 2 + 2 * 1536 * 256 + 3 * 1536 * 8960
                 + (12 + 2 * 2) * 128 + 2 * 1536)
    assert per_layer == 46_797_824
    assert flops.param_count(c) == 8 * per_layer + 151_936 * 1536 + 1536
    assert round(flops.param_count(c) / 1e6) == 608


def test_param_count_qwen2p5_32b_8_layers():
    c = _cfg("qwen2.5-32b-8l")
    # 8 x 487.6 M + embedding and untied head of 778.6 M each
    assert flops.param_count(c) == pytest.approx(5.457e9, rel=1e-3)


def test_train_flops_per_token_hand_count():
    c = _cfg("qwen2-1.5b-8l")
    matmul = 8 * (46_792_704) + 151_936 * 1536
    attn = 4 * 12 * 128 * (4096 + 1) / 2 * 8
    assert flops.train_flops_per_token(c, 4096) == pytest.approx(
        3 * (2 * matmul + attn))
    assert flops.train_flops_per_token(c, 4096) == pytest.approx(
        3.948e9, rel=1e-3)


def test_causal_span_matches_position_sum():
    c = _cfg("qwen2-1.5b")
    assert flops.causal_attn_flops_span(c, 512, 256) == \
        flops.attn_flops(c, range(512, 768))


def test_prefill_chunk_counts_valid_tokens_only():
    c = _cfg("qwen2-1.5b")
    w = flops.prefill_chunk_work(c, 256, 100)
    # q and o for 100 tokens x 12 heads, k and v for 356 tokens x 2 heads
    per_layer = 2 * 128 * (2 * 100 * 12 + 2 * 356 * 2)
    assert w["attn_bytes"] == 28 * per_layer
    assert w["attn_flops"] == 28 * 4 * 12 * 128 * sum(
        p + 1 for p in range(256, 356))


def test_decode_step_reads_valid_cache_not_capacity():
    c = _cfg("qwen2-1.5b")
    w = flops.decode_step_work(c, [10, 1000])
    assert w["bytes"] - flops.param_count(c) * 2 == 1010 * 28_672
    assert w["attn_flops"] == 28 * 4 * 12 * 128 * 1010


def test_roofline_names_the_bound():
    pk = peaks.peak("TPU v5 lite")
    assert flops.roofline_s(197e12, 1.0, pk) == (1.0, "flops")
    assert flops.roofline_s(1.0, 819e9, pk) == (1.0, "bytes")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v9 imaginary")
