"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, ops Mosaic has no
lowering for, scratch beyond VMEM.  These tests compile each kernel at
the published head widths of the configs that route to it, for a
described (not attached) v5e chip, and check that the compiled program
really holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels.ssd import ssd_chunk_scan

# (config, q heads, kv heads, head_dim, sliding window) at published widths
HEADS = [
    pytest.param(12, 2, 128, None, id="qwen2-1.5b"),
    pytest.param(32, 32, 80, None, id="zamba2-2.7b"),
    pytest.param(32, 8, 120, 1024, id="h2o-danube-3-4b"),
]
SEQ = 2048
SLOTS = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out of the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("h,kv,hd,window", HEADS)
def test_flash_forward(one_chip, h, kv, hd, window):
    q = _spec(one_chip, (2, SEQ, h, hd))
    k = _spec(one_chip, (2, SEQ, kv, hd))
    txt = _compile_text(
        lambda q, k, v: fa.flash_attention_fwd(q, k, v, window=window),
        q, k, k)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("h,kv,hd,window", HEADS)
def test_flash_offset_prefill(one_chip, h, kv, hd, window):
    q = _spec(one_chip, (1, 256, h, hd))
    k = _spec(one_chip, (1, SEQ, kv, hd))
    off = _spec(one_chip, (), jnp.int32)
    txt = _compile_text(
        lambda q, k, v, o: fa.flash_attention_fwd(q, k, v, window=window,
                                                  q_offset=o),
        q, k, k, off)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("h,kv,hd,window", HEADS)
def test_flash_backward(one_chip, h, kv, hd, window):
    q = _spec(one_chip, (2, SEQ, h, hd))
    k = _spec(one_chip, (2, SEQ, kv, hd))
    lse = _spec(one_chip, (2, h, SEQ), jnp.float32)
    txt = _compile_text(
        lambda q, k, v, o, lse, do: fa.flash_attention_bwd(
            q, k, v, o, lse, do, window=window),
        q, k, k, q, lse, q)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("h,kv,hd,window", HEADS)
def test_decode(one_chip, h, kv, hd, window):
    q = _spec(one_chip, (SLOTS, h, hd))
    cache = _spec(one_chip, (SLOTS, SEQ, kv, hd))
    lengths = _spec(one_chip, (SLOTS,), jnp.int32)
    txt = _compile_text(
        lambda q, kc, vc, n: fa.flash_attention_decode(q, kc, vc, n,
                                                       window=window),
        q, cache, cache, lengths)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("h,kv,hd,window", HEADS)
def test_paged_decode(one_chip, h, kv, hd, window):
    block_len, n_blocks = 16, 256
    q = _spec(one_chip, (SLOTS, h, hd))
    pool = _spec(one_chip, (n_blocks, block_len, kv, hd))
    table = _spec(one_chip, (SLOTS, SEQ // block_len), jnp.int32)
    lengths = _spec(one_chip, (SLOTS,), jnp.int32)
    txt = _compile_text(fa.flash_attention_paged_decode,
                        q, pool, pool, table, lengths)
    assert "tpu_custom_call" in txt


def test_ssd_chunk_scan(one_chip):
    # zamba2-2.7b: d_inner 5120 = 80 SSM heads x 64 channels, state 64,
    # chunk 256 (configs/zamba2_2p7b.py)
    b, s, h, p, n = 2, SEQ, 80, 64, 64
    f32 = jnp.float32
    txt = _compile_text(
        lambda x, a, bb, cc: ssd_chunk_scan(x, a, bb, cc, chunk=256),
        _spec(one_chip, (b, s, h, p), f32), _spec(one_chip, (b, s, h), f32),
        _spec(one_chip, (b, s, n), f32), _spec(one_chip, (b, s, n), f32))
    assert "tpu_custom_call" in txt
