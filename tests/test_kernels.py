"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles, in interpret mode (CPU container; TPU is the target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.ssd import ssd_chunk_scan
from repro.models.attention import flash_attention_xla


def _qkv(key, b, sq, sk, h, kv, hd, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, sq, h, hd), dtype)
    k = jax.random.normal(k2, (b, sk, kv, hd), dtype)
    v = jax.random.normal(k3, (b, sk, kv, hd), dtype)
    return q, k, v


SWEEP = [
    # b, sq, sk, h, kv, hd, causal, window, dtype, tol
    (1, 128, 128, 4, 4, 64, True, None, jnp.float32, 2e-5),
    (2, 128, 128, 4, 2, 64, True, None, jnp.float32, 2e-5),   # GQA
    (1, 256, 256, 2, 1, 128, True, None, jnp.float32, 2e-5),  # MQA
    (1, 128, 128, 2, 2, 64, False, None, jnp.float32, 2e-5),
    (1, 256, 256, 2, 2, 64, True, 64, jnp.float32, 2e-5),     # SWA
    (1, 128, 128, 4, 4, 64, True, None, jnp.bfloat16, 3e-2),
    (1, 96, 96, 2, 2, 32, True, None, jnp.float32, 2e-5),     # ragged blocks
]


class TestFlashAttentionFwd:
    @pytest.mark.parametrize(
        "b,sq,sk,h,kv,hd,causal,window,dtype,tol", SWEEP)
    def test_matches_oracle(self, b, sq, sk, h, kv, hd, causal, window,
                            dtype, tol):
        q, k, v = _qkv(jax.random.PRNGKey(0), b, sq, sk, h, kv, hd, dtype)
        o_ref = ref.attention_ref(q, k, v, causal=causal, window=window)
        o, lse = flash_attention_fwd(q, k, v, causal=causal,
                                     window=window, interpret=True,
                                     block_q=64, block_k=64)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
            atol=tol, rtol=tol)

    def test_lse_correct(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, 64, 64, 2, 2, 32,
                       jnp.float32)
        _, lse = flash_attention_fwd(q, k, v, causal=True, interpret=True,
                                     block_q=32, block_k=32)
        # reference lse
        scale = 32 ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
        mask = jnp.tril(jnp.ones((64, 64), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        lse_ref = jax.nn.logsumexp(s, -1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   atol=1e-4, rtol=1e-4)


class TestFlashAttentionBwd:
    @pytest.mark.parametrize(
        "b,sq,sk,h,kv,hd,causal,window,dtype,tol",
        [s for s in SWEEP if s[8] == jnp.float32][:5])
    def test_grads_match_oracle(self, b, sq, sk, h, kv, hd, causal,
                                window, dtype, tol):
        q, k, v = _qkv(jax.random.PRNGKey(2), b, sq, sk, h, kv, hd, dtype)

        def f_pl(q, k, v):
            return jnp.sum(ops.flash_attention(q, k, v, causal, window)
                           * 0.01)

        def f_ref(q, k, v):
            return jnp.sum(ref.attention_ref(q, k, v, causal=causal,
                                             window=window) * 0.01)

        g_pl = jax.grad(f_pl, argnums=(0, 1, 2))(q, k, v)
        g_rf = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(g_pl, g_rf, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b_, np.float32),
                atol=5e-4, rtol=5e-4, err_msg=f"d{name}")


class TestSSDKernel:
    @pytest.mark.parametrize("b,s,h,p,n,chunk", [
        (1, 64, 2, 8, 16, 16),
        (2, 128, 3, 8, 16, 32),
        (1, 128, 1, 16, 8, 64),
        (2, 64, 4, 4, 4, 64),     # chunk == seq
    ])
    def test_matches_sequential_oracle(self, b, s, h, p, n, chunk):
        key = jax.random.PRNGKey(3)
        ks = jax.random.split(key, 4)
        xh = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
        al = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        bb = jax.random.normal(ks[2], (b, s, n)) * 0.3
        cc = jax.random.normal(ks[3], (b, s, n)) * 0.3
        y_ref, _ = ref.ssd_ref(xh, al, bb, cc)
        y = ssd_chunk_scan(xh, al, bb, cc, chunk=chunk, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-5, rtol=2e-5)


class TestXlaPathMatchesOracle:
    """The XLA chunked-attention path (used by the dry-run) must agree
    with the same oracle as the Pallas kernel."""

    @pytest.mark.parametrize("k_chunk", [32, 64, 1024])
    def test_chunk_invariance(self, k_chunk):
        q, k, v = _qkv(jax.random.PRNGKey(4), 2, 96, 96, 4, 2, 32,
                       jnp.float32)
        o_ref = ref.attention_ref(q, k, v, causal=True)
        o = flash_attention_xla(q, k, v, causal=True, k_chunk=k_chunk)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)

    def test_window(self):
        q, k, v = _qkv(jax.random.PRNGKey(5), 1, 128, 128, 2, 2, 32,
                       jnp.float32)
        o_ref = ref.attention_ref(q, k, v, causal=True, window=32)
        o = flash_attention_xla(q, k, v, causal=True, window=32,
                                k_chunk=64)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)


class TestOffsetAttention:
    """Chunked-prefill masking: a query chunk at absolute offset must
    reproduce the matching rows of the full-sequence oracle (this is the
    q_offset kwarg serve.py's prefill forwards — previously dropped on
    the pallas path)."""

    @pytest.mark.parametrize("off,cq,window", [
        (64, 64, None), (32, 96, None), (64, 64, 48), (96, 32, 16),
    ])
    def test_offset_chunk_matches_full(self, off, cq, window):
        S = off + cq
        q, k, v = _qkv(jax.random.PRNGKey(6), 2, S, S, 4, 2, 32,
                       jnp.float32)
        full = ref.attention_ref(q, k, v, causal=True, window=window)
        got = ops.flash_attention_offset(q[:, off:off + cq], k, v, off,
                                         causal=True, window=window)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(full[:, off:off + cq]),
            atol=2e-5, rtol=2e-5)

    def test_attention_dispatch_forwards_offset(self):
        """attention(impl='pallas', q_offset=...) must honor the offset,
        including a *traced* offset under jit (serve passes
        positions[0, 0])."""
        from repro.models.attention import attention
        off, cq = 64, 64
        S = off + cq
        q, k, v = _qkv(jax.random.PRNGKey(7), 1, S, S, 2, 2, 32,
                       jnp.float32)
        full = ref.attention_ref(q, k, v, causal=True)
        want = np.asarray(full[:, off:off + cq])
        got = attention(q[:, off:off + cq], k, v, causal=True,
                        impl="pallas", q_offset=off)
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=2e-5, rtol=2e-5)
        jitted = jax.jit(lambda qc, kk, vv, o: attention(
            qc, kk, vv, causal=True, impl="pallas", q_offset=o))
        got_t = jitted(q[:, off:off + cq], k, v, jnp.int32(off))
        np.testing.assert_allclose(np.asarray(got_t), want,
                                   atol=2e-5, rtol=2e-5)

    def test_zero_offset_matches_plain_kernel(self):
        q, k, v = _qkv(jax.random.PRNGKey(8), 1, 128, 128, 2, 2, 32,
                       jnp.float32)
        a = ops.flash_attention_offset(q, k, v, 0, causal=True)
        b = ops.flash_attention(q, k, v, True, None, None)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)

    def test_unknown_kwarg_raises(self):
        from repro.models.attention import attention
        q, k, v = _qkv(jax.random.PRNGKey(9), 1, 64, 64, 2, 2, 32,
                       jnp.float32)
        with pytest.raises(TypeError, match="unsupported"):
            attention(q, k, v, impl="pallas", bogus=1)


class TestGQAParity:
    """GQA/MQA head mapping: pallas kernels vs the XLA path the dry-run
    executes, plus the loud divisibility check."""

    @pytest.mark.parametrize("h,kv", [(4, 2), (8, 1), (6, 3)])
    def test_fwd_matches_xla(self, h, kv):
        q, k, v = _qkv(jax.random.PRNGKey(10), 2, 128, 128, h, kv, 32,
                       jnp.float32)
        o_x = flash_attention_xla(q, k, v, causal=True)
        o_p, _ = flash_attention_fwd(q, k, v, causal=True,
                                     interpret=True, block_q=64,
                                     block_k=64)
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x),
                                   atol=2e-5, rtol=2e-5)

    def test_indivisible_heads_raise(self):
        q, k, v = _qkv(jax.random.PRNGKey(11), 1, 64, 64, 4, 3, 32,
                       jnp.float32)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention_fwd(q, k, v, interpret=True)
        with pytest.raises(ValueError, match="divisible"):
            jax.grad(lambda *a: jnp.sum(
                ops.flash_attention(*a, True, None, None)))(q, k, v)

    def test_decode_indivisible_heads_raise(self):
        key = jax.random.PRNGKey(12)
        q = jax.random.normal(key, (2, 4, 32))
        kc = jax.random.normal(key, (1, 2, 64, 3, 32))
        lengths = jnp.full((2,), 16, jnp.int32)
        with pytest.raises(ValueError, match="divisible"):
            ops.flash_attention_decode(q, kc, kc, lengths, 0)


class TestDecodeKernel:
    """Fused decode kernel vs the XLA attend_cache path (the serving
    engine's slot semantics: per-slot lengths, optional window)."""

    @pytest.mark.parametrize("h,kv,window", [
        (4, 2, None), (4, 4, None), (8, 2, 16), (2, 1, 24),
    ])
    def test_matches_attend_cache(self, h, kv, window):
        """The kernel reads layer ``l`` of a stacked [L, B, S, KV, hd]
        cache in place; each layer must equal attend_cache on that
        layer's slice.  A row of length 0 reads nothing and comes out
        zero (attend_cache has no defined answer there: an all-masked
        softmax averages every position)."""
        from repro.models.attention import attend_cache
        n_layers, b, S, hd = 3, 5, 96, 32
        key = jax.random.PRNGKey(13)
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (b, h, hd))
        kc = jax.random.normal(k2, (n_layers, b, S, kv, hd))
        vc = jax.random.normal(k3, (n_layers, b, S, kv, hd))
        lengths = jnp.array([1, 17, 0, 64, 96], jnp.int32)
        live = np.asarray(lengths) > 0
        for layer in range(n_layers):
            o_x = attend_cache(q, kc[layer], vc[layer], lengths,
                               window=window, impl="xla")
            o_p = ops.flash_attention_decode(q, kc, vc, lengths,
                                             jnp.int32(layer),
                                             window=window)
            np.testing.assert_allclose(np.asarray(o_p)[live],
                                       np.asarray(o_x)[live],
                                       atol=2e-5, rtol=2e-5)
            assert not np.asarray(o_p)[~live].any()
            o_s = attend_cache(q, kc, vc, lengths, layer=layer,
                               window=window, impl="xla")
            np.testing.assert_array_equal(np.asarray(o_s),
                                          np.asarray(o_x))

    def test_attend_cache_pallas_dispatch(self):
        from repro.models.attention import attend_cache
        b, S, h, kv, hd = 2, 64, 4, 2, 32
        key = jax.random.PRNGKey(14)
        q = jax.random.normal(key, (b, h, hd))
        kc = jax.random.normal(key, (2, b, S, kv, hd))
        vc = jax.random.normal(key, (2, b, S, kv, hd))
        lengths = jnp.array([5, 33], jnp.int32)
        o_x = attend_cache(q, kc, vc, lengths, layer=1, impl="xla")
        o_p = attend_cache(q, kc, vc, lengths, layer=1, impl="pallas")
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x),
                                   atol=2e-5, rtol=2e-5)


class TestPagedDecodeKernel:
    """Scalar-prefetched paged decode kernel vs the XLA gather path
    (pool blocks materialized through the table, then attend_cache)."""

    @pytest.mark.parametrize("h,kv,bl,mb", [
        (4, 2, 16, 4), (4, 4, 8, 6), (2, 1, 32, 2),
    ])
    def test_matches_xla_gather(self, h, kv, bl, mb):
        from repro.models.attention import attend_paged
        b, hd = 4, 32
        nb = mb * b + 1
        key = jax.random.PRNGKey(21)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        q = jax.random.normal(k1, (b, h, hd))
        k_pool = jax.random.normal(k2, (nb, bl, kv, hd))
        v_pool = jax.random.normal(k3, (nb, bl, kv, hd))
        # each slot owns a random disjoint slice of the pool (block 0
        # is the reserved null sink for unowned table tail entries)
        perm = np.asarray(jax.random.permutation(k4, nb - 1)) + 1
        table = np.zeros((b, mb), np.int32)
        lengths = np.asarray([1, bl, bl + 3, mb * bl], np.int32)[:b]
        for s in range(b):
            n_owned = int(-(-int(lengths[s]) // bl))
            table[s, :n_owned] = perm[s * mb:s * mb + n_owned]
        o_x = attend_paged(q, k_pool, v_pool, jnp.asarray(table),
                           jnp.asarray(lengths), impl="xla")
        o_p = ops.flash_attention_paged_decode(q, k_pool, v_pool,
                                               jnp.asarray(table),
                                               jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x),
                                   atol=2e-5, rtol=2e-5)

    def test_null_block_garbage_cannot_leak(self):
        """Entries past ``length`` route to block 0; poisoning it (and
        every unowned block) with huge values must not move the
        output."""
        from repro.models.attention import attend_paged
        b, h, kv, hd, bl, mb, nb = 2, 4, 2, 32, 8, 4, 9
        key = jax.random.PRNGKey(22)
        q = jax.random.normal(key, (b, h, hd))
        k_pool = jax.random.normal(key, (nb, bl, kv, hd))
        v_pool = jax.random.normal(key, (nb, bl, kv, hd))
        table = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
        lengths = jnp.asarray([11, 8], jnp.int32)
        clean = ops.flash_attention_paged_decode(q, k_pool, v_pool,
                                                 table, lengths)
        owned = {1, 2, 3}
        poison = np.array(k_pool)
        for blk in range(nb):
            if blk not in owned:
                poison[blk] = 1e9
        dirty = ops.flash_attention_paged_decode(
            q, jnp.asarray(poison), v_pool, table, lengths)
        np.testing.assert_allclose(np.asarray(dirty), np.asarray(clean),
                                   atol=2e-5, rtol=2e-5)
        ref = attend_paged(q, jnp.asarray(poison), v_pool, table,
                           lengths, impl="xla")
        np.testing.assert_allclose(np.asarray(dirty), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestSSDVjp:
    """Pallas SSD forward with the exact XLA-scan VJP: values AND grads
    must match the XLA path bit-for-tolerance (train/engine.py routes
    the microbatch step through this for ssd/hybrid families)."""

    @pytest.mark.parametrize("b,s,h,p,n,chunk", [
        (1, 64, 2, 8, 16, 32),
        (2, 96, 1, 8, 8, 64),      # padded: 96 % 64 != 0
    ])
    def test_values_and_grads_match_xla(self, b, s, h, p, n, chunk):
        from repro.models.mamba import _ssd_dispatch
        key = jax.random.PRNGKey(15)
        ks = jax.random.split(key, 4)
        xh = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
        al = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        bb = jax.random.normal(ks[2], (b, s, n)) * 0.3
        cc = jax.random.normal(ks[3], (b, s, n)) * 0.3

        def loss(impl):
            def f(xh, al, bb, cc):
                y = _ssd_dispatch(xh, al, bb, cc, chunk, impl)
                return jnp.sum(y * 0.01)
            return f

        y_x = _ssd_dispatch(xh, al, bb, cc, chunk, "xla")
        y_p = _ssd_dispatch(xh, al, bb, cc, chunk, "pallas")
        np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x),
                                   atol=2e-5, rtol=2e-5)
        g_x = jax.grad(loss("xla"), argnums=(0, 1, 2, 3))(xh, al, bb, cc)
        g_p = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3))(xh, al, bb,
                                                             cc)
        for a, b_, name in zip(g_p, g_x, ("xh", "a_log", "bb", "cc")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5,
                err_msg=f"d{name}")


class TestInterpretOverride:
    """REPRO_PALLAS_INTERPRET overrides backend autodetection; the
    resolution is cached (previously re-evaluated on every kernel
    call)."""

    def test_env_override(self, monkeypatch):
        from repro.kernels.ops import _default_interpret
        try:
            monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
            _default_interpret.cache_clear()
            assert _default_interpret() is False
            monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "true")
            _default_interpret.cache_clear()
            assert _default_interpret() is True
            monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
            _default_interpret.cache_clear()
            # no env: CPU container -> interpret
            assert _default_interpret() is (
                jax.default_backend() != "tpu")
        finally:
            _default_interpret.cache_clear()

    def test_resolution_is_cached(self, monkeypatch):
        from repro.kernels.ops import _default_interpret
        try:
            _default_interpret.cache_clear()
            first = _default_interpret()
            # flipping the env without cache_clear must NOT change the
            # resolved value (one os.environ read per process)
            monkeypatch.setenv("REPRO_PALLAS_INTERPRET",
                               "0" if first else "1")
            assert _default_interpret() is first
        finally:
            _default_interpret.cache_clear()

    def test_interpret_refused_on_tpu(self, monkeypatch):
        """On a TPU backend the kernels run compiled: asking for the
        interpreter is an error, not a silent slow path."""
        from repro.kernels import ops
        try:
            monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
            monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
            ops._default_interpret.cache_clear()
            with pytest.raises(RuntimeError, match="TPU"):
                ops._default_interpret()
            monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
            ops._default_interpret.cache_clear()
            assert ops._default_interpret() is False
        finally:
            ops._default_interpret.cache_clear()
