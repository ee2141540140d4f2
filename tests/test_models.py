"""Per-architecture smoke tests: reduced config of the same family, one
forward + one train-grad + one decode step on CPU; shapes + finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_arch
from repro.models.model import LM

B, S = 2, 16


def _batch(cfg, key):
    if cfg.embed_stub:
        emb = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
        labels = jax.random.randint(key, (B, S), 0, cfg.vocab)
        return {"embeds": emb, "labels": labels}
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ASSIGNED)
class TestArchSmoke:
    def test_forward_and_grad(self, arch, key):
        cfg = get_arch(arch).reduced()
        m = LM(cfg)
        params = m.init(key)
        batch = _batch(cfg, key)
        logits, aux = jax.jit(m.forward)(
            params, batch.get("tokens"), batch.get("embeds"))
        assert logits.shape == (B, S, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
        loss, grads = jax.value_and_grad(m.loss)(params, batch)
        assert np.isfinite(float(loss))
        gleaves = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                   for g in gleaves)
        # at least one non-zero gradient
        assert any(float(jnp.max(jnp.abs(g.astype(jnp.float32)))) > 0
                   for g in gleaves)

    def test_decode_steps(self, arch, key):
        cfg = get_arch(arch).reduced()
        m = LM(cfg)
        params = m.init(key)
        cache = m.init_cache(B, 32)
        step = jax.jit(m.decode_step)
        tok = (jax.random.normal(key, (B, cfg.d_model), jnp.float32)
               if cfg.embed_stub
               else jnp.zeros((B,), jnp.int32))
        for i in range(3):
            logits, cache = step(params, cache, tok)
            assert logits.shape == (B, cfg.vocab)
            assert bool(jnp.all(jnp.isfinite(
                logits.astype(jnp.float32)))), f"step {i}"
        assert int(cache["pos"][0]) == 3


class TestDecodePrefillConsistency:
    """Decoding token-by-token must match the parallel forward pass
    (validates KV caches, SSM decode recurrences, xLSTM steps)."""

    @pytest.mark.parametrize("arch", ["llama3.2-3b", "zamba2-2.7b",
                                      "xlstm-125m", "qwen2-1.5b",
                                      "moonshot-v1-16b-a3b",
                                      "h2o-danube-3-4b"])
    def test_stepwise_matches_forward(self, arch, key):
        cfg = get_arch(arch).reduced()
        m = LM(cfg)
        params = m.init(key)
        toks = jax.random.randint(key, (B, 8), 0, cfg.vocab)
        full_logits, _ = m.forward(params, toks)
        cache = m.init_cache(B, 16)
        step = jax.jit(m.decode_step)
        outs = []
        for i in range(8):
            lg, cache = step(params, cache, toks[:, i])
            outs.append(lg)
        stepwise = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(stepwise, np.float32),
            np.asarray(full_logits, np.float32), rtol=0.15, atol=0.15)


class TestConfigExactness:
    """The registry carries the exact published configs."""

    def test_assigned_complete(self):
        assert len(ASSIGNED) == 10

    @pytest.mark.parametrize("arch,expect", [
        ("zamba2-2.7b", dict(n_layers=54, d_model=2560, n_heads=32,
                             d_ff=10240, vocab=32000)),
        ("qwen2.5-32b", dict(n_layers=64, d_model=5120, n_heads=40,
                             n_kv_heads=8, d_ff=27648, vocab=152064,
                             qkv_bias=True)),
        ("qwen2-1.5b", dict(n_layers=28, d_model=1536, n_heads=12,
                            n_kv_heads=2, d_ff=8960, vocab=151936)),
        ("h2o-danube-3-4b", dict(n_layers=24, d_model=3840, n_heads=32,
                                 n_kv_heads=8, d_ff=10240, vocab=32000)),
        ("llama3.2-3b", dict(n_layers=28, d_model=3072, n_heads=24,
                             n_kv_heads=8, d_ff=8192, vocab=128256)),
        ("moonshot-v1-16b-a3b", dict(n_layers=48, d_model=2048,
                                     n_heads=16, vocab=163840)),
        ("phi3.5-moe-42b-a6.6b", dict(n_layers=32, d_model=4096,
                                      n_heads=32, n_kv_heads=8,
                                      vocab=32064)),
        ("internvl2-76b", dict(n_layers=80, d_model=8192, n_heads=64,
                               n_kv_heads=8, d_ff=28672, vocab=128256)),
        ("xlstm-125m", dict(n_layers=12, d_model=768, n_heads=4,
                            d_ff=0, vocab=50304)),
        ("musicgen-large", dict(n_layers=48, d_model=2048, n_heads=32,
                                d_ff=8192, vocab=2048)),
    ])
    def test_exact_config(self, arch, expect):
        cfg = get_arch(arch)
        for k, v in expect.items():
            assert getattr(cfg, k) == v, (arch, k)

    def test_moe_configs(self):
        m = get_arch("moonshot-v1-16b-a3b").moe
        assert (m.n_experts, m.top_k, m.d_ff_expert) == (64, 6, 1408)
        p = get_arch("phi3.5-moe-42b-a6.6b").moe
        assert (p.n_experts, p.top_k, p.d_ff_expert) == (16, 2, 6400)

    def test_param_counts_near_published(self):
        # name-plate sizes within tolerance (embeddings/frontends differ)
        approx = {"qwen2.5-32b": 32.8e9, "llama3.2-3b": 3.2e9,
                  "zamba2-2.7b": 2.4e9, "xlstm-125m": 0.125e9,
                  "qwen2-1.5b": 1.5e9}
        for a, n in approx.items():
            assert get_arch(a).param_count() == pytest.approx(n, rel=0.25)

    def test_active_params_moe(self):
        assert get_arch("moonshot-v1-16b-a3b").active_param_count() \
            == pytest.approx(3.97e9, rel=0.2)
        assert get_arch("phi3.5-moe-42b-a6.6b").active_param_count() \
            == pytest.approx(6.6e9, rel=0.2)


def _kv_caches(cfg, cache):
    """The attention KV leaves of a decode cache: the dense families'
    stacked "kv", the hybrid's shared-block "shared"."""
    return cache["shared"] if cfg.family == "hybrid" else cache["kv"]


def _scans(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "scan":
            yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _scans(sub)


# dense, ring-buffer SWA, MoE, and the hybrid's shared attention block
KV_ARCHS = ["qwen2-1.5b", "h2o-danube-3-4b", "moonshot-v1-16b-a3b",
            "zamba2-2.7b"]


class TestDecodeCacheInPlace:
    """The decode step carries the stacked KV cache through its layer
    scan: nothing slices a layer out of it, restacks it or copies it."""

    @pytest.mark.parametrize("arch", KV_ARCHS)
    def test_cache_rides_the_carry(self, arch, key):
        cfg = get_arch(arch).reduced()
        m = LM(cfg)
        params = m.init(key)
        cache = m.init_cache(B, 32)
        kv = _kv_caches(cfg, cache)
        stacked = kv["k"].shape
        per_layer = stacked[1:]
        jaxpr = jax.make_jaxpr(m.decode_step)(
            params, cache, jnp.zeros((B,), jnp.int32),
            jnp.array([True, False])).jaxpr
        scans = list(_scans(jaxpr))
        assert scans
        carried = 0
        for e in scans:
            nc, nk = e.params["num_carry"], e.params["num_consts"]
            xs = e.invars[nk + nc:]
            ys = e.outvars[nc:]
            body_ys = e.params["jaxpr"].jaxpr.outvars[nc:]
            assert all(v.aval.shape not in (stacked, per_layer)
                       for v in list(xs) + list(ys) + list(body_ys)), arch
            carried += sum(v.aval.shape == stacked
                           for v in e.outvars[:nc])
        assert carried >= 2                         # k and v

    @pytest.mark.parametrize("arch", KV_ARCHS)
    def test_scan_and_unrolled_bit_equal(self, arch, key):
        # float32 weights (the cache stays bf16): XLA's CPU backend
        # rounds bf16 intermediates differently in a while body than in
        # straight-line code, which would hide what this compares — the
        # two loop forms threading the same carried cache
        cfg = dataclasses.replace(get_arch(arch).reduced(), dtype="float32")
        scan = LM(cfg)
        unrolled = LM(cfg, layer_loop="unrolled")
        params = scan.init(key)
        toks = jax.random.randint(key, (B, 5), 0, cfg.vocab)
        active = jnp.array([True, False])
        steps = [jax.jit(scan.decode_step), jax.jit(unrolled.decode_step)]
        caches = [scan.init_cache(B, 32), unrolled.init_cache(B, 32)]
        for i in range(toks.shape[1]):
            (la, caches[0]), (lb, caches[1]) = (
                step(params, c, toks[:, i], active if i % 2 else None)
                for step, c in zip(steps, caches))
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        for a, b in zip(jax.tree_util.tree_leaves(caches[0]),
                        jax.tree_util.tree_leaves(caches[1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("arch", KV_ARCHS)
    def test_inactive_row_untouched(self, arch, key):
        cfg = get_arch(arch).reduced()
        m = LM(cfg)
        params = m.init(key)
        cache = m.init_cache(B, 32)
        name = "shared" if cfg.family == "hybrid" else "kv"
        # a row full of noise, at a position inside the cache
        cache[name] = jax.tree_util.tree_map(
            lambda a: jax.random.normal(key, a.shape).astype(a.dtype),
            cache[name])
        cache["pos"] = jnp.array([5, 7], jnp.int32)
        before = jax.tree_util.tree_map(np.asarray, cache[name])
        _, new = jax.jit(m.decode_step)(
            params, cache, jnp.array([3, 4], jnp.int32),
            jnp.array([True, False]))
        for leaf in ("k", "v"):
            got = np.asarray(new[name][leaf])
            np.testing.assert_array_equal(got[:, 1], before[leaf][:, 1])
            # the active row wrote its token at its position, only there
            changed = (got[:, 0] != before[leaf][:, 0]).any(axis=(0, 2, 3))
            assert changed.nonzero()[0].tolist() == [5]
        assert np.asarray(new["pos"]).tolist() == [6, 7]
