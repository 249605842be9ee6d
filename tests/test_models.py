"""Model-level equivalence tests: the paper's optimized execution forms must
match their naive counterparts exactly."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, smoke
from repro.models import decode_step, forward, init_params, prefill
from repro.models import mla as mla_mod
from repro.models.mamba2 import ssd_chunked, ssd_reference


def test_mla_absorbed_equals_naive():
    """Absorbed decode (compressed-latent attention) == unabsorbed MHA form
    at the final position — the weight-absorption identity of §4.2.2."""
    cfg = smoke("deepseek-r1")
    p1 = mla_mod.init_mla_params(jax.random.PRNGKey(0), cfg, 1, jnp.float32)
    p = jax.tree.map(lambda a: a[0], p1)
    b, s = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model))
    full_out, latent = mla_mod.mla_prefill(p, x, cfg)
    # decode the last token against the cache of the first s-1
    cache = jnp.zeros((b, s + 4, latent.shape[-1]))
    cache = cache.at[:, : s - 1].set(latent[:, : s - 1])
    out_dec, _ = mla_mod.mla_decode(p, x[:, s - 1:], cache[None],
                                    jnp.int32(s - 1), cfg, 0, jnp.int32(s - 1))
    np.testing.assert_allclose(np.asarray(out_dec[:, 0]),
                               np.asarray(full_out[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_ssd_chunked_equals_reference():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, s, h, p, n = 2, 96, 3, 8, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.random.uniform(ks[1], (b, s, h), minval=0.01, maxval=0.2)
    alog = jax.random.normal(ks[2], (h,)) * 0.2
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    for chunk in (8, 32, 96):
        y1, h1 = ssd_chunked(x, dt, alog, bm, cm, chunk)
        y2, h2 = ssd_reference(x, dt, alog, bm, cm)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("arch", ["qwen3-8b", "olmoe-1b-7b", "mamba2-780m",
                                  "zamba2-1.2b", "deepseek-r1", "deepseek-v3"])
def test_decode_continuation_matches_forward(arch):
    """prefill(s tokens) + n decode_steps == forward(s+n tokens) logits."""
    # generous expert capacity: token drops depend on total token count and
    # would (legitimately) differ between prefill and full forward.
    cfg = dataclasses.replace(smoke(arch), capacity_factor=16.0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, s, n = 2, 16, 4
    batch = make_batch(cfg, b, s + n)
    toks = batch["tokens"]
    logits_full, _ = forward(params, cfg, {"tokens": toks})
    pl, caches = prefill(params, cfg, {"tokens": toks[:, :s]},
                         capacity=s + n + 4, cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(pl), np.asarray(logits_full[:, :s]),
                               rtol=5e-3, atol=5e-3)
    cl = jnp.int32(s)
    for i in range(n):
        dl, caches = decode_step(params, cfg, toks[:, s + i: s + i + 1],
                                 caches, cl)
        np.testing.assert_allclose(
            np.asarray(dl), np.asarray(logits_full[:, s + i]),
            rtol=5e-3, atol=5e-3,
            err_msg=f"{arch}: decode step {i} diverges from forward")
        cl = cl + 1


def test_sliding_window_ring_decode():
    """Ring-buffer decode (window < sequence) matches windowed full forward."""
    cfg = dataclasses.replace(smoke("granite-3-2b"), sliding_window=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, total = 1, 24
    toks = jax.random.randint(jax.random.PRNGKey(5), (b, total), 0,
                              cfg.vocab_size)
    logits_full, _ = forward(params, cfg, {"tokens": toks})
    # prefill 16 (> window 8) then decode the rest through the ring cache
    s = 16
    _, caches = prefill(params, cfg, {"tokens": toks[:, :s]},
                        capacity=total, cache_dtype=jnp.float32)
    cl = jnp.int32(s)
    for i in range(total - s - 1):
        dl, caches = decode_step(params, cfg, toks[:, s + i: s + i + 1],
                                 caches, cl)
        np.testing.assert_allclose(
            np.asarray(dl), np.asarray(logits_full[:, s + i]),
            rtol=5e-3, atol=5e-3, err_msg=f"ring decode step {i}")
        cl = cl + 1


def test_vector_cache_len_equivalence():
    """Per-request (B,) cache_len gives identical results to scalar when all
    requests are aligned (the MTP-aware masking path, §4.2.2-(3))."""
    cfg = smoke("qwen3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, s = 3, 12
    batch = make_batch(cfg, b, s)
    _, caches1 = prefill(params, cfg, {"tokens": batch["tokens"]},
                         capacity=s + 4, cache_dtype=jnp.float32)
    caches2 = jax.tree.map(lambda x: x, caches1)
    tok = jnp.ones((b, 1), jnp.int32)
    d1, _ = decode_step(params, cfg, tok, caches1, jnp.int32(s))
    d2, _ = decode_step(params, cfg, tok, caches2,
                        jnp.full((b,), s, jnp.int32))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5)
