"""Property-based tests (hypothesis) on system invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import example, given, settings, strategies as st  # noqa: E402

from repro.configs import get_config, smoke_variant
from repro.mempool.pool import MemoryPool, MPController
from repro.models import moe as moe_mod
from repro.serving.transfer import connection_map, transfer_balance

SET = settings(max_examples=20, deadline=None)


# ---------------------------------------------------------------------------
# MoE dispatch/combine invariants
# ---------------------------------------------------------------------------


@SET
@given(t=st.integers(4, 48), e=st.sampled_from([4, 8, 16]),
       k=st.integers(1, 4), seed=st.integers(0, 100))
def test_dispatch_indices_conservation(t, e, k, seed):
    """Every (token, expert) assignment gets a unique in-capacity slot when
    capacity is sufficient; no slot collisions (paper Eq. 1-2 buffers)."""
    key = jax.random.PRNGKey(seed)
    top_i = jax.random.randint(key, (t, k), 0, e)
    cap = t * k  # generous: nothing dropped
    slot, valid = moe_mod.dispatch_indices(top_i, e, cap)
    assert bool(jnp.all(valid))
    pairs = set()
    ti, si = np.asarray(top_i).reshape(-1), np.asarray(slot).reshape(-1)
    for eid, s in zip(ti, si):
        assert (eid, s) not in pairs, "slot collision"
        pairs.add((eid, s))
    # slots are dense per expert: 0..count-1
    for eid in range(e):
        slots = sorted(s for x, s in pairs if x == eid)
        assert slots == list(range(len(slots)))


@SET
@given(t=st.integers(4, 32), seed=st.integers(0, 50))
def test_moe_capacity_matches_reference(t, seed):
    """Static-buffer gather/scatter == dense all-experts oracle when nothing
    is dropped (token conservation through dispatch+combine)."""
    cfg = dataclasses.replace(smoke_variant(get_config("olmoe-1b-7b")),
                              capacity_factor=16.0)
    p1 = moe_mod.init_moe_params(jax.random.PRNGKey(0), cfg, 1, jnp.float32)
    p = jax.tree.map(lambda a: a[0], p1)
    x = jax.random.normal(jax.random.PRNGKey(seed), (t, cfg.d_model))
    ref, _ = moe_mod.moe_reference(p, x, cfg)
    out, aux = moe_mod.moe_capacity(p, x, cfg)
    assert int(aux["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@SET
@given(seed=st.integers(0, 50))
def test_router_renormalized(seed):
    cfg = smoke_variant(get_config("olmoe-1b-7b"))
    w = jax.random.normal(jax.random.PRNGKey(seed),
                          (cfg.d_model, cfg.num_experts))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (8, cfg.d_model))
    top_i, top_p, aux = moe_mod.route(w, x, cfg)
    np.testing.assert_allclose(np.asarray(jnp.sum(top_p, -1)), 1.0, rtol=1e-5)
    assert float(aux) >= 1.0 - 1e-5  # load-balance loss lower bound E·Σf·P ≥ 1


# ---------------------------------------------------------------------------
# Quantization invariants
# ---------------------------------------------------------------------------


@SET
@given(t=st.integers(1, 32), d=st.sampled_from([16, 64, 256]),
       scale=st.floats(0.01, 100.0), seed=st.integers(0, 50))
@example(t=32, d=256, scale=100.0, seed=24)
def test_per_token_quant_error_bound(t, d, scale, seed):
    """Dequantisation is off by at most half a step, plus the float32
    rounding of x/s and q*s, which is relative to the values."""
    from repro.quant import quantize_act_per_token
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (t, d))
                   * scale)
    q, s = quantize_act_per_token(x)
    deq = np.asarray(q, np.float32) * np.asarray(s)
    err = np.abs(deq - x)
    rounding = 4 * np.finfo(np.float32).eps * np.abs(x)
    assert (err <= np.asarray(s) * 0.5 + rounding).all()
    assert (np.abs(np.asarray(q)) <= 127).all()


@SET
@given(seed=st.integers(0, 30))
def test_equalization_preserves_function(seed):
    """x/s @ (s·w) == x @ w exactly (the structural transformation is
    function-preserving before quantization, §4.5)."""
    from repro.quant import equalization_scales
    w = jax.random.normal(jax.random.PRNGKey(seed), (32, 16))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (8, 32))
    s = equalization_scales(w, x)
    ref = x @ w
    out = (x / s[None, :]) @ (w * s[:, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Disaggregated pool invariants
# ---------------------------------------------------------------------------


@SET
@given(n_keys=st.integers(50, 300), seed=st.integers(0, 20))
def test_consistent_hash_stability_and_spread(n_keys, seed):
    ctrl = MPController(n_nodes=8)
    rng = np.random.RandomState(seed)
    keys = [f"key{rng.randint(1 << 30)}:{i}" for i in range(n_keys)]
    locs = [ctrl.locate(k) for k in keys]
    # stability: same key -> same node
    assert locs == [ctrl.locate(k) for k in keys]
    # spread: no node owns everything
    counts = np.bincount(locs, minlength=8)
    assert counts.max() < n_keys  # not degenerate
    assert (counts > 0).sum() >= 4  # most nodes participate


@SET
@given(seed=st.integers(0, 20))
def test_pool_put_get_roundtrip(seed):
    pool = MemoryPool(n_nodes=4)
    rng = np.random.RandomState(seed)
    blobs = {f"k{i}": rng.randn(rng.randint(1, 64)).astype(np.float32)
             for i in range(20)}
    for k, v in blobs.items():
        assert pool.put(k, v)
    for k, v in blobs.items():
        got = pool.get(k)
        np.testing.assert_array_equal(got, v)


def test_pool_lru_eviction_and_ssd_recovery():
    pool = MemoryPool(n_nodes=1, dram_per_node=8 * 2 * 1024 * 1024)
    vals = {f"k{i}": np.full(1024, i, np.float32) for i in range(32)}
    for k, v in vals.items():
        pool.put(k, v)
    srv = pool.servers[0]
    assert srv.evictions > 0, "LRU eviction should have triggered"
    # evicted keys recover from the SSD tier
    for k, v in vals.items():
        np.testing.assert_array_equal(pool.get(k), v)
    assert srv.recoveries > 0


# ---------------------------------------------------------------------------
# Connection-mapping balance (paper §4.3.3)
# ---------------------------------------------------------------------------


@SET
@given(prefill_tp=st.sampled_from([8, 16, 32]),
       decode_tp=st.sampled_from([1, 2, 4]),
       dp_mult=st.integers(1, 8))
def test_connection_map_balanced(prefill_tp, decode_tp, dp_mult):
    ratio = prefill_tp // decode_tp
    decode_dp = ratio * dp_mult
    mapping = connection_map(prefill_tp, decode_tp, decode_dp)
    bal = transfer_balance(mapping, prefill_tp)
    assert bal >= 0.5, f"unbalanced transfer topology: {bal}"


# ---------------------------------------------------------------------------
# Context-cache prefix invariants
# ---------------------------------------------------------------------------


@SET
@given(seed=st.integers(0, 30), plen=st.integers(8, 64))
def test_context_cache_prefix_semantics(seed, plen):
    from repro.mempool import ContextCache
    rng = np.random.RandomState(seed)
    pool = MemoryPool(n_nodes=4)
    cc = ContextCache(pool, block_tokens=8)
    tokens = list(rng.randint(0, 1000, plen))
    n_blocks = plen // 8
    payloads = [np.float32(rng.randn(4)) * 0 + i for i in range(n_blocks)]
    cc.store(tokens, payloads)
    # exact prefix matches all stored blocks
    reuse, keys = cc.match_prefix(tokens)
    assert reuse == n_blocks * 8
    # diverging first token matches nothing
    div = [tokens[0] + 1] + tokens[1:]
    reuse2, _ = cc.match_prefix(div)
    assert reuse2 == 0
    # diverging after the first block matches exactly one block
    if n_blocks >= 2:
        div2 = tokens[:8] + [tokens[8] + 1] + tokens[9:]
        reuse3, _ = cc.match_prefix(div2)
        assert reuse3 == 8
    # storing again is a pure dedup no-op
    before = cc.stored_blocks
    cc.store(tokens, payloads)
    assert cc.stored_blocks == before


# ---------------------------------------------------------------------------
# Sampling invariants (CPU-free in-graph sampling, §4.2.4)
# ---------------------------------------------------------------------------


@SET
@given(seed=st.integers(0, 40))
def test_top_p_support(seed):
    from repro.core.mtp import sample_top_p
    key = jax.random.PRNGKey(seed)
    logits = jax.random.normal(key, (4, 64)) * 3
    tok = sample_top_p(jax.random.PRNGKey(seed + 1), logits,
                       temperature=0.6, top_p=0.9)
    # sampled tokens must lie in the top-p nucleus
    probs = jax.nn.softmax(logits / 0.6, axis=-1)
    for b in range(4):
        order = np.argsort(-np.asarray(probs[b]))
        cum = np.cumsum(np.asarray(probs[b])[order])
        nucleus_size = int((cum < 0.9).sum()) + 1
        nucleus = set(order[:nucleus_size].tolist())
        assert int(tok[b]) in nucleus
