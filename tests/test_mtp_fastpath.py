"""Fused MTP speculative decoding fast path (`model.decode_loop_mtp`), the
one-forward base+draft verification, the MTP-aware scheduler accounting,
the open-loop Poisson serving mode, fresh-prompt chunked prefill, and the
`sample_top_p` cutoff regressions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke
from repro.core import mtp as mtp_mod
from repro.models import decode_step, init_params, prefill
from repro.models.model import cache_batch_axes, decode_loop_mtp
from repro.serving import (DecodeCostModel, PrefillEngine, Request,
                           SchedulerConfig, ServingSystem, poisson_requests)
from repro.serving import cache_ops


@pytest.fixture(scope="module")
def qwen():
    cfg = smoke("qwen3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    mtp = mtp_mod.init_mtp_params(jax.random.PRNGKey(7), cfg)
    return cfg, params, mtp


def _prefill_batch(cfg, params, n_req=3, plen=10, capacity=40, seed=2):
    rng = np.random.RandomState(seed)
    prompts = [list(rng.randint(0, 200, plen)) for _ in range(n_req)]
    logits, caches = prefill(params, cfg,
                             {"tokens": jnp.asarray(prompts, jnp.int32)},
                             capacity=capacity, cache_dtype=jnp.float32)
    tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    cl0 = jnp.full((n_req,), plen, jnp.int32)
    return prompts, tok0, caches, cl0


def _mtp_sequential(cfg, params, mtp, tok, drf, caches, cl, n, key,
                    fused=False):
    """Reference: n per-step mtp_step calls with the scan's key schedule."""
    ems, accs = [], []
    for _ in range(n):
        key, sub = jax.random.split(key)
        em, acc, tok, drf, caches, cl = mtp_mod.mtp_step(
            params, mtp, cfg, tok, drf, caches, cl, sub,
            fused_verify=fused)
        ems.append(np.asarray(em))
        accs.append(np.asarray(acc))
    return np.stack(ems, 1), np.stack(accs, 1), tok, drf, caches, cl


def _content_equal(cfg, a, b):
    """Bitwise equality of every batched cache leaf (the `length`
    bookkeeping leaves are excluded: per-step mtp_step leaves them at the
    speculative write position regardless of acceptance, while the scanned
    loop normalizes them to the committed per-slot cache_len)."""
    axes = cache_batch_axes(cfg)
    oks = jax.tree.leaves(jax.tree.map(
        lambda x, y, ax: True if ax is None else bool(jnp.array_equal(x, y)),
        a, b, axes))
    return all(oks)


# ---------------------------------------------------------------------------
# decode_loop_mtp(n) == n sequential mtp_step calls
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-r1", "olmoe-1b-7b"])
def test_decode_loop_mtp_matches_per_step(arch):
    """Token-identical and bitwise cache-equal across dense/MLA/MoE."""
    cfg = smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mtp = mtp_mod.init_mtp_params(jax.random.PRNGKey(1), cfg)
    _, tok0, caches, cl0 = _prefill_batch(cfg, params)
    key0 = jax.random.PRNGKey(5)
    n = 4
    ref_em, ref_acc, tok_s, _, caches_s, cl_s = _mtp_sequential(
        cfg, params, mtp, tok0, mtp_mod.propose_draft(params, mtp, cfg, tok0),
        caches, cl0, n, key0)
    em, acc, lv, tok_l, _, caches_l, cl_l = decode_loop_mtp(
        params, mtp, cfg, tok0, mtp_mod.propose_draft(params, mtp, cfg, tok0),
        caches, cl0, n, key=key0)
    assert np.array_equal(np.asarray(em), ref_em)
    assert np.array_equal(np.asarray(acc), ref_acc)
    assert np.asarray(lv).all()
    assert np.array_equal(np.asarray(cl_l), np.asarray(cl_s))
    assert np.array_equal(np.asarray(tok_l), np.asarray(tok_s))
    assert _content_equal(cfg, caches_s, caches_l)


def test_decode_loop_mtp_accept_reject_divergence(qwen):
    """Forced accept/reject divergence within one batch: slot 0 starts with
    the oracle draft (guaranteed accept), slot 1 with a wrong one."""
    cfg, params, mtp = qwen
    _, tok0, caches, cl0 = _prefill_batch(cfg, params, n_req=2)
    # oracle successor of tok0 per slot
    lg, c2 = decode_step(params, cfg, tok0[:, None], caches, cl0)
    oracle = jnp.argmax(lg, -1).astype(jnp.int32)
    d0 = jnp.stack([oracle[0], (oracle[1] + 1) % cfg.vocab_size])
    key0 = jax.random.PRNGKey(3)
    ref_em, ref_acc, _, _, caches_s, cl_s = _mtp_sequential(
        cfg, params, mtp, tok0, d0, caches, cl0, 3, key0)
    assert ref_acc[0, 0] and not ref_acc[1, 0]      # the divergence is real
    em, acc, lv, _, _, caches_l, cl_l = decode_loop_mtp(
        params, mtp, cfg, tok0, d0, caches, cl0, 3, key=key0)
    assert np.array_equal(np.asarray(em), ref_em)
    assert np.array_equal(np.asarray(acc), ref_acc)
    assert np.array_equal(np.asarray(cl_l), np.asarray(cl_s))
    # accepted slot advanced 2 on iteration one, rejected slot advanced 1
    assert int(cl_l[0]) >= int(cl0[0]) + 4
    assert _content_equal(cfg, caches_s, caches_l)


def test_decode_loop_mtp_steps_left_freezes(qwen):
    """A slot whose token budget drains mid-chunk freezes bit-exactly."""
    cfg, params, mtp = qwen
    _, tok0, caches, cl0 = _prefill_batch(cfg, params, n_req=2)
    d0 = mtp_mod.propose_draft(params, mtp, cfg, tok0)
    key0 = jax.random.PRNGKey(4)
    n = 4
    em, acc, lv, _, _, caches_m, cl_m = decode_loop_mtp(
        params, mtp, cfg, tok0, d0, caches, cl0, n, key=key0,
        steps_left=jnp.asarray([2 * n, 2], jnp.int32))
    lv = np.asarray(lv)
    k = int(lv[1].sum())                 # live iterations of the frozen slot
    assert k < n and lv[1, :k].all() and not lv[1, k:].any()
    # the frozen slot's cache/emissions equal a k-iteration per-step run
    ref_em, ref_acc, _, _, caches_k, cl_k = _mtp_sequential(
        cfg, params, mtp, tok0, d0, caches, cl0, k, key0)
    assert np.array_equal(np.asarray(em)[1, :k], ref_em[1, :k])
    assert int(cl_m[1]) == int(cl_k[1])
    axes = cache_batch_axes(cfg)
    sl_m = cache_ops.slice_request(cfg, caches_m, 1)
    sl_k = cache_ops.slice_request(cfg, caches_k, 1)
    oks = jax.tree.leaves(jax.tree.map(
        lambda x, y, ax: True if ax is None else bool(jnp.array_equal(x, y)),
        sl_k, sl_m, axes))
    assert all(oks)


def test_decode_loop_mtp_capacity_freeze(qwen):
    """Slots freeze (instead of corrupting KV) when both speculative writes
    no longer fit: live requires cache_len + 2 <= capacity."""
    cfg, params, mtp = qwen
    plen, cap = 10, 13                  # 3 free cells
    _, tok0, caches, cl0 = _prefill_batch(cfg, params, n_req=2, plen=plen,
                                          capacity=cap)
    d0 = mtp_mod.propose_draft(params, mtp, cfg, tok0)
    em, acc, lv, _, _, _, cl_f = decode_loop_mtp(
        params, mtp, cfg, tok0, d0, caches, cl0, 5, key=jax.random.PRNGKey(0))
    lv, acc = np.asarray(lv), np.asarray(acc)
    cl_f = np.asarray(cl_f)
    assert (cl_f <= cap).all()
    assert not lv[:, -1].any()          # everyone froze by the end
    # the mask must have stopped exactly when the speculative write would
    # no longer fit
    for i in range(2):
        cl = int(cl0[i])
        for j in range(5):
            expect_live = cl + 2 <= cap
            assert bool(lv[i, j]) == expect_live
            if expect_live:
                cl += 1 + int(acc[i, j])


def test_fused_verify_matches_two_step_tokens(qwen):
    """One-forward verification emits the same tokens/acceptance as the
    two-decode-step form (not bitwise: different reduction order)."""
    cfg, params, mtp = qwen
    _, tok0, caches, cl0 = _prefill_batch(cfg, params)
    d0 = mtp_mod.propose_draft(params, mtp, cfg, tok0)
    key0 = jax.random.PRNGKey(6)
    outs = {}
    for fused in (False, True):
        em, acc, lv, _, _, _, cl = decode_loop_mtp(
            params, mtp, cfg, tok0, d0, caches, cl0, 4, key=key0,
            fused_verify=fused)
        outs[fused] = (np.asarray(em), np.asarray(acc), np.asarray(cl))
    assert np.array_equal(outs[True][0], outs[False][0])
    assert np.array_equal(outs[True][1], outs[False][1])
    assert np.array_equal(outs[True][2], outs[False][2])


def test_can_fuse_verify_gating():
    assert mtp_mod.can_fuse_verify(smoke("qwen3-8b"), 32)
    assert mtp_mod.can_fuse_verify(smoke("deepseek-r1"), 32)
    assert not mtp_mod.can_fuse_verify(smoke("mamba2-780m"), 32)
    assert not mtp_mod.can_fuse_verify(smoke("zamba2-1.2b"), 32)
    phi = smoke("phi3-medium-14b")
    if phi.sliding_window:              # ring cache at long capacity
        assert not mtp_mod.can_fuse_verify(phi, phi.sliding_window + 1)


# ---------------------------------------------------------------------------
# Serving end-to-end: width-4 MTP scans == width-1 MTP scans
# ---------------------------------------------------------------------------


def test_serving_mtp_chunked_token_identical(qwen):
    """use_mtp + decode_chunk=4 emits token-identical output (and identical
    per-request iteration counts) to width-1 MTP serving."""
    cfg, params, mtp = qwen
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(0, 200, 12)) for _ in range(5)]
    reqs = [Request(i, p, 6) for i, p in enumerate(prompts)]
    out = {}
    for chunk in (1, 4):
        system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                               capacity=32, use_mtp=True, mtp_params=mtp,
                               decode_chunk=chunk)
        results = system.serve(list(reqs))
        assert len(results) == len(reqs)
        out[chunk] = {r.rid: r for r in results}
    for rid in out[1]:
        assert out[4][rid].tokens == out[1][rid].tokens, f"rid {rid}"
        assert out[4][rid].decode_iters == out[1][rid].decode_iters
    # scheduler ran with the MTP cost model and credited real tokens
    sched = system.scheduler
    assert sched.cost.mtp_iter_factor == DecodeCostModel.MTP_ITER_FACTOR
    for rec in sched.trace_records():
        assert rec["decode_tokens"] == rec["tokens_out"] - 1
        assert rec["decode_iters"] <= rec["decode_tokens"]


def test_serving_mtp_fused_token_identical(qwen):
    """The fused one-forward verify serves the same tokens end-to-end."""
    cfg, params, mtp = qwen
    rng = np.random.RandomState(12)
    prompts = [list(rng.randint(0, 200, 12)) for _ in range(4)]
    reqs = [Request(i, p, 6) for i, p in enumerate(prompts)]
    out = {}
    for fused in (False, True):
        system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                               capacity=32, use_mtp=True, mtp_params=mtp,
                               decode_chunk=4, mtp_fused=fused)
        out[fused] = {r.rid: r.tokens for r in system.serve(list(reqs))}
    assert out[True] == out[False]


def test_mtp_cost_model_terms():
    cm = DecodeCostModel(fixed_s=4e-3, per_req_s=1e-3)
    m = cm.with_mtp()
    assert m.mtp_iter_factor == 1.44 and m.mtp_accept == 0.70
    assert m.step_time(8) == pytest.approx(cm.step_time(8) * 1.44)
    assert m.token_time(8) == pytest.approx(m.step_time(8) / 1.7)
    # the budget buys more batch under MTP: slower iterations, 1+α credit
    b = m.max_batch_for(15e-3)
    assert b > 0
    assert m.token_time(b) <= 15e-3 + 1e-12
    assert m.token_time(b + 1) > 15e-3
    # defaults (no MTP terms) keep the PR 1 semantics bit-for-bit
    assert cm.step_time(8) == 4e-3 + 8e-3
    assert cm.max_batch_for(15e-3) == 11
    # a measured acceptance overrides the paper default
    m2 = cm.with_mtp(accept=0.25)
    assert m2.tokens_per_iter == pytest.approx(1.25)


def test_scheduler_config_use_mtp_is_baked_in(qwen):
    cfg, params, mtp = qwen
    system = ServingSystem(params, cfg, n_prefill=1, decode_batch=2,
                           capacity=24, use_mtp=True, mtp_params=mtp)
    with pytest.raises(ValueError, match="use_mtp"):
        system.reconfigure_scheduler(SchedulerConfig(use_mtp=False))
    system.reconfigure_scheduler(SchedulerConfig(use_mtp=True))


# ---------------------------------------------------------------------------
# sample_top_p cutoff regressions
# ---------------------------------------------------------------------------


def test_sample_top_p_keeps_at_least_one_token():
    """top_p >= 1.0 must keep the whole vocabulary (no OOB cutoff index)
    and a top token whose mass alone exceeds top_p must still be
    sampleable."""
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([[10.0, 0.0, -1.0, -2.0],
                          [0.1, 0.2, 0.3, 0.4]], jnp.float32)
    for top_p in (1.0, 1.5):
        out = mtp_mod.sample_top_p(key, logits, temperature=1.0, top_p=top_p)
        assert out.shape == (2,)
        assert ((out >= 0) & (out < 4)).all()
        # keeping everything == pure temperature+gumbel sampling
        g = -jnp.log(-jnp.log(jax.random.uniform(key, logits.shape) + 1e-20)
                     + 1e-20)
        ref = jnp.argmax(logits + g, axis=-1).astype(jnp.int32)
        assert jnp.array_equal(out, ref), top_p
    # peaked row: p(top) ≈ 1 > top_p=0.5 — must deterministically keep it
    peaked = jnp.asarray([[30.0, 0.0, 0.0, 0.0]], jnp.float32)
    for seed in range(8):
        out = mtp_mod.sample_top_p(jax.random.PRNGKey(seed), peaked,
                                   temperature=1.0, top_p=0.5)
        assert int(out[0]) == 0


# ---------------------------------------------------------------------------
# Open-loop Poisson serving
# ---------------------------------------------------------------------------


def test_poisson_requests_generator():
    reqs = poisson_requests(32, 100.0, 12, 4, 200, seed=1, shared_prefix=4)
    arr = [r.arrival for r in reqs]
    assert all(b > a for a, b in zip(arr, arr[1:]))
    assert all(len(r.prompt) == 12 for r in reqs)
    assert all(r.prompt[:4] == reqs[0].prompt[:4] for r in reqs)
    # mean inter-arrival ~ 1/rate (loose: 32 samples)
    gaps = np.diff([0.0] + arr)
    assert 0.2 / 100 < gaps.mean() < 5.0 / 100
    with pytest.raises(ValueError):
        poisson_requests(4, 0.0, 12, 4, 200, seed=0)
    # shared_prefix == prompt_len is legal (fully-cached re-entry stream);
    # only a prefix longer than the prompt is rejected
    full = poisson_requests(4, 10.0, 12, 4, 200, seed=0, shared_prefix=12)
    assert all(r.prompt == full[0].prompt for r in full)
    with pytest.raises(ValueError):
        poisson_requests(4, 10.0, 12, 4, 200, seed=0, shared_prefix=13)


def test_open_loop_burst_queues_and_matches_greedy(qwen):
    """An open-loop burst completes with token-identical output to closed
    loop, and actually queues (decode busy when later arrivals land)."""
    cfg, params, _ = qwen
    reqs = poisson_requests(6, 300.0, 10, 4, 200, seed=3)
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                           capacity=32)
    res_open = {r.rid: r.tokens for r in system.serve(list(reqs),
                                                      open_loop=True)}
    open_summary = system.scheduler.summary()
    res_closed = {r.rid: r.tokens
                  for r in system.serve(list(reqs), open_loop=False)}
    assert res_open == res_closed
    assert open_summary["completed"] == 6
    assert open_summary["queue_p99_s"] > 0
    # arrival-ordered admission: nobody decodes before arriving
    for rec in system.scheduler.trace_records():
        assert rec["decode_admit"] >= rec["arrival"]


def test_open_loop_tight_budget_sheds(qwen):
    """Burst + tight TPOT budget + shedding gate: load is actually shed."""
    cfg, params, _ = qwen
    reqs = poisson_requests(8, 500.0, 10, 4, 200, seed=4)
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=4,
                           capacity=32, tpot_budget_ms=5.5, admission="shed")
    results = system.serve(reqs, open_loop=True)
    s = system.scheduler.summary()
    assert s["completed"] + s["shed"] == 8
    assert s["shed"] > 0
    assert s["tpot_max_s"] * 1e3 <= 5.5 + 1e-9


# ---------------------------------------------------------------------------
# Fresh-prompt chunked prefill (bounded compile shapes)
# ---------------------------------------------------------------------------


def test_fresh_chunked_prefill_matches_full(qwen):
    """Chunked fresh prefill produces the same first token + equivalent
    caches as full prefill, from ONE compiled program per chunk width."""
    cfg, params, _ = qwen
    rng = np.random.RandomState(21)
    eng_full = PrefillEngine(params, cfg, capacity=48)
    eng_chunk = PrefillEngine(params, cfg, capacity=48, prefill_chunk=8)
    for i, plen in enumerate((24, 17, 9)):      # varied lengths, one program
        prompt = list(rng.randint(0, 200, plen))
        f1, c1, r1 = eng_full.run(Request(i, prompt, 1))
        f2, c2, r2 = eng_chunk.run(Request(i, prompt, 1))
        assert f1 == f2, plen
        assert r2.computed_tokens == plen
        sl1 = cache_ops.seq_slice(cfg, c1, 0, plen)
        sl2 = cache_ops.seq_slice(cfg, c2, 0, plen)
        for a, b in zip(jax.tree.leaves(sl1), jax.tree.leaves(sl2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
    assert eng_chunk.continue_widths == {8}
    assert eng_chunk.continue_cache_hit_rate > 0.8


def test_chunked_prefill_overflow_fails_fast(qwen):
    """A prompt that cannot fit the prefill cache raises instead of
    spinning forever once the chunk width clamps to zero."""
    cfg, params, _ = qwen
    eng = PrefillEngine(params, cfg, capacity=16, prefill_chunk=8)
    prompt = list(np.random.RandomState(0).randint(0, 200, 24))
    with pytest.raises(ValueError, match="capacity"):
        eng.run(Request(0, prompt, 1))


def test_scheduler_config_cannot_flip_use_mtp_at_construction(qwen):
    """The scheduler's MTP cost accounting always matches the engine: a
    scheduler_config with use_mtp=True cannot attach MTP charging to a
    non-MTP decode engine."""
    cfg, params, _ = qwen
    system = ServingSystem(params, cfg, n_prefill=1, decode_batch=2,
                           capacity=24,
                           scheduler_config=SchedulerConfig(use_mtp=True))
    assert system.scheduler.config.use_mtp is False
    assert system.scheduler.cost.mtp_iter_factor == 1.0


def test_serving_with_fresh_chunked_prefill_token_identical(qwen):
    """End-to-end serving with prefill_chunk set matches default serving."""
    cfg, params, _ = qwen
    rng = np.random.RandomState(22)
    prompts = [list(rng.randint(0, 200, 14)) for _ in range(4)]
    reqs = [Request(i, p, 5) for i, p in enumerate(prompts)]
    base = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                         capacity=32)
    chunked = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                            capacity=32, prefill_chunk=8)
    out_b = {r.rid: r.tokens for r in base.serve(list(reqs))}
    out_c = {r.rid: r.tokens for r in chunked.serve(list(reqs))}
    assert out_b == out_c
    assert all(e.continue_widths <= {8} for e in chunked.prefills)
