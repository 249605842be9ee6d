"""Device-resident decode fast path: scanned multi-step decode
(`model.decode_loop`), chunked suffix prefill (`model.prefill_continue`),
batched EMS block packing, single-collective quantized LEP dispatch, and the
chunked serving path end-to-end."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke
from repro.models import (decode_loop, decode_step, decode_step_held,
                          init_params, make_caches, prefill, prefill_continue)
from repro.models.attention import is_ring
from repro.serving import (DecodeCostModel, MicrobatchInterleaver, Request,
                           SchedulerConfig, ServingSystem,
                           decode_cost_from_roofline)
from repro.serving import cache_ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def qwen():
    cfg = smoke("qwen3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prefill_batch(cfg, params, n_req=2, plen=12, capacity=32, seed=0):
    rng = np.random.RandomState(seed)
    prompts = [list(rng.randint(0, 200, plen)) for _ in range(n_req)]
    logits, caches = prefill(params, cfg, {"tokens": jnp.asarray(prompts,
                                                                 jnp.int32)},
                             capacity=capacity, cache_dtype=jnp.float32)
    tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    cl0 = jnp.full((n_req,), plen, jnp.int32)
    return prompts, tok0, caches, cl0


def _sequential(cfg, params, tok, caches, cl, n, step=None):
    step = step or (lambda t, c, l: decode_step(params, cfg, t, c, l))
    seq = []
    for _ in range(n):
        lg, caches = step(tok[:, None], caches, cl)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        cl = cl + 1
        seq.append(np.asarray(tok))
    return np.stack(seq, 1), caches, cl


def _content_equal(a, b):
    """Bitwise equality of every cache leaf (length bookkeeping leaves may
    legitimately be scalar on one side and per-slot on the other)."""
    oks = jax.tree.leaves(jax.tree.map(
        lambda x, y: bool(jnp.array_equal(jnp.broadcast_to(x, y.shape)
                                          if x.shape != y.shape else x, y)),
        a, b))
    return all(oks)


# ---------------------------------------------------------------------------
# decode_loop(n) == n sequential decode_step calls
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-r1", "olmoe-1b-7b",
                                  "zamba2-1.2b"])
def test_decode_loop_matches_sequential(arch):
    cfg = smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    _, tok0, caches, cl0 = _prefill_batch(cfg, params)
    n = 4
    seq, caches_s, _ = _sequential(cfg, params, tok0, caches, cl0, n)
    em, lv, fin, _, _, caches_l, clf = decode_loop(params, cfg, tok0,
                                                   caches, cl0, n)
    assert np.array_equal(np.asarray(em), seq)
    assert np.asarray(lv).all()
    assert np.asarray(fin).all()
    assert np.array_equal(np.asarray(clf), np.asarray(cl0) + n)
    assert _content_equal(caches_s, caches_l)


#: The cache families decode_loop freezes slots of: GQA KV, MLA latents,
#: MoE, the hybrid's shared KV beside its SSM states, and a ring (granite
#: with an 8-token window, so a 14- or 32-token cache is written as a ring).
MASKING_CASES = ["qwen3-8b", "deepseek-r1", "olmoe-1b-7b", "zamba2-1.2b",
                 "granite-ring"]


def _masking_case(case):
    if case == "granite-ring":
        cfg = dataclasses.replace(smoke("granite-3-2b"), sliding_window=8)
    else:
        cfg = smoke(case)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("case", MASKING_CASES)
def test_decode_loop_per_slot_masking(case):
    """A slot whose steps_left runs out mid-chunk freezes bit-exactly."""
    cfg, params = _masking_case(case)
    _, tok0, caches, cl0 = _prefill_batch(cfg, params)
    seq, _, _ = _sequential(cfg, params, tok0, caches, cl0, 5)
    em, lv, _, _, _, caches_m, clm = decode_loop(
        params, cfg, tok0, caches, cl0, 5,
        steps_left=jnp.asarray([5, 2], jnp.int32))
    em, lv = np.asarray(em), np.asarray(lv)
    assert np.array_equal(em[0], seq[0])
    assert np.array_equal(em[1, :2], seq[1, :2])
    assert lv.tolist() == [[True] * 5, [True, True, False, False, False]]
    assert np.asarray(clm).tolist() == [17, 14]
    # the frozen slot's cache content must equal a 2-step sequential run
    # (length bookkeeping is global per-batch, so compare batched leaves)
    _, caches_2, _ = _sequential(cfg, params, tok0, caches, cl0, 2)
    sl_m = cache_ops.slice_request(cfg, caches_m, 1)
    sl_2 = cache_ops.slice_request(cfg, caches_2, 1)
    axes = cache_ops.cache_batch_axes(cfg, caches)
    oks = jax.tree.leaves(jax.tree.map(
        lambda x, y, ax: True if ax is None else bool(jnp.array_equal(x, y)),
        sl_2, sl_m, axes))
    assert all(oks)


@pytest.mark.parametrize("case", MASKING_CASES)
def test_decode_loop_capacity_masking(case):
    """Slots at cache capacity stop advancing instead of corrupting KV, and
    their caches hold bit for bit what two sequential steps leave. A ring
    has no capacity to reach: its slots decode on as sequential steps do."""
    cfg, params = _masking_case(case)
    _, tok0, caches, cl0 = _prefill_batch(cfg, params, capacity=14)  # 2 free
    em, lv, _, _, _, caches_l, clf = decode_loop(params, cfg, tok0, caches,
                                                 cl0, 5)
    n = 5 if is_ring(cfg, 14) else 2
    seq, caches_s, _ = _sequential(cfg, params, tok0, caches, cl0, n)
    assert np.asarray(clf).tolist() == [12 + n] * 2
    assert np.asarray(lv)[:, :n].all() and not np.asarray(lv)[:, n:].any()
    assert np.array_equal(np.asarray(em)[:, :n], seq)
    assert _content_equal(caches_s, caches_l)


def test_decode_loop_interleaved_matches_sequential(qwen):
    """Byte-exactness holds when the inner step is microbatch-interleaved."""
    cfg, params = qwen
    _, tok0, caches, cl0 = _prefill_batch(cfg, params)
    interleaver = MicrobatchInterleaver(2)
    wrap = interleaver.wrap(
        lambda t, c, l: decode_step(params, cfg, t, c, l), 2)
    seq, caches_s, _ = _sequential(cfg, params, tok0, caches, cl0, 4,
                                   step=wrap)
    wrap_held = interleaver.wrap(
        lambda t, c, l, live: decode_step_held(params, cfg, t, c, l,
                                               live=live), 2)
    em, lv, _, _, _, caches_l, _ = decode_loop(params, cfg, tok0, caches,
                                               cl0, 4, step_fn=wrap_held)
    assert np.array_equal(np.asarray(em), seq)
    assert _content_equal(caches_s, caches_l)


# ---------------------------------------------------------------------------
# prefill_continue == per-token teacher-forced suffix loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-r1"])
def test_prefill_continue_matches_token_loop(arch):
    cfg = smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    prompt = list(rng.randint(0, 200, 14))
    reuse = 8
    _, caches = prefill(params, cfg,
                        {"tokens": jnp.asarray([prompt[:reuse]], jnp.int32)},
                        capacity=32, cache_dtype=jnp.float32)
    # reference: per-token decode_step suffix loop
    c_ref, cl, lg = caches, jnp.int32(reuse), None
    for t in prompt[reuse:]:
        lg, c_ref = decode_step(params, cfg, jnp.asarray([[t]], jnp.int32),
                                c_ref, cl)
        cl = cl + 1
    lg2, c_new = prefill_continue(params, cfg,
                                  jnp.asarray([prompt[reuse:]], jnp.int32),
                                  caches, jnp.int32(reuse))
    np.testing.assert_allclose(np.asarray(lg2[0, -1]), np.asarray(lg[0]),
                               rtol=1e-4, atol=1e-4)
    assert int(jnp.argmax(lg2[0, -1])) == int(jnp.argmax(lg[0]))
    # caches agree over the valid region [0, len(prompt))
    sl_ref = cache_ops.seq_slice(cfg, c_ref, 0, len(prompt))
    sl_new = cache_ops.seq_slice(cfg, c_new, 0, len(prompt))
    for a, b in zip(jax.tree.leaves(sl_ref), jax.tree.leaves(sl_new)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_prefill_continue_rejects_unsupported_archs():
    cfg = smoke("mamba2-780m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    from repro.models import make_caches
    caches = make_caches(cfg, 1, 16, jnp.float32)
    with pytest.raises(NotImplementedError):
        prefill_continue(params, cfg, jnp.zeros((1, 4), jnp.int32), caches,
                         jnp.int32(4))


# ---------------------------------------------------------------------------
# Batched EMS block packing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-r1"])
def test_pack_blocks_matches_per_block_pack(arch):
    cfg = smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    _, _, caches, _ = _prefill_batch(cfg, params, n_req=1, plen=16,
                                     capacity=24)
    block, n_blocks = 4, 3
    rows = cache_ops.pack_blocks(cfg, caches, n_blocks, block)
    assert len(rows) == n_blocks
    for bi in range(n_blocks):
        ref = cache_ops.pack_payload(
            cache_ops.seq_slice(cfg, caches, bi * block, block))
        assert np.array_equal(rows[bi], ref), f"block {bi} differs"
    assert cache_ops.pack_blocks(cfg, caches, 0, block) == []


def _insert_per_block(cfg, caches, rows, block):
    """EMS rows into ``caches`` one block at a time: an unpack_payload and a
    seq_insert each."""
    tmpl = cache_ops.seq_slice(cfg, caches, 0, block)
    for bi, row in enumerate(rows):
        caches = cache_ops.seq_insert(
            cfg, caches, cache_ops.unpack_payload(row, tmpl), bi * block)
    return caches


@pytest.mark.parametrize("arch", ["qwen3-8b", "deepseek-r1", "olmoe-1b-7b"])
def test_insert_blocks_matches_per_block_insert(arch):
    """insert_blocks of pack_blocks rows into a fresh cache equals the
    per-block unpack_payload/seq_insert loop bit for bit, over the blocks
    and beyond them, and holds the source cache's tokens over the blocks."""
    cfg = smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    capacity = 24
    _, _, caches, _ = _prefill_batch(cfg, params, n_req=1, plen=16,
                                     capacity=capacity)
    block, n_blocks = 4, 3
    rows = cache_ops.pack_blocks(cfg, caches, n_blocks, block)

    def fresh():
        return make_caches(cfg, 1, capacity, jnp.float32)

    want = _insert_per_block(cfg, fresh(), rows, block)
    got = cache_ops.insert_blocks(cfg, fresh(), rows, block)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert _content_equal(got, want)
    span = n_blocks * block
    assert _content_equal(cache_ops.seq_slice(cfg, got, 0, span),
                          cache_ops.seq_slice(cfg, caches, 0, span))
    rest = capacity - span
    assert not any(bool(jnp.any(x)) for x in jax.tree.leaves(
        cache_ops.seq_slice(cfg, got, span, rest)))


def test_prefill_engine_inserts_ems_hit_in_one_call(qwen, monkeypatch):
    """An EMS hit of any block count lands through one insert_blocks call;
    the first token and the last-position logits equal those computed from
    a cache rebuilt block by block, and a second hit of the same block
    count compiles nothing."""
    from repro.mempool import ContextCache, MemoryPool
    from repro.serving.engine import PrefillEngine

    cfg, params = qwen
    block = 4
    cc = ContextCache(MemoryPool(n_nodes=4), block_tokens=block,
                      model_tag=cfg.name)
    engine = PrefillEngine(params, cfg, 48, context_cache=cc)
    calls = []
    insert = cache_ops.insert_blocks

    def counted(*args):
        calls.append(len(args[2]))
        return insert(*args)

    monkeypatch.setattr(cache_ops, "insert_blocks", counted)
    compiles = []

    def on_event(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration_secs)

    def per_block_logits(prompt, n_blocks):
        _, keys = cc.match_prefix(prompt)
        rows = cc.fetch(keys[:n_blocks])
        caches = _insert_per_block(cfg, engine._fresh_cache(), rows, block)
        last, _ = engine._compute(prompt, caches, n_blocks * block)
        return np.asarray(last)

    rng = np.random.RandomState(11)
    for n_blocks in (1, 3, 6):
        doc = list(rng.randint(0, 200, n_blocks * block))
        engine.run(Request(-n_blocks, doc, 1))        # stores, no hit
        prompt = doc + list(rng.randint(0, 200, 5))
        first, _, res = engine.run(Request(n_blocks, prompt, 1))
        assert res.reused_tokens == n_blocks * block
        assert first == int(np.argmax(per_block_logits(prompt, n_blocks)))

        prompt = doc + list(rng.randint(0, 200, 5))
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            last, _, res = engine.run_logits(Request(100 + n_blocks, prompt,
                                                     1))
            last = np.asarray(last)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        assert res.reused_tokens == n_blocks * block
        assert compiles == [], f"{len(compiles)} compiles at {n_blocks} blocks"
        assert np.array_equal(last, per_block_logits(prompt, n_blocks))
    assert calls == [1, 1, 3, 3, 6, 6]


# ---------------------------------------------------------------------------
# Chunked serving end-to-end
# ---------------------------------------------------------------------------


def test_serving_decode_chunk_token_identical(qwen):
    """decode_chunk >= 4 emits token-identical output to per-step decode,
    with identical per-request decode_iters in the trace."""
    cfg, params = qwen
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(0, 200, 12)) for _ in range(5)]
    reqs = [Request(i, p, 6) for i, p in enumerate(prompts)]
    out = {}
    for chunk in (1, 4):
        system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                               capacity=32, decode_chunk=chunk)
        results = system.serve(list(reqs))
        out[chunk] = {r.rid: r for r in results}
        assert len(results) == len(reqs)
    for rid in out[1]:
        assert out[4][rid].tokens == out[1][rid].tokens, f"rid {rid}"
        assert out[4][rid].decode_iters == out[1][rid].decode_iters
    # virtual decode time must be charged per iteration, not per chunk
    assert not out[4][0].shed


@pytest.mark.parametrize("chunk", [1, 4])
def test_serving_counts_nonfinite_logits(qwen, chunk):
    """Every served logits row is inspected, on the per-step and the scanned
    decode path: none with finite weights; all of them (the prompt's last
    position and each decode step) with a NaN final norm, which leaves the
    KV caches, and so decoding itself, intact."""
    cfg, params = qwen
    reqs = [Request(i, list(range(1 + i, 13 + i)), 6) for i in range(3)]
    nan_norm = dict(params, final_norm=jnp.full_like(params["final_norm"],
                                                     jnp.nan))
    for p, want in ((params, 0), (nan_norm, 6)):
        system = ServingSystem(p, cfg, n_prefill=1, decode_batch=2,
                               capacity=32, decode_chunk=chunk)
        results = system.serve(list(reqs))
        assert [len(r.tokens) for r in results] == [6] * 3
        assert [r.nonfinite_logits for r in results] == [want] * 3


def test_serving_decode_chunk_with_reuse_and_trace(qwen):
    """Chunked decode + EMS reuse (chunked suffix prefill) still accounts
    reused+computed == prompt and keeps the trace consistent."""
    from repro.mempool import ContextCache, MemoryPool

    cfg, params = qwen
    rng = np.random.RandomState(6)
    shared = list(rng.randint(0, 200, 16))
    prompts = [shared + list(rng.randint(0, 200, 8)) for _ in range(4)]
    pool = MemoryPool(n_nodes=4)
    cc = ContextCache(pool, block_tokens=8, model_tag=cfg.name)
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                           capacity=48, context_cache=cc, decode_chunk=4)
    results = system.serve([Request(i, p, 5) for i, p in enumerate(prompts)])
    assert any(r.reused_tokens > 0 for r in results)
    for r in results:
        assert r.reused_tokens + r.computed_tokens == len(prompts[r.rid])
        assert len(r.tokens) == 5
    for rec in system.scheduler.trace_records():
        assert rec["decode_iters"] == 4          # 5 tokens - 1 from prefill
        assert rec["decode_seconds"] > 0


def test_chunked_engine_raises_on_capacity_frozen_slot(qwen):
    """A slot that hits cache capacity with tokens still requested must
    raise SlotError on the chunked path (like per-step decode via
    DecodeSlotManager.advance), never livelock silently."""
    from repro.serving import DecodeEngine, RequestResult, SlotError
    from repro.serving.cache_ops import slice_request

    cfg, params = qwen
    plen, cap = 10, 12                      # room for only 2 decode writes
    rng = np.random.RandomState(13)
    prompt = list(rng.randint(0, 200, plen))
    logits, caches = prefill(params, cfg,
                             {"tokens": jnp.asarray([prompt], jnp.int32)},
                             capacity=cap, cache_dtype=jnp.float32)
    eng = DecodeEngine(params, cfg, max_batch=1, capacity=cap,
                       decode_chunk=4)
    res = RequestResult(0, [])
    eng.add(0, slice_request(cfg, caches, 0), int(jnp.argmax(logits[0, -1])),
            plen, res, max_new=8)           # wants more than capacity allows
    with pytest.raises(SlotError, match="capacity"):
        while eng.active:
            eng.step_chunk()


def test_admit_with_no_free_slot_requeues_instead_of_crashing(qwen):
    """A stale 'admit' decision (gate says admit, no slot free) must never
    reach DecodeSlotManager.allocate with slot=None."""
    cfg, params = qwen
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, 200, 10)) for _ in range(3)]
    system = ServingSystem(params, cfg, n_prefill=1, decode_batch=1,
                           capacity=24)
    system.scheduler.gate.decide = (lambda active, has_free_slot,
                                *a, **k: "admit")
    results = system.serve([Request(i, p, 4) for i, p in enumerate(prompts)])
    assert len(results) == 3
    for r in results:
        assert len(r.tokens) == 4 and not r.shed


# ---------------------------------------------------------------------------
# Calibrated decode cost model (ROADMAP open item)
# ---------------------------------------------------------------------------


def test_decode_cost_from_roofline_and_fallback():
    rec = {"compute_s": 1e-4, "memory_s": 3e-3, "collective_s": 2e-4}
    kv_bytes = 0.4e9                            # 0.4 GB latent/KV per request
    model = decode_cost_from_roofline(rec, kv_bytes, batch_per_chip=0.5)
    step = max(rec["compute_s"], rec["memory_s"]) + rec["collective_s"]
    per = kv_bytes / 819e9
    assert model.per_req_s == pytest.approx(per)
    assert model.fixed_s == pytest.approx(step - 0.5 * per)
    assert model.step_time(1) == pytest.approx(model.fixed_s + per)
    # fixed-term floor: KV so large the remainder would go negative
    degenerate = decode_cost_from_roofline(rec, 1e13, batch_per_chip=4.0)
    assert degenerate.fixed_s == pytest.approx(0.2 * step)
    # fallbacks -> placeholder defaults
    assert decode_cost_from_roofline(None, kv_bytes, 1.0) == DecodeCostModel()
    assert decode_cost_from_roofline(rec, 0.0, 1.0) == DecodeCostModel()


def test_scheduler_config_decode_chunk_is_baked_in(qwen):
    cfg, params = qwen
    system = ServingSystem(params, cfg, n_prefill=1, decode_batch=2,
                           capacity=24, decode_chunk=2)
    with pytest.raises(ValueError, match="decode_chunk"):
        system.reconfigure_scheduler(SchedulerConfig(decode_chunk=1))
    system.reconfigure_scheduler(SchedulerConfig(decode_chunk=2))


# ---------------------------------------------------------------------------
# Single-collective quantized LEP dispatch (multi-device subprocess)
# ---------------------------------------------------------------------------


def test_quantized_dispatch_single_collective():
    """Packed-scale dispatch compiles to exactly ONE all_to_all per hop
    (dispatch + combine = 2 total vs 3 for the two-collective baseline) and
    is bit-identical to the baseline (the scale bitcast is exact)."""
    code = '''
import dataclasses, jax, jax.numpy as jnp
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 4)
from repro.configs import get_config, smoke_variant
from repro.core.lep import make_lep_moe_fn
from repro.models import moe as moe_mod
cfg = dataclasses.replace(smoke_variant(get_config("olmoe-1b-7b")),
                          capacity_factor=8.0)
p1 = moe_mod.init_moe_params(jax.random.PRNGKey(0), cfg, 1, jnp.float32)
p = jax.tree.map(lambda a: a[0], p1)
x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.d_model), jnp.float32)
outs, counts = {}, {}
for packed in (True, False):
    fn = make_lep_moe_fn(mesh, ep_axes=("model",), pack_scales=packed)
    with mesh:
        outs[packed], _ = jax.jit(lambda pp, xx: fn(pp, xx, cfg))(p, x)
        counts[packed] = str(jax.make_jaxpr(
            lambda pp, xx: fn(pp, xx, cfg))(p, x)).count("all_to_all")
assert counts[True] == 2, counts    # 1 dispatch + 1 combine
assert counts[False] == 3, counts   # payload + scales + combine
assert jnp.array_equal(outs[True], outs[False])
ref, _ = moe_mod.moe_reference(p, x, cfg)
rel = float(jnp.max(jnp.abs(outs[True] - ref))) / float(jnp.max(jnp.abs(ref)))
assert rel < 0.05, rel              # int8 quantization tolerance
print("SINGLE_COLLECTIVE_OK")
'''
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=520)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "SINGLE_COLLECTIVE_OK" in r.stdout
