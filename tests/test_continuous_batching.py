"""Continuous batching in the scanned decode loop: adaptive chunk widths +
mid-scan slot refill must emit bit-identical tokens at width 4 and width 1
(chunk-split invariance of `model.decode_loop`), never admit later than
width 1, and plan no dead iterations — masked iterations burned on
resident slots — of their own. Also covers the accounting around it:
masked-iteration attribution in the trace (a capacity-frozen slot), unified
shed semantics (gate shed == capacity reject: no tokens delivered),
shed-inclusive queue percentiles, and full-prompt shared prefixes in the
synthetic workload."""
import jax
import numpy as np
import pytest

from conftest import smoke
from repro.core import init_mtp_params
from repro.models import init_params
from repro.serving import (Request, ServingSystem, SlotError,
                           poisson_requests)
from repro.serving.scheduler import RequestTrace, SLOTracker

_PARAMS = {}


def model(arch):
    if arch not in _PARAMS:
        cfg = smoke(arch)
        _PARAMS[arch] = (cfg, init_params(jax.random.PRNGKey(0), cfg))
    return _PARAMS[arch]


def _burst(n=8, rate=300.0, plen=10, max_new=6, seed=7):
    return poisson_requests(n, rate, plen, max_new, 100, seed=seed)


def _clone(reqs):
    return [Request(r.rid, list(r.prompt), r.max_new_tokens, r.arrival)
            for r in reqs]


def _serve(params, cfg, reqs, *, chunk, open_loop, **kw):
    kw.setdefault("decode_batch", 2)
    system = ServingSystem(params, cfg, n_prefill=2, capacity=32,
                           decode_chunk=chunk, **kw)
    results = system.serve(_clone(reqs), open_loop=open_loop)
    return {r.rid: r for r in results}, system.scheduler


# ---------------------------------------------------------------------------
# Token identity of width-4 scans vs width-1 scans
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen3-8b",        # dense attention
                                  "deepseek-r1",     # MLA latent cache
                                  "olmoe-1b-7b"])    # MoE
def test_cb_token_identical_to_per_step(arch):
    """Adaptive chunks + mid-scan refill emit the same tokens as width-1
    scans, closed AND open loop, with identical per-request decode_iters
    (masked iterations must not leak into the trace)."""
    cfg, params = model(arch)
    reqs = _burst()
    for open_loop in (False, True):
        ref, _ = _serve(params, cfg, reqs, chunk=1, open_loop=open_loop)
        out, sched = _serve(params, cfg, reqs, chunk=4, open_loop=open_loop)
        assert set(out) == set(ref)
        for rid in ref:
            assert out[rid].tokens == ref[rid].tokens, (arch, open_loop, rid)
            assert out[rid].decode_iters == ref[rid].decode_iters
        # adaptive widths snap down to where the shortest request ends, so
        # the continuous path plans no dead iterations of its own
        assert sched.summary()["dead_slot_rate"] == 0.0


def test_cb_token_identical_with_mtp():
    """MTP speculation on the continuous path: greedy accept/reject is
    PRNG-independent, so chunk-split invariance carries over."""
    cfg, params = model("granite-3-2b")
    mtp = init_mtp_params(jax.random.PRNGKey(2), cfg)
    reqs = _burst(n=6)
    for open_loop in (False, True):
        ref, _ = _serve(params, cfg, reqs, chunk=1, open_loop=open_loop,
                        use_mtp=True, mtp_params=mtp)
        out, _ = _serve(params, cfg, reqs, chunk=4, open_loop=open_loop,
                        use_mtp=True, mtp_params=mtp)
        for rid in ref:
            assert out[rid].tokens == ref[rid].tokens, (open_loop, rid)
            assert out[rid].decode_iters == ref[rid].decode_iters


def test_cb_mid_scan_refill_on_autoscaled_pool():
    """A refill landing mid-wave on a pooled + autoscaled run: freed slots
    are refilled between engine chunks (mid_scan_refills > 0) and the
    tokens still match a width-1 autoscaled serve bit-exactly."""
    cfg, params = model("granite-3-2b")
    reqs = _burst(n=10, rate=400.0, seed=5)
    pool_kw = dict(decode_engines=1, autoscale=True, min_engines=1,
                   max_engines=3)
    ref, _ = _serve(params, cfg, reqs, chunk=1, open_loop=True, **pool_kw)
    out, sched = _serve(params, cfg, reqs, chunk=4, open_loop=True,
                        **pool_kw)
    for rid in ref:
        assert out[rid].tokens == ref[rid].tokens, rid
    s = sched.summary()
    assert s["mid_scan_refills"] > 0
    assert s["scale_grows"] >= 1                # the burst did scale out
    # per-engine masked-iteration ledgers reconcile with the global one
    assert sum(s["engine_masked_iters"]) == s["masked_slot_iters"]


def test_wave_shaped_mode_is_rejected():
    """The wave-shaped decode mode is gone: asking for it raises instead of
    silently serving with adaptive widths."""
    cfg, params = model("qwen3-8b")
    with pytest.raises(ValueError, match="wave-shaped"):
        ServingSystem(params, cfg, n_prefill=1, decode_batch=2, capacity=32,
                      decode_chunk=4, continuous_batching=False)


# ---------------------------------------------------------------------------
# No dead iterations, admissions never later
# ---------------------------------------------------------------------------


def test_cb_lowers_dead_slot_rate_and_never_admits_later():
    """Identical arrival trace through width-1 and width-4 scans: same
    tokens, no dead iterations at either width (the width snaps down to
    where the shortest request ends), mid-scan refills, no request admitted
    later at width 4 than at width 1, and the TPOT gate still holds."""
    cfg, params = model("granite-3-2b")
    # max_new=6 -> 5 decode iters, != 0 mod chunk 4: a fixed width-4 scan
    # would burn masked tail iterations on the shortest slot.
    reqs = _burst(n=8, rate=300.0, max_new=6)
    kw = dict(open_loop=True, decode_batch=3, tpot_budget_ms=9.0,
              admission="queue")
    one, s_one = _serve(params, cfg, reqs, chunk=1, **kw)
    four, s_four = _serve(params, cfg, reqs, chunk=4, **kw)
    for rid in one:
        assert four[rid].tokens == one[rid].tokens, rid
    so, sn = s_one.summary(), s_four.summary()
    assert so["dead_slot_rate"] == sn["dead_slot_rate"] == 0.0
    assert sn["mid_scan_refills"] > 0
    assert sn["tpot_max_s"] <= 9.0e-3 + 1e-12    # gate never violated
    for rid, tr in s_four.traces.items():
        assert tr.decode_admit <= s_one.traces[rid].decode_admit + 1e-12


# ---------------------------------------------------------------------------
# Masked-iteration attribution in the trace
# ---------------------------------------------------------------------------


def test_masked_iterations_attributed_not_charged():
    """A capacity-frozen slot is masked inside a width-4 scan: its masked
    iterations must land in trace.masked_iters — NOT in decode_iters,
    decode_seconds, or the virtual clock — and the next turn, in which it
    is never live, raises instead of livelocking."""
    cfg, params = model("granite-3-2b")
    system = ServingSystem(params, cfg, n_prefill=1, decode_batch=2,
                           capacity=16, decode_chunk=4)
    sched = system.scheduler
    rng = np.random.RandomState(3)
    # rid 0 has room for every token; rid 1 (prompt 14, capacity 16) for
    # two decode writes. The serve loop rejects rid 1 up front, so it is
    # placed the way the loop places an admitted request.
    for rid, plen in ((0, 6), (1, 14)):
        req = Request(rid, list(rng.randint(0, 100, plen)), 8)
        trace = sched.on_arrival(rid, 0.0, plen)
        first, caches, res = system.prefills[0].run(req)
        sched.on_prefill_done(trace, 0, res.computed_tokens,
                              res.reused_tokens)
        sched.on_transfer(trace, system.transfer.transfer(caches, rid=rid))
        slot = system.pool.engines[0].free_slot()
        system.pool.add(0, slot, caches, first, plen, res, 8)
        sched.on_admit(trace, slot, 0)
    clock0 = sched.engine_clock(0)
    finished, iter_log = system.pool.step_engine(0)
    assert finished == [] and len(iter_log) == 4
    assert [masked for *_, masked in iter_log] == [[], [], [1], [1]]
    for entry in iter_log:
        sched.on_decode_step(*entry, engine=0)
    recs = {r.rid: r for r in sched.traces.values()}
    assert (recs[0].decode_iters, recs[0].masked_iters) == (4, 0)
    assert (recs[1].decode_iters, recs[1].masked_iters) == (2, 2)
    s = sched.summary()
    assert s["masked_slot_iters"] == 2 and s["live_slot_iters"] == 6
    assert s["dead_slot_rate"] == pytest.approx(2 / 8)
    # masked iterations charge zero virtual time: the clock and each trace
    # are charged per iteration over the live batch only
    cost = sched.cost
    assert sched.engine_clock(0) - clock0 == pytest.approx(
        2 * cost.step_time(2) + 2 * cost.step_time(1))
    assert recs[1].decode_seconds == pytest.approx(2 * cost.step_time(2))
    with pytest.raises(SlotError, match="capacity"):
        system.pool.step_engine(0)


# ---------------------------------------------------------------------------
# Satellite 2: unified shed semantics
# ---------------------------------------------------------------------------


def test_gate_shed_and_capacity_reject_deliver_no_tokens():
    """Both rejection paths agree: shed=True, tokens == [], tokens_out == 0
    — the prefill-produced first token of a gate shed is discarded, not
    leaked into throughput."""
    cfg, params = model("granite-3-2b")
    rng = np.random.RandomState(11)
    reqs = [Request(i, list(rng.randint(0, 100, 10)), 4) for i in range(6)]
    reqs.append(Request(6, list(rng.randint(0, 100, 30)), 8))  # 30+7 > 32
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=4,
                           capacity=32, tpot_budget_ms=6.0, admission="shed")
    results = {r.rid: r for r in system.serve(reqs)}
    recs = {r["rid"]: r for r in system.scheduler.trace_records()}
    shed = [r for r in results.values() if r.shed]
    assert results[6].shed                       # capacity reject
    assert any(r.rid != 6 for r in shed)         # gate demonstrably shed
    for r in shed:
        assert r.tokens == [] and r.decode_iters == 0
        assert recs[r.rid]["tokens_out"] == 0
    # throughput counts only delivered tokens
    assert system.scheduler.decode_token_count \
        == sum(len(r.tokens) for r in results.values() if not r.shed) \
        - sum(1 for r in results.values() if not r.shed)  # 1st from prefill
    # gate sheds stamp their queue time; capacity rejects never queued
    assert recs[6]["queue_seconds"] == 0.0
    for r in shed:
        if r.rid != 6:
            assert recs[r.rid]["decode_admit"] >= recs[r.rid]["prefill_end"]


# ---------------------------------------------------------------------------
# Satellite 3: queue percentiles include shed traces
# ---------------------------------------------------------------------------


def test_queue_p99_includes_shed_traces():
    tracker = SLOTracker()
    fin = RequestTrace(0, decode_admit=0.1, decode_end=0.2, decode_iters=1,
                       decode_tokens=1, decode_seconds=0.1, tokens_out=2)
    tracker.record(fin)
    shed = RequestTrace(1, decode_admit=5.0, decode_end=5.0, shed=True)
    tracker.record(shed)
    s = tracker.summary()
    assert fin.queue_seconds == pytest.approx(0.1)
    assert shed.queue_seconds == pytest.approx(5.0)
    # the pooled percentile sees the shed request's 5 s wait
    assert s["queue_p99_s"] > 1.0
    assert s["queue_p99_shed_s"] == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# Satellite 4: shared_prefix == prompt_len in the synthetic workload
# ---------------------------------------------------------------------------


def test_poisson_requests_full_prompt_shared_prefix():
    """shared_prefix == prompt_len models a fully-cached multi-turn
    re-entry stream: every prompt is the same block-aligned prefix."""
    reqs = poisson_requests(4, 100.0, 8, 4, 100, seed=0, shared_prefix=8)
    assert len({tuple(r.prompt) for r in reqs}) == 1
    assert all(len(r.prompt) == 8 for r in reqs)
    with pytest.raises(ValueError, match="shared_prefix"):
        poisson_requests(4, 100.0, 8, 4, 100, seed=0, shared_prefix=9)
    with pytest.raises(ValueError, match="shared_prefix"):
        poisson_requests(4, 100.0, 8, 4, 100, seed=0, shared_prefix=-1)
    # and the stream actually serves; reuse caps at prompt_len - 1 (the
    # last token must be computed for first-token logits) block-aligned,
    # so block 4 under an 8-token fully-shared prompt reuses exactly 4
    from repro.mempool import ContextCache, MemoryPool
    cfg, params = model("qwen3-8b")
    cc = ContextCache(MemoryPool(n_nodes=4), block_tokens=4,
                      model_tag=cfg.name)
    reqs = poisson_requests(4, 100.0, 8, 4, cfg.vocab_size, seed=0,
                            shared_prefix=8)
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                           capacity=32, context_cache=cc)
    results = system.serve(reqs, open_loop=True)
    assert all(len(r.tokens) == 4 for r in results)
    assert any(r.reused_tokens == 4 for r in results)
    for r in results:
        assert r.reused_tokens + r.computed_tokens == 8
