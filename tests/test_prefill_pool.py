"""Prefill-pool invariants for the peer-to-peer PDC plane: instance
lifecycle (spawn/park/retire/fail over stable ids), routed-token
conservation across every prefill policy (the least_loaded in-flight load
must drain to zero on ALL completion paths, including shed and fault
recovery), bit-identity of the pipelined chunked KV handoff vs the
synchronous whole-request path across dense/MLA/MoE, the streamed-TTFT
monotonicity property, and the joint P/D autoscaler's capacity see-saw."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke
from repro.models import decode_step, init_params, prefill
from repro.serving import (FaultEvent, FaultInjector, FaultPlan,
                           JointAutoscaler, PrefillPool, Request,
                           SchedulerConfig, ServingSystem)
from repro.serving.scheduler import ROUTERS, make_router


@pytest.fixture(scope="module")
def granite():
    cfg = smoke("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def stream_requests(n, prompt_len=12, max_new=4, seed=1):
    rng = np.random.RandomState(seed)
    return [Request(i, list(rng.randint(0, 100, prompt_len)), max_new)
            for i in range(n)]


def greedy_reference(cfg, params, prompt, n_new):
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    logits, caches = prefill(params, cfg, batch,
                             capacity=len(prompt) + n_new + 4,
                             cache_dtype=jnp.float32)
    toks = [int(jnp.argmax(logits[0, -1]))]
    cl = jnp.int32(len(prompt))
    for _ in range(n_new - 1):
        lg, caches = decode_step(params, cfg,
                                 jnp.asarray([[toks[-1]]], jnp.int32),
                                 caches, cl)
        toks.append(int(jnp.argmax(lg[0])))
        cl = cl + 1
    return toks


# ---------------------------------------------------------------------------
# PrefillPool lifecycle (pure control plane, no jax)
# ---------------------------------------------------------------------------


class _FakeCfg:
    name = "fake-arch"


class _FakePrefill:
    """Shape-compatible stand-in: the pool reads capacity/cfg.name/load."""

    def __init__(self, instance_id, capacity=32):
        self.instance_id = instance_id
        self.capacity = capacity
        self.cfg = _FakeCfg()
        self.load = 0


def test_prefill_pool_lifecycle_stable_ids():
    built = []

    def factory(i):
        built.append(i)
        return _FakePrefill(i)

    pool = PrefillPool([_FakePrefill(0), _FakePrefill(1)],
                       engine_factory=factory)
    assert (pool.n, pool.n_live, pool.live_ids) == (2, 2, [0, 1])

    # retire parks (id survives); reviving prefers the parked id
    pool.retire_engine(1)
    assert pool.live_ids == [0] and pool.n == 2
    inst, revived = pool.spawn_engine()
    assert (inst, revived) == (1, True) and built == []

    # failure marks dead; a spawn restarts over the same stable id
    pool.fail_engine(1)
    assert pool.dead_ids == [1] and pool.live_ids == [0]
    inst, revived = pool.spawn_engine()
    assert (inst, revived) == (1, True) and pool.dead_ids == []

    # full live roster: a spawn extends through the factory
    inst, revived = pool.spawn_engine()
    assert (inst, revived) == (2, False) and built == [2]
    assert pool.live_ids == [0, 1, 2]
    assert (pool.spawns, pool.retires, pool.failures) == (3, 1, 1)


def test_prefill_pool_lifecycle_errors():
    pool = PrefillPool([_FakePrefill(0), _FakePrefill(1)])
    pool.retire_engine(1)
    with pytest.raises(ValueError, match="already parked"):
        pool.retire_engine(1)
    with pytest.raises(ValueError, match="last live prefill instance"):
        pool.retire_engine(0)
    pool.spawn_engine()                      # revive 1
    pool.fail_engine(1)
    with pytest.raises(ValueError, match="already dead"):
        pool.fail_engine(1)
    with pytest.raises(ValueError, match="last live prefill instance"):
        pool.retire_engine(0)
    # no factory and nothing parked/dead left to revive after restarting 1
    pool.spawn_engine()
    with pytest.raises(RuntimeError, match="no engine_factory"):
        pool.spawn_engine()
    with pytest.raises(ValueError, match="at least one prefill instance"):
        PrefillPool([])
    with pytest.raises(ValueError, match="must share model config"):
        PrefillPool([_FakePrefill(0, capacity=32),
                     _FakePrefill(1, capacity=64)])


def test_prefill_router_resize_grows_never_shrinks():
    for policy in sorted(ROUTERS):
        r = make_router(policy, 2)
        r.resize(3)
        assert r.n == 3
        # routing reaches the new id once it is the best candidate
        assert r.select([5, 5, 0], candidates=[2]) == 2
        with pytest.raises(ValueError, match="never disappear"):
            r.resize(2)
        with pytest.raises(ValueError, match="no live prefill instance"):
            r.select([0, 0, 0], candidates=[])


def test_router_candidates_exclude_parked_instances():
    ll = make_router("least_loaded", 3)
    assert ll.select([9, 0, 4], candidates=[0, 2]) == 2   # 1 parked
    rr = make_router("round_robin", 3)
    assert rr.select([0, 0, 0], candidates=[0, 2]) == 0
    assert rr.select([0, 0, 0], candidates=[0, 2]) == 2   # cursor skips 1
    assert rr.select([0, 0, 0], candidates=[0, 2]) == 0   # wrapped
    qd = make_router("queue_depth", 2)
    assert qd.select([0, 0], candidates=[0, 1]) == 0
    assert qd.select([0, 0], candidates=[0, 1]) == 1      # depth-balanced
    qd.on_complete(0)
    assert qd.select([0, 0], candidates=[0, 1]) == 0


# ---------------------------------------------------------------------------
# JointAutoscaler decision semantics (pure control plane, no jax)
# ---------------------------------------------------------------------------


def test_joint_autoscaler_decisions_and_hysteresis():
    j = JointAutoscaler(None, 4, min_prefill=1, max_prefill=2,
                        min_decode=1, max_decode=2, ttft_budget_s=1e-3,
                        patience=1, cooldown=1)
    # TTFT pressure + sparable decode engine -> d2p, then cooldown holds
    assert j.decide(1, 2, 0, 0, 5e-3) == "shift_d2p"
    assert j.decide(1, 2, 0, 0, 5e-3) == "hold"
    # decode at min_decode can never donate
    assert j.decide(1, 1, 0, 0, 5e-3) == "hold"
    # TPOT pressure (demand 9 > 1 engine * 4 slots) + idle prefill -> p2d
    assert j.decide(2, 1, 4, 5, 0.0) == "shift_p2d"
    j.reset()
    # an undrainable victim blocks the shift
    assert j.decide(1, 2, 0, 0, 5e-3, decode_shrinkable=False) == "hold"
    # queued decode work vetoes donating a decode engine to prefill
    assert j.decide(1, 2, 0, 1, 5e-3) == "hold"

    slow = JointAutoscaler(None, 4, min_prefill=1, max_prefill=2,
                           min_decode=1, max_decode=2, ttft_budget_s=1e-3,
                           patience=2, cooldown=0)
    assert slow.decide(1, 2, 0, 0, 5e-3) == "hold"        # streak 1 < 2
    assert slow.decide(1, 2, 0, 0, 5e-3) == "shift_d2p"
    with pytest.raises(ValueError, match="min_prefill"):
        JointAutoscaler(None, 4, min_prefill=0, max_prefill=2,
                        min_decode=1, max_decode=2)


# ---------------------------------------------------------------------------
# Routed-token conservation (the satellite-1 accounting fix)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", sorted(ROUTERS))
def test_routed_load_conserved_across_lifecycle(granite, policy):
    """Token-weighted in-flight routed load drains to exactly zero when a
    wave completes — per policy, and across spawn/park/retire/fail roster
    churn between waves. Routing only ever targets live instances."""
    cfg, params = granite
    reqs = stream_requests(6)
    system = ServingSystem(params, cfg, prefill_engines=3, decode_batch=4,
                           capacity=64, policy=policy)
    ref = {r.rid: list(r.tokens) for r in system.serve(reqs)}
    sched = system.scheduler
    assert sched.prefill_inflight_tokens == [0.0, 0.0, 0.0]
    assert sched._routed_load == {}
    assert ref[0] == greedy_reference(cfg, params, reqs[0].prompt, 4)

    # park 2, crash 1: the wave must route only to instance 0
    system.prefill_pool.retire_engine(2)
    sched.set_prefill_live(2, False)
    system.prefill_pool.fail_engine(1)
    sched.set_prefill_live(1, False)
    got = {r.rid: list(r.tokens) for r in system.serve(reqs)}
    assert got == ref
    sched = system.scheduler
    assert sched.prefill_inflight_tokens == [0.0, 0.0, 0.0]
    assert all(t.prefill_instance == 0 for t in sched.traces.values())

    # revive: spawn prefers the parked id (2), then restarts the dead (1)
    assert system.prefill_pool.spawn_engine() == (2, True)
    sched.set_prefill_live(2, True)
    assert system.prefill_pool.spawn_engine() == (1, True)
    sched.set_prefill_live(1, True)
    got = {r.rid: list(r.tokens) for r in system.serve(reqs)}
    assert got == ref
    sched = system.scheduler
    assert sched.prefill_inflight_tokens == [0.0, 0.0, 0.0]
    assert {t.prefill_instance for t in sched.traces.values()} > {0}


def test_shed_requests_release_routed_load(granite):
    """Regression for the pre-fix leak: gate sheds and capacity rejects
    left their token-weighted load on the routed instance forever, skewing
    least_loaded away from it for the rest of the epoch."""
    cfg, params = granite
    rng = np.random.RandomState(11)
    reqs = [Request(i, list(rng.randint(0, 100, 10)), 4) for i in range(6)]
    reqs.append(Request(6, list(rng.randint(0, 100, 30)), 8))  # 30+7 > 32
    system = ServingSystem(params, cfg, prefill_engines=2, decode_batch=4,
                           capacity=32, policy="least_loaded",
                           tpot_budget_ms=6.0, admission="shed")
    results = system.serve(reqs)
    assert any(r.shed for r in results)          # the leak path exercised
    sched = system.scheduler
    assert sched.prefill_inflight_tokens == [0.0, 0.0]
    assert sched._routed_load == {}


def test_fault_recovery_releases_routed_load(granite):
    """The recover-then-finish (and recover-then-shed) path releases the
    routed load exactly once — idempotent by rid."""
    cfg, params = granite
    reqs = stream_requests(5, max_new=6)
    inj = FaultInjector(FaultPlan([
        FaultEvent("engine_crash", engine=1, at=0.004)]))
    system = ServingSystem(params, cfg, prefill_engines=2, decode_batch=2,
                           capacity=32, decode_engines=2,
                           policy="least_loaded", fault_injector=inj)
    results = system.serve(reqs)
    assert inj.crashes_fired == 1
    assert system.scheduler.summary()["recoveries"] >= 1
    assert not any(r.shed for r in results)
    assert system.scheduler.prefill_inflight_tokens == [0.0, 0.0]
    assert system.scheduler._routed_load == {}


# ---------------------------------------------------------------------------
# Pipelined chunked KV handoff: bit-identity + TTFT monotonicity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["granite-3-2b",     # dense GQA
                                  "deepseek-r1",      # MLA
                                  "olmoe-1b-7b"])     # MoE
def test_streamed_handoff_tokens_bit_identical(arch):
    """The streamed path rebuilds the decode cache from the bytes that
    crossed the wire, chunk by chunk — emitted tokens must match the
    synchronous whole-request handoff exactly, for every cache layout."""
    cfg = smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = stream_requests(3, prompt_len=10, max_new=4)
    system = ServingSystem(params, cfg, prefill_engines=2, decode_batch=2,
                           capacity=32)
    sync = {r.rid: list(r.tokens) for r in system.serve(reqs)}
    sync_ttft = {r: system.scheduler.traces[r].ttft for r in sync}
    system.reconfigure_scheduler(SchedulerConfig(stream_handoff=True,
                                                 stream_chunk=4))
    strm_res = system.serve(reqs)
    assert {r.rid: list(r.tokens) for r in strm_res} == sync
    sched = system.scheduler
    s = sched.summary()
    assert s["stream_requests"] == 3
    assert s["stream_chunks"] == 3 * 3           # 10 tokens = 2 full + tail
    assert s["stream_bytes"] > 0 and s["stream_max_chunk_bytes"] > 0
    for t in sched.traces.values():
        assert t.transfer_chunks == 3
        assert t.overlap_seconds >= 0.0
        assert t.transfer_seconds > 0.0          # last chunk's wire time
        assert t.ready_at == pytest.approx(t.prefill_end
                                           + t.transfer_seconds)
        assert t.ttft <= sync_ttft[t.rid] + 1e-12


def test_streamed_ttft_monotonically_better(granite):
    """Open-loop burst: per-request virtual-clock TTFT under streaming is
    never worse than synchronous handoff, and strictly better somewhere
    (the hidden transfer time is real)."""
    cfg, params = granite
    rng = np.random.RandomState(7)
    reqs = [Request(i, list(rng.randint(0, 100, 16)), 3, arrival=2e-4 * i)
            for i in range(6)]
    system = ServingSystem(params, cfg, prefill_engines=2, decode_batch=4,
                           capacity=48)
    sync_res = system.serve(reqs, open_loop=True)
    sync = {r.rid: system.scheduler.traces[r.rid].ttft for r in sync_res}
    system.reconfigure_scheduler(SchedulerConfig(stream_handoff=True,
                                                 stream_chunk=4))
    strm_res = system.serve(reqs, open_loop=True)
    strm = {r.rid: system.scheduler.traces[r.rid].ttft for r in strm_res}
    assert [r.tokens for r in strm_res] == [r.tokens for r in sync_res]
    assert all(strm[r] <= sync[r] + 1e-12 for r in sync)
    assert any(strm[r] < sync[r] - 1e-12 for r in sync)
    assert np.median(list(strm.values())) < np.median(list(sync.values()))
    assert system.scheduler.summary()["stream_overlap_s"] > 0


def test_hybrid_arch_falls_back_to_synchronous_handoff():
    """Ring-buffer SSM state has no per-position KV to stream: the gate
    keeps hybrids on the synchronous path even when streaming is on."""
    cfg = smoke("zamba2-1.2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = stream_requests(2, prompt_len=10, max_new=3)
    system = ServingSystem(params, cfg, prefill_engines=1, decode_batch=2,
                           capacity=32, stream_handoff=True, stream_chunk=4)
    ref = {r.rid: greedy_reference(cfg, params, r.prompt, r.max_new_tokens)
           for r in reqs}
    got = {r.rid: list(r.tokens) for r in system.serve(reqs)}
    assert got == ref
    s = system.scheduler.summary()
    assert s["stream_requests"] == 0 and s["stream_chunks"] == 0


# ---------------------------------------------------------------------------
# Joint P/D autoscaler end-to-end: the capacity see-saw
# ---------------------------------------------------------------------------


def _phase_skewed_burst(cfg):
    """Prefill-heavy opening (long prompts, 2-token generations), then a
    decode-heavy phase (short prompts, long generations)."""
    rng = np.random.RandomState(3)
    reqs = [Request(i, list(rng.randint(0, cfg.vocab_size, 48)), 2,
                    arrival=5e-4 * i) for i in range(8)]
    reqs += [Request(100 + i, list(rng.randint(0, cfg.vocab_size, 6)), 24,
                     arrival=0.15 + 2e-4 * i) for i in range(8)]
    return reqs


def test_joint_autoscaler_shifts_both_ways_tokens_identical(granite):
    cfg, params = granite
    reqs = _phase_skewed_burst(cfg)
    kw = dict(prefill_engines=1, decode_batch=2, capacity=96,
              decode_engines=2)
    ref_sys = ServingSystem(params, cfg, **kw)
    ref = {r.rid: list(r.tokens) for r in ref_sys.serve(reqs,
                                                        open_loop=True)}
    system = ServingSystem(params, cfg, joint_autoscale=True,
                           min_prefill=1, max_prefill=3,
                           min_engines=1, max_engines=3,
                           ttft_budget_ms=2.0, tpot_budget_ms=6.0,
                           admission="queue", **kw)
    got = {r.rid: list(r.tokens) for r in system.serve(reqs,
                                                       open_loop=True)}
    assert got == ref                      # the see-saw never alters tokens
    s = system.scheduler.summary()
    assert s["shifts_d2p"] >= 1 and s["shifts_p2d"] >= 1
    counts = [n for _, n in s["prefill_count_timeline"]]
    assert max(counts) >= 2 and min(counts) == 1
    shifts = [e for e in system.scheduler.scale_events
              if e["action"].startswith("shift_")]
    assert all(e["role"] == "joint" for e in shifts)
    # the prefill phase pulls capacity d2p before decode pulls it back
    first_d2p = min(e["t"] for e in shifts if e["action"] == "shift_d2p")
    last_p2d = max(e["t"] for e in shifts if e["action"] == "shift_p2d")
    assert first_d2p < last_p2d
    # conservation inside the clamp: every event stamps both role counts
    for e in shifts:
        assert 1 <= e["prefill_live"] <= 3
        assert 1 <= e["engines_live"] <= 3
    assert system.scheduler.prefill_inflight_tokens \
        == [0.0] * system.prefill_pool.n
