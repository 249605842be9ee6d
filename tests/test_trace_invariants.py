"""Scheduler trace invariants that previously went unchecked: per-request
virtual-clock monotonicity, decode_tokens == emitted tokens under chunked
decode and MTP, seed-determinism of the Poisson workload and of
admission-gate decisions, and the MTP acceptance-rate feedback loop."""
import jax
import numpy as np
import pytest

from conftest import smoke
from repro.core import init_mtp_params
from repro.models import init_params
from repro.serving import (DecodeCostModel, DecodeSlotManager, Request,
                           Scheduler, SchedulerConfig, ServingSystem,
                           poisson_requests)


@pytest.fixture(scope="module")
def granite():
    cfg = smoke("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def granite_mtp_system(granite):
    cfg, params = granite
    mtp = init_mtp_params(jax.random.PRNGKey(2), cfg)
    return ServingSystem(params, cfg, n_prefill=1, decode_batch=2,
                         capacity=40, use_mtp=True, mtp_params=mtp)


def stream_requests(n, prompt_len=12, max_new=4, seed=1):
    rng = np.random.RandomState(seed)
    return [Request(i, list(rng.randint(0, 100, prompt_len)), max_new)
            for i in range(n)]


def assert_monotone(records):
    """arrival -> prefill -> KV-ready -> admit -> decode-end never rewinds."""
    for rec in records:
        if rec["shed"]:
            continue
        assert rec["arrival"] <= rec["prefill_start"] <= rec["prefill_end"]
        ready = rec["prefill_end"] + rec["transfer_seconds"]
        assert rec["decode_admit"] >= ready - 1e-12
        assert rec["decode_end"] >= rec["decode_admit"]
        assert rec["decode_seconds"] >= 0 and rec["queue_seconds"] >= 0


# ---------------------------------------------------------------------------
# Virtual-clock monotonicity per request
# ---------------------------------------------------------------------------


def test_clock_monotone_closed_loop_pooled_chunked(granite):
    cfg, params = granite
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                           capacity=32, decode_engines=2,
                           decode_router="least_loaded_slots",
                           decode_chunk=2, decode_rebalance_every=1)
    results = system.serve(stream_requests(5, max_new=6))
    assert len(results) == 5
    assert_monotone(system.scheduler.trace_records())


def test_clock_monotone_open_loop_poisson(granite):
    cfg, params = granite
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                           capacity=32)
    reqs = poisson_requests(8, 300.0, 10, 4, 100, seed=11)
    system.serve(reqs, open_loop=True)
    recs = system.scheduler.trace_records()
    assert_monotone(recs)
    for rec in recs:                  # open loop: nothing precedes arrival
        assert rec["prefill_start"] >= rec["arrival"]


# ---------------------------------------------------------------------------
# decode_tokens in the trace == tokens the engine actually emitted
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("decode_chunk", [1, 3])
def test_trace_decode_tokens_sum_matches_emitted(granite, decode_chunk):
    cfg, params = granite
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                           capacity=32, decode_chunk=decode_chunk)
    results = system.serve(stream_requests(4, max_new=5))
    sched = system.scheduler
    for r in results:
        # prefill produced tokens[0]; every other token was a decode commit
        assert sched.traces[r.rid].decode_tokens == len(r.tokens) - 1
    assert sched.decode_token_count == sum(len(r.tokens) - 1
                                           for r in results)


def test_trace_decode_tokens_sum_matches_emitted_mtp(granite_mtp_system):
    """Under MTP an iteration may commit 2 tokens; the per-iteration credit
    must still sum exactly to what each request received."""
    system = granite_mtp_system
    results = system.serve(stream_requests(4, max_new=5, seed=9))
    sched = system.scheduler
    for r in results:
        tr = sched.traces[r.rid]
        assert tr.decode_tokens == len(r.tokens) - 1
        assert tr.decode_iters <= tr.decode_tokens    # speculation credits
    assert sched.decode_token_count == sum(len(r.tokens) - 1
                                           for r in results)


def test_open_loop_pool_decodes_concurrently(granite):
    """Idle engines' clocks track the busy frontier: an arrival landing
    while engine 0 decodes a long request must be admitted to idle
    engine 1 at its arrival time, not after the pool drains (the pool
    would otherwise serialize into bulk-synchronous waves open-loop)."""
    cfg, params = granite
    rng = np.random.RandomState(19)
    reqs = [Request(0, list(rng.randint(0, 100, 8)), 12, arrival=0.0),
            Request(1, list(rng.randint(0, 100, 8)), 3, arrival=5e-3)]
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=1,
                           capacity=32, decode_engines=2,
                           decode_router="least_loaded_slots")
    results = {r.rid: r for r in system.serve(reqs, open_loop=True)}
    assert len(results) == 2
    tr0, tr1 = system.scheduler.traces[0], system.scheduler.traces[1]
    assert (tr0.decode_engine, tr1.decode_engine) == (0, 1)
    # rid 1 decodes DURING rid 0's residency, not after it
    assert tr1.decode_admit < tr0.decode_end
    assert_monotone(system.scheduler.trace_records())


def test_open_loop_advances_to_fifo_head_ready_at(granite):
    """Livelock regression: with the decode pool idle, the clock must
    fast-forward to the FIFO *head's* KV-ready time. A later-arriving
    request with a shorter prompt (idler prefill instance) gets an earlier
    ready_at; advancing only to min-over-waiting left the head gated and
    the serve loop spinning on the same instant forever."""
    import signal

    cfg, params = granite
    rng = np.random.RandomState(17)
    reqs = [Request(0, list(rng.randint(0, 100, 60)), 3, arrival=0.0),
            Request(1, list(rng.randint(0, 100, 4)), 3, arrival=4e-4)]
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                           capacity=64)
    signal.alarm(120)              # fail loudly instead of hanging CI
    try:
        results = system.serve(reqs, open_loop=True)
    finally:
        signal.alarm(0)
    assert sorted(r.rid for r in results) == [0, 1]
    assert all(len(r.tokens) == 3 for r in results)
    recs = system.scheduler.trace_records()
    assert_monotone(recs)
    # the head (long prefill) really was the later-ready request
    assert recs[0]["prefill_end"] + recs[0]["transfer_seconds"] > \
        recs[1]["prefill_end"] + recs[1]["transfer_seconds"]


# ---------------------------------------------------------------------------
# Determinism given a seed
# ---------------------------------------------------------------------------


def test_poisson_requests_seed_determinism():
    a = poisson_requests(16, 250.0, 12, 4, 500, seed=42, shared_prefix=4)
    b = poisson_requests(16, 250.0, 12, 4, 500, seed=42, shared_prefix=4)
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert [r.prompt for r in a] == [r.prompt for r in b]
    c = poisson_requests(16, 250.0, 12, 4, 500, seed=43, shared_prefix=4)
    assert [r.arrival for r in a] != [r.arrival for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_admission_decisions_deterministic_given_seed(granite):
    """Replaying the same seeded Poisson burst through the same system
    yields byte-identical traces — shed/queue decisions included."""
    cfg, params = granite
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=4,
                           capacity=32, tpot_budget_ms=5.5,
                           admission="shed")

    def run():
        reqs = poisson_requests(10, 400.0, 10, 4, 100, seed=21)
        results = system.serve(reqs, open_loop=True)
        shed = sorted(r.rid for r in results if r.shed)
        return shed, system.scheduler.trace_records()

    shed_a, recs_a = run()
    shed_b, recs_b = run()
    assert shed_a == shed_b and shed_a     # the gate actually shed
    assert recs_a == recs_b                # floats equal: same ops, same seed


# ---------------------------------------------------------------------------
# Acceptance-rate feedback into DecodeCostModel.mtp_accept
# ---------------------------------------------------------------------------


def _simulated_wave(sched, accept_tokens_per_iter, iters=4):
    tr = sched.on_arrival(0, 0.0, 8)
    sched.on_prefill_done(tr, 0, 8, 0)
    sched.on_transfer(tr, 0.0)
    sched.slot_mgr.allocate(0, 8)
    sched.on_admit(tr, 0)
    for i in range(iters):
        fin = [0] if i == iters - 1 else []
        sched.on_decode_step([0], fin, {0: accept_tokens_per_iter})
    sched.slot_mgr.release(0)


def test_mtp_feedback_expands_gate_after_high_acceptance_wave():
    cfg = SchedulerConfig(use_mtp=True, tpot_budget_ms=10.0,
                          admission="queue")
    sched = Scheduler(1, DecodeSlotManager(8, 64), cfg)
    cap0 = sched.gate.max_batch            # sized for the paper's α = 0.70
    assert sched.cost.mtp_accept == DecodeCostModel.MTP_ACCEPT
    _simulated_wave(sched, accept_tokens_per_iter=2)   # perfect acceptance
    assert sched.feedback_mtp_acceptance() == pytest.approx(1.0)
    assert sched.cost.mtp_accept == pytest.approx(1.0)
    assert sched.gate.max_batch > cap0     # more tokens/iter => bigger batch

    # and a dismal wave shrinks it below the paper default
    sched.begin_epoch()
    _simulated_wave(sched, accept_tokens_per_iter=1)   # nothing accepted
    assert sched.feedback_mtp_acceptance() == pytest.approx(0.0)
    assert sched.gate.max_batch < cap0


def test_mtp_feedback_noop_without_mtp_or_data():
    sched = Scheduler(1, DecodeSlotManager(4, 64),
                      SchedulerConfig(tpot_budget_ms=10.0))
    assert sched.feedback_mtp_acceptance() is None     # not an MTP system
    sched_mtp = Scheduler(1, DecodeSlotManager(4, 64),
                          SchedulerConfig(use_mtp=True))
    assert sched_mtp.feedback_mtp_acceptance() is None  # no finished trace


def test_mtp_feedback_applied_end_to_end(granite_mtp_system):
    """ServingSystem folds the measured acceptance back into the cost model
    after each wave: cost.mtp_accept equals the trace-derived rate."""
    system = granite_mtp_system
    results = system.serve(stream_requests(3, max_new=5, seed=13))
    sched = system.scheduler
    iters = sum(t.decode_iters for t in sched.tracker.finished)
    toks = sum(t.decode_tokens for t in sched.tracker.finished)
    assert iters > 0
    measured = min(1.0, max(0.0, toks / iters - 1.0))
    assert sched.cost.mtp_accept == pytest.approx(measured)
    assert len(results) == 3


# ---------------------------------------------------------------------------
# Preempt-then-resume invariants (SLO-class overload control)
# ---------------------------------------------------------------------------


def _overload_requests(seed=7, n_batch=6, n_interactive=4):
    """Batch flood first, interactive arriving mid-decode: forces the gate
    to preempt batch slots when preemption is enabled."""
    rng = np.random.RandomState(seed)
    reqs = [Request(i, list(rng.randint(0, 100, 12)), 6,
                    arrival=5e-4 * i, slo_class="batch")
            for i in range(n_batch)]
    reqs += [Request(100 + i, list(rng.randint(0, 100, 12)), 4,
                     arrival=4e-3 + 2e-3 * i, slo_class="interactive")
             for i in range(n_interactive)]
    return reqs


@pytest.fixture(scope="module")
def overload_runs(granite):
    """The overload burst served with class-aware control (6 ms interactive
    budget, 30 ms batch budget, batch preemption) and class-blind with the
    gate open: ``(controlled system, its results, blind system, its
    results)``."""
    cfg, params = granite
    reqs = _overload_requests()

    def run(class_aware):
        kw = dict(n_prefill=2, decode_batch=3, capacity=64)
        if class_aware:
            kw.update(tpot_budget_ms=6.0, batch_tpot_budget_ms=30.0,
                      preempt_batch=True)
        system = ServingSystem(params, cfg, **kw)
        results = system.serve(list(reqs), open_loop=True)
        return system, results

    return (*run(class_aware=True), *run(class_aware=False))


def test_preempt_resume_token_identical_and_monotone(overload_runs):
    """A preempted-then-resumed batch request finishes token-identical to
    the uncontended run, its per-request clock stays monotone through the
    preemption, and DecodeSlotManager acquired/released conservation holds
    across every evict/re-admit cycle."""
    controlled, res_c, _, res_r = overload_runs
    sched = controlled.scheduler
    preempted = [t.rid for t in sched.traces.values() if t.preemptions > 0]
    assert preempted, "scenario must actually preempt"
    assert sched.preemptions >= len(preempted)
    assert all(sched.traces[rid].slo_class == "batch" for rid in preempted)
    # Token identity: every preempted request's tokens match the
    # uncontended (class-blind) reference run exactly.
    tok_c = {r.rid: r.tokens for r in res_c if not r.shed}
    tok_r = {r.rid: r.tokens for r in res_r if not r.shed}
    for rid in preempted:
        assert not tok_c[rid] == [] and tok_c[rid] == tok_r[rid]
    # Monotone per-request clocks through the preemption; the preemption
    # latency is charged to the trace.
    assert_monotone(sched.trace_records())
    for rid in preempted:
        tr = sched.traces[rid]
        assert tr.preempt_seconds > 0
        assert tr.decode_end >= tr.decode_admit
    # Slot conservation: every acquire (admission + re-admission) has a
    # matching release (preemption eviction + finish) once the wave drains.
    for mgr in controlled.pool.slot_mgrs:
        assert mgr.acquired == mgr.released
        assert mgr.active == 0
    # Preemption must not have shed anyone in queue mode.
    assert sched.tracker.summary()["shed"] == 0


def test_class_aware_control_holds_interactive_tpot_p99(overload_runs):
    """On the identical overload burst, class-aware control (per-class
    budgets, strict priority, batch preemption) holds the interactive TPOT
    p99 inside its 6 ms budget, which the class-blind run breaks, and every
    request is served."""
    controlled, res_c, blind, res_b = overload_runs

    def interactive_p99_ms(system):
        s = system.scheduler.summary()
        return s.get("classes", {}).get("interactive", s)["tpot_p99_s"] * 1e3

    assert interactive_p99_ms(controlled) <= 6.0 + 1e-6
    assert interactive_p99_ms(blind) > 6.0 + 1e-6
    assert controlled.scheduler.summary()["preemptions"] >= 1
    assert len(res_c) == len(res_b) == 10
    assert not any(r.shed for r in res_c + res_b)


def test_preempt_composes_with_continuous_batching_and_chunk(granite):
    """Preemption through width-2 adaptive scans keeps the same
    invariants: conservation, monotone traces, completion."""
    cfg, params = granite
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=3,
                           capacity=64, decode_chunk=2,
                           tpot_budget_ms=6.0, batch_tpot_budget_ms=30.0,
                           preempt_batch=True)
    results = system.serve(_overload_requests(seed=23), open_loop=True)
    sched = system.scheduler
    assert len(results) == 10 and not any(r.shed for r in results)
    assert_monotone(sched.trace_records())
    for mgr in system.pool.slot_mgrs:
        assert mgr.acquired == mgr.released and mgr.active == 0
