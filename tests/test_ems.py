"""EMS service semantics: tiered fetch/store, write-back conservation,
cost-aware eviction, pool-wide dedup, eviction-race regression, hit-aware
admission, EMS-backed affinity routing — plus bit-exactness of EMS reuse
across the dense/MLA/MoE families and a multi-turn session soak."""
import hashlib
import os

import jax
import numpy as np
import pytest

from conftest import smoke
from repro.mempool import ContextCache, EMSService, MemoryPool
from repro.mempool.ems import _slab_bytes
from repro.mempool.pool import HUGE_PAGE
from repro.models import init_params
from repro.serving import Request, ServingSystem
from repro.serving.pool import CacheAffinityRouter, make_decode_router
from repro.serving.scheduler import (AdmissionGate, DecodeCostModel,
                                     RequestTrace, Scheduler,
                                     SchedulerConfig)
from repro.serving.workload import multi_turn_sessions


def _purge(pool: MemoryPool, key: str) -> None:
    """Evict ``key`` from every tier of every MP server behind the cache's
    back — the eviction-race scenario."""
    for srv in pool.servers:
        for tier, used in (("dram", "dram_used"), ("ssd", "ssd_used")):
            store = getattr(srv, tier)
            if key in store:
                _, nbytes, _ = store.pop(key)
                setattr(srv, used, getattr(srv, used) - srv._slabs(nbytes))


def _blocks(tokens, block, scale=1.0):
    """Deterministic synthetic per-block payloads (token-derived)."""
    return [np.asarray(tokens[b * block:(b + 1) * block], np.float32) * scale
            for b in range(len(tokens) // block)]


# ---------------------------------------------------------------------------
# satellite regressions: eviction race + per-request key memo
# ---------------------------------------------------------------------------


def test_fetch_eviction_race_is_graceful_miss():
    """match_prefix says hit, the pool evicts before fetch: the old code
    hard-asserted; now the fetch returns the still-resolvable prefix and
    the caller recomputes the suffix."""
    pool = MemoryPool(n_nodes=2)
    cc = ContextCache(pool, block_tokens=4, model_tag="race")
    toks = list(range(8))
    cc.store(toks, _blocks(toks, 4))
    matched, keys = cc.match_prefix(toks)
    assert matched == 8
    _purge(pool, keys[1])
    got = cc.fetch(keys)               # no AssertionError
    assert len(got) == 1
    assert cc.fetch_misses == 1
    np.testing.assert_array_equal(got[0], _blocks(toks, 4)[0])


def test_ems_fetch_race_repairs_index():
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4, model_tag="race")
    toks = list(range(8))
    ems.store(toks, _blocks(toks, 4))
    ems.flush()
    _, keys = ems.match_prefix(toks)
    ems.drop_engine("shared")          # force the pool path
    _purge(ems.pool, keys[0])
    assert ems.fetch(keys) == []
    assert ems.fetch_misses == 1 and ems.index_repairs == 1
    # index repaired: the vanished block no longer advertises a match
    assert ems.match_prefix(toks)[0] == 0


def test_block_keys_hashed_once_per_prompt():
    """The sha256 prefix chain runs once per request, not once per
    block_keys/match_prefix/store call."""
    cc = ContextCache(MemoryPool(n_nodes=2), block_tokens=4, model_tag="m")
    toks = list(range(16))
    k1 = cc.block_keys(toks)
    cc.match_prefix(toks)
    cc.store(toks, _blocks(toks, 4))
    cc.match_prefix(toks)
    assert cc.hash_calls == 1
    assert cc.block_keys(toks) == k1
    cc.block_keys(list(range(1, 17)))
    assert cc.hash_calls == 2          # different prompt, fresh chain


# ---------------------------------------------------------------------------
# tier mechanics: write-back conservation, cost-aware eviction, dedup
# ---------------------------------------------------------------------------


def test_writeback_byte_conservation_and_flush():
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4, model_tag="wb")
    toks = list(range(24))
    ems.store(toks, _blocks(toks, 4), engine="prefill0")
    assert ems.ems_stats()["pending_demotes"] > 0
    ems.flush()
    stats = ems.ems_stats()
    assert stats["pending_demotes"] == 0
    assert stats["demote_blocks"] == 6
    assert ems.demote_bytes == ems.transfer.bytes_demoted > 0
    # every demotion also landed in the pooled tier
    for k in ems.block_keys(toks):
        assert ems.pool.get(k) is not None
    # fetch from a different engine promotes over the RDMA plane
    got = ems.fetch(ems.block_keys(toks), engine="decode1")
    assert len(got) == 6
    assert ems.promote_bytes == ems.transfer.bytes_promoted > 0
    # and a re-fetch is a device-local HBM hit (no new promotes)
    before = ems.promote_blocks
    ems.fetch(ems.block_keys(toks), engine="decode1")
    assert ems.promote_blocks == before


def test_cost_aware_eviction_is_not_lru():
    """Two resident blocks, the older one expensive to refetch, the newer
    one cheap: inserting a third must evict the cheap *newer* block (lowest
    retention value per slab byte) where LRU would kill the older one —
    and a dirty victim is demoted, never dropped."""
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4, model_tag="ev",
                     hbm_capacity_bytes=2 * HUGE_PAGE)
    toks = list(range(12))
    keys = ems.block_keys(toks)
    big = np.zeros(1 << 18, np.float32)          # 1 MiB, still one slab
    small = np.zeros(16, np.float32)             # cheap to refetch
    assert _slab_bytes(big.nbytes) == _slab_bytes(small.nbytes) == HUGE_PAGE
    ems.store(toks, [big, small, small], engine="e0")
    assert ems.hbm_evictions == 1
    # the expensive old block survived; the cheap middle block was evicted
    assert ems.engine_residency("e0", keys[:1]) == 1
    assert keys[1] not in ems._hbm["e0"]
    # dirty victim was written back, not lost: fetch recovers all three
    got = ems.fetch(keys, engine="e0")
    assert len(got) == 3
    np.testing.assert_array_equal(got[1], small)


def test_pool_wide_dedup_across_engines():
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4, model_tag="dd")
    toks = list(range(16))
    assert ems.store(toks, _blocks(toks, 4), engine="prefill0") == 4
    assert ems.store(toks, _blocks(toks, 4), engine="prefill1") == 0
    assert ems.dedup_skipped == 4
    # dedup is advertised pool-wide before any demotion happened
    assert ems.match_prefix(toks)[0] == 16


def test_match_prefix_does_not_mutate_pool_lru():
    """MemoryPool.contains promotes/reorders (it is a get); the EMS index
    probe must not — a thousand probes leave the server LRU untouched."""
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4, model_tag="lru")
    toks = list(range(8))
    ems.store(toks, _blocks(toks, 4))
    ems.flush()
    orders = [list(srv.dram.keys()) for srv in ems.pool.servers]
    for _ in range(1000):
        ems.match_prefix(toks)
        ems.probe_prefix(toks)
    assert [list(srv.dram.keys()) for srv in ems.pool.servers] == orders


# ---------------------------------------------------------------------------
# hit-aware admission
# ---------------------------------------------------------------------------


def test_hit_aware_gate_default_is_bit_identical():
    cost = DecodeCostModel()
    blind = AdmissionGate(cost, 6e-3, "queue")
    aware = AdmissionGate(cost, 6e-3, "queue", hit_aware=True)
    for active in range(5):
        assert aware.decide(active, True) == blind.decide(active, True)
    assert aware.decide(0, False) == blind.decide(0, False) == "wait"


def test_hit_aware_gate_admits_cached_suffix():
    cost = DecodeCostModel()           # cap 2 at the 6 ms budget
    blind = AdmissionGate(cost, 6e-3, "queue")
    aware = AdmissionGate(cost, 6e-3, "queue", hit_aware=True)
    assert blind.decide(2, True) == "wait"
    # two 90%-cached residents + a 90%-cached joiner: 0.3 of a slot
    assert aware.decide(2, True, load=0.2, charge=0.1) == "admit"
    # cold joiner at a saturated suffix-load still waits
    assert aware.decide(2, True, load=1.2, charge=1.0) == "wait"
    # brownout shed override wins regardless of charges
    assert aware.decide(2, True, "batch", mode_override="shed",
                        load=0.2, charge=0.1) == "shed"


def test_scheduler_suffix_charge():
    sched = Scheduler(1, __import__("repro.serving.scheduler",
                                    fromlist=["DecodeSlotManager"]
                                    ).DecodeSlotManager(2, 32),
                      SchedulerConfig(hit_aware_admission=True))
    tr = RequestTrace(rid=0, arrival=0.0, prompt_tokens=10)
    assert sched.suffix_charge(tr) == pytest.approx(1.0)
    tr.cached_tokens = 8
    assert sched.suffix_charge(tr) == pytest.approx(0.2)
    tr.reused_tokens = 10              # clamped to prompt - 1
    assert sched.suffix_charge(tr) == pytest.approx(0.1)


def test_hit_aware_serving_end_to_end():
    """A warm EMS + hit-aware gate admits a third mostly-cached request
    into a cap-2 batch; the blind gate serves the identical stream but
    holds it (strictly later decode admit)."""
    cfg = smoke("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    shared = list(rng.randint(0, cfg.vocab_size, 16))
    prompts = [shared + list(rng.randint(0, cfg.vocab_size, 2))
               for _ in range(3)]
    reqs = [Request(i, p, 4, arrival=1e-4 * i)
            for i, p in enumerate(prompts)]

    def run(hit_aware):
        ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4,
                         model_tag=cfg.name)
        system = ServingSystem(
            params, cfg, n_prefill=1, decode_batch=3, capacity=32,
            context_cache=ems, tpot_budget_ms=6.0, admission="queue",
            hit_aware_admission=hit_aware or None)
        system.serve([Request(9 + i, list(p), 2)     # warm the cache
                      for i, p in enumerate(prompts)])
        results = system.serve([Request(r.rid, list(r.prompt),
                                        r.max_new_tokens, arrival=r.arrival)
                                for r in reqs], open_loop=True)
        return results, system.scheduler

    res_a, sched_a = run(True)
    res_b, sched_b = run(False)
    # token identity: hit-aware admission only reorders time, not tokens
    for ra, rb in zip(sorted(res_a, key=lambda r: r.rid),
                      sorted(res_b, key=lambda r: r.rid)):
        assert ra.tokens == rb.tokens
    last = max(r.rid for r in reqs)
    assert sched_a.traces[last].decode_admit \
        < sched_b.traces[last].decode_admit, \
        "hit-aware gate did not admit the cached request earlier"


# ---------------------------------------------------------------------------
# EMS-backed affinity routing
# ---------------------------------------------------------------------------


def test_router_residency_derived_from_ems():
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4, model_tag="rt")
    toks = list(range(16))
    keys = ems.block_keys(toks)
    router = make_decode_router("cache_affinity", 2, ems=ems)
    assert isinstance(router, CacheAffinityRouter) and router.ems is ems
    router.on_admit(1, keys)
    assert router.residency(1, keys) == 4 and router.residency(0, keys) == 0
    assert router.select([0, 0], [2, 2], keys) == 1
    # migration moves the affinity signal with the bytes
    router.on_migrate(0, keys)
    assert router.residency(0, keys) == 4
    # retire drops the device tier but cached prefixes survive in the pool
    ems.store(toks, _blocks(toks, 4), engine="decode1")
    router.on_retire(1)
    assert router.residency(1, keys) == 0
    assert ems.match_prefix(toks)[0] == 16
    assert len(ems.fetch(keys, engine="decode0")) == 4


def test_make_decode_router_ignores_ems_for_locality_free():
    r = make_decode_router("least_loaded_slots", 2, ems=object())
    assert not r.uses_affinity


# ---------------------------------------------------------------------------
# bit-exactness: EMS reuse at any tier == cold recompute (dense/MLA/MoE)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["granite-3-2b", "deepseek-r1",
                                  "olmoe-1b-7b"])
def test_ems_reuse_bit_exact(arch):
    cfg = smoke(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(4)
    shared = list(rng.randint(0, cfg.vocab_size, 12))
    prompts = [shared + list(rng.randint(0, cfg.vocab_size, 4))
               for _ in range(3)]

    def serve(system, rid0=0):
        return sorted(system.serve(
            [Request(rid0 + i, list(p), 4) for i, p in enumerate(prompts)]),
            key=lambda r: r.rid)

    plain = serve(ServingSystem(params, cfg, n_prefill=1, decode_batch=3,
                                capacity=40))
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4,
                     model_tag=cfg.name)
    system = ServingSystem(params, cfg, n_prefill=1, decode_batch=3,
                           capacity=40, context_cache=ems)
    warm = serve(system)
    assert any(r.reused_tokens > 0 for r in warm), "no reuse happened"
    for rp, rw in zip(plain, warm):
        assert rp.tokens == rw.tokens
    # force the deepest path: device tiers dropped, blocks re-promoted
    # from the pooled tier — still bit-exact
    ems.flush()
    for tag in list(ems._hbm):
        ems.drop_engine(tag)
    deep = serve(system, rid0=10)
    assert ems.pool_hits > 0, "pooled tier never served a block"
    for rp, rd in zip(plain, deep):
        assert rp.tokens == rd.tokens


def test_ems_cache_miss_path_token_identical_to_plain():
    """An EMS that never hits (every prompt unique, index empty) must be
    a pure pass-through: tokens identical to the cache-less system."""
    cfg = smoke("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, 10)) for _ in range(3)]
    plain = ServingSystem(params, cfg, n_prefill=1, decode_batch=3,
                          capacity=32)
    res_p = plain.serve([Request(i, list(p), 4)
                         for i, p in enumerate(prompts)])
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4,
                     model_tag=cfg.name)
    cached = ServingSystem(params, cfg, n_prefill=1, decode_batch=3,
                           capacity=32, context_cache=ems)
    res_c = cached.serve([Request(i, list(p), 4)
                          for i, p in enumerate(prompts)])
    assert all(r.reused_tokens == 0 for r in res_c)
    for rp, rc in zip(sorted(res_p, key=lambda r: r.rid),
                      sorted(res_c, key=lambda r: r.rid)):
        assert rp.tokens == rc.tokens


# ---------------------------------------------------------------------------
# multi-turn session soak through the EMS tier (control-plane only)
# ---------------------------------------------------------------------------

SOAK_SESSIONS = int(os.environ.get("EMS_SOAK_SESSIONS", "40"))


def _drive_sessions(n_sessions, turns, seed):
    """Drive a session trace through a bare EMSService with synthetic
    payloads on the virtual clock; returns (per-request rows, ems)."""
    ems = EMSService(MemoryPool(n_nodes=4), block_tokens=4,
                     model_tag="soak",
                     hbm_capacity_bytes=64 * HUGE_PAGE)
    reqs = multi_turn_sessions(n_sessions, seed=seed, vocab_size=1000,
                               session_rate_rps=50.0, turns=turns,
                               turn_tokens_median=10, turn_tokens_max=24,
                               max_new_median=4, max_new_max=8)
    rows = []
    for r in reqs:
        tag = f"prefill{r.rid % 2}"
        t0 = ems.pool.clock.elapsed
        matched, keys = ems.match_prefix(r.prompt)
        reuse = min(matched, len(r.prompt) - 1)
        reuse -= reuse % ems.block
        flats = ems.fetch(keys[: reuse // ems.block], engine=tag)
        reuse = len(flats) * ems.block
        ttft = (len(r.prompt) - reuse) * 2e-4 \
            + (ems.pool.clock.elapsed - t0)
        n_blocks = len(r.prompt) // ems.block
        ems.store(r.prompt[: n_blocks * ems.block],
                  _blocks(r.prompt, ems.block), engine=tag)
        rows.append((r.rid, len(r.prompt), reuse, round(ttft, 12)))
    ems.flush()
    return rows, reqs, ems


@pytest.mark.workload_soak
def test_ems_session_soak():
    turns = 3
    rows, reqs, ems = _drive_sessions(SOAK_SESSIONS, turns, seed=17)
    # 1) hit depth grows across turns
    frac = {t: [] for t in range(turns)}
    for rid, prompt, reuse, _ in rows:
        frac[rid % turns].append(reuse / prompt)
    means = [float(np.mean(frac[t])) for t in range(turns)]
    assert means[0] == 0.0
    assert means[1] > 0.3 and means[2] > means[1], \
        f"hit depth did not grow across turns: {means}"
    # 2) promote/demote byte conservation against the RDMA-plane books
    assert ems.demote_bytes == ems.transfer.bytes_demoted > 0
    assert ems.promote_bytes == ems.transfer.bytes_promoted
    assert ems.ems_stats()["pending_demotes"] == 0
    # 3) TTFT per prompt token improves with hit depth
    cold = [t / p for _, p, re, t in rows if re == 0]
    deep = [t / p for _, p, re, t in rows if re / p >= 0.5]
    assert deep, "no deep hits at soak scale"
    assert float(np.mean(deep)) < float(np.mean(cold)), \
        "deep EMS hits did not lower per-token TTFT"
    # 4) bit-stable: the whole trajectory digests identically on a re-run
    def digest(rows):
        h = hashlib.sha256()
        for row in rows:
            h.update(repr(row).encode())
        return h.hexdigest()
    rows2, _, _ = _drive_sessions(SOAK_SESSIONS, turns, seed=17)
    assert digest(rows) == digest(rows2)


@pytest.mark.workload_soak
def test_ems_session_soak_through_serving_system():
    """The same session shape through the full ServingSystem at smoke
    scale: reuse grows across turns and reused+computed always accounts
    for the prompt."""
    cfg = smoke("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = multi_turn_sessions(2, seed=13, vocab_size=cfg.vocab_size,
                               session_rate_rps=200.0, turns=3,
                               turn_tokens_median=8, turn_tokens_max=10,
                               max_new_median=3, max_new_max=4)
    cap = max(len(r.prompt) + r.max_new_tokens for r in reqs) + 8
    ems = EMSService(MemoryPool(n_nodes=2), block_tokens=4,
                     model_tag=cfg.name)
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=4,
                           capacity=cap, decode_engines=2,
                           decode_router="cache_affinity",
                           context_cache=ems, hit_aware_admission=True)
    results = system.serve(reqs, open_loop=True)
    served = [r for r in results if not r.shed]
    assert len(served) == len(reqs)
    by_rid = {r.rid: r for r in served}
    turn_frac = {0: [], 1: [], 2: []}
    for q in reqs:
        r = by_rid[q.rid]
        assert r.reused_tokens + r.computed_tokens == len(q.prompt)
        turn_frac[q.rid % 3].append(r.reused_tokens / len(q.prompt))
    assert np.mean(turn_frac[0]) == 0.0
    assert np.mean(turn_frac[2]) > np.mean(turn_frac[0])
    assert ems.ems_stats()["hit_rate"] > 0
    # promote/demote bytes agree with the RDMA-plane transfer books
    ems.flush()
    assert ems.demote_bytes == ems.transfer.bytes_demoted > 0
    assert ems.promote_bytes == ems.transfer.bytes_promoted
