"""Shared helpers for the per-table benchmarks.

These benchmarks measure no device time. Each derives its table from (a)
functional runs of the real system on the smoke config, on whatever device
JAX finds, and (b) the compiled dry-run artifacts (experiments/dryrun/*.json)
priced with v5e peak constants, which are estimates, not measurements.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

PEAK_FLOPS = 197e12       # bf16/chip (v5e-class)
PEAK_INT8 = 394e12        # int8 ≈ 2× bf16 on MXU
HBM_BW = 819e9
ICI_BW = 50e9             # per link
ICI_LINKS = 4

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")


def load_dryrun(arch: str, shape: str, mesh: str = "16x16") -> Optional[Dict]:
    fn = os.path.join(DRYRUN_DIR, f"{arch}__{shape}__{mesh}.json")
    if not os.path.exists(fn):
        return None
    with open(fn) as f:
        rec = json.load(f)
    return rec if rec.get("status") == "ok" else None


def ensure_dryrun(arch: str, shape: str, mesh: str = "16x16") -> Optional[Dict]:
    """Load a dry-run record, running it on demand in a child process
    (it needs 512 placeholder CPU devices; dryrun.py pins the child to the
    CPU so it never claims an accelerator this process holds). A child
    that fails raises here instead of leaving the caller on placeholder
    costs."""
    rec = load_dryrun(arch, shape, mesh)
    if rec is not None:
        return rec
    import subprocess
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape]
    if mesh == "2x16x16":
        cmd.append("--multi-pod")
    env = dict(os.environ, PYTHONPATH=src)
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=580)
    if r.returncode != 0:
        raise RuntimeError(
            f"dry run {arch} x {shape} x {mesh} exited {r.returncode}:\n"
            f"{r.stderr[-2000:]}")
    return load_dryrun(arch, shape, mesh)


def step_time_from_record(rec: Dict, overlap_collectives: bool = False) -> float:
    """Roofline step time: serial sum or max-overlap of the three terms."""
    c, m, k = rec["compute_s"], rec["memory_s"], rec["collective_s"]
    if overlap_collectives:
        return max(c + m, k)
    return max(c, m) + k


def emit(name: str, metric: str, value, derived: str = "") -> None:
    print(f"{name},{metric},{value},{derived}")


REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def write_bench_artifact(name: str, payload: Dict, schema: int = 7) -> str:
    """Persist a benchmark record as BENCH_<name>.json at the repo root so
    the perf trajectory is trackable PR-over-PR. Schema 2 added the MTP
    section (acceptance rate + speedup) to the decode artifact; schema 3
    added the decode-pool section (per-engine throughput + routing policy +
    migration counts); schema 4 added the pool autoscale section
    (engine-count timeline + scale-event counts + fixed-pool token
    identity); schema 5 added the continuous-batching section
    (dead_slot_rate before/after, mid-scan refill counts, per-step token
    identity); schema 6 added the fault-tolerance section (engine failures,
    replay recoveries, transfer retries, recovery-TTFT percentiles, and
    token identity of the faulted run against its fault-free reference);
    schema 7 adds the slo_classes section (per-class TPOT under a mixed
    overload burst with vs without class-aware control, batch preemption
    counts, preempt-resume token identity, brownout transitions); schema 8
    (prefill artifact) adds the handoff_overlap section (streamed vs
    synchronous TTFT split under pipelined chunked KV streaming, transfer
    bytes in flight, token identity of the two paths); schema 9 (prefill
    artifact) adds the ems section (multi-turn session hit rate by turn,
    promote/demote bytes through the shared EMS tier, TTFT split by hit
    depth, analytic UB-vs-VPC reuse gain, and the hit-aware admission
    demo: a mostly-cached request admitted where the suffix-blind gate
    waits)."""
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump({"schema": schema, "bench": name, **payload}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    return path


def update_bench_artifact(name: str, extra: Dict, schema: int = 7) -> str:
    """Merge ``extra`` into an existing BENCH_<name>.json (or start a fresh
    one) — benches that contribute sections to a shared artifact (bench_mtp
    -> BENCH_decode.json) use this instead of clobbering it."""
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    payload: Dict = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload.update(extra)
    payload.pop("schema", None)
    payload.pop("bench", None)
    return write_bench_artifact(name, payload, schema)


# ---------------------------------------------------------------------------
# DecodeCostModel calibration from the dry-run roofline records
# (ROADMAP open item — placeholder defaults only when no record exists).
# ---------------------------------------------------------------------------


def kv_bytes_per_request(cfg, context: int = 32768) -> float:
    """Per-request KV/latent cache bytes at `context` (bf16) — the strictly
    batch-proportional HBM traffic of one decode step."""
    if cfg.attention_kind == "mla":
        return cfg.num_layers * context * (cfg.kv_lora_rank
                                           + cfg.qk_rope_head_dim) * 2
    if cfg.attention_kind in ("causal", "bidirectional") and cfg.num_kv_heads:
        return cfg.num_layers * context * 2 * cfg.num_kv_heads \
            * cfg.head_dim * 2
    return 0.0


_calibrated_costs: Dict = {}


def calibrated_decode_cost(arch: str, shape: str = "decode_32k",
                           batch: int = 128):
    """DecodeCostModel from the arch's compiled dry-run record; falls back
    to the placeholder defaults when no record (or no KV traffic) exists.
    Memoized: live_smoke_serve calls this inside timed benchmark loops."""
    from repro.configs import get_config
    from repro.serving.scheduler import decode_cost_from_roofline

    key = (arch, shape, batch)
    if key not in _calibrated_costs:
        rec = load_dryrun(arch, shape)
        if rec is None:
            _calibrated_costs[key] = decode_cost_from_roofline(None, 0.0, 0.0)
        else:
            cfg = get_config(arch)
            _calibrated_costs[key] = decode_cost_from_roofline(
                rec, kv_bytes_per_request(cfg), batch / rec["n_devices"],
                HBM_BW)
    return _calibrated_costs[key]


# ---------------------------------------------------------------------------
# Live-scheduler smoke harness (shared by bench_tpot_slo and
# bench_decode_throughput so their request streams stay comparable).
# ---------------------------------------------------------------------------

LIVE_ARCH = "granite-3-2b"
LIVE_REQUESTS = 10
LIVE_PROMPT_LEN = 12
LIVE_MAX_NEW = 4

_live_model = None
_live_systems: Dict[int, object] = {}


def live_model():
    global _live_model
    import jax

    from repro.configs import get_config, smoke_variant
    from repro.models import init_params

    if _live_model is None:
        cfg = smoke_variant(get_config(LIVE_ARCH))
        _live_model = (cfg, init_params(jax.random.PRNGKey(0), cfg))
    return _live_model


_live_mtp_params = None


def live_mtp_params():
    """Draft-head params for the live smoke arch — distilled against the
    base model's greedy continuations of the *live serving prompts*
    (memoized), the smoke-scale analogue of the paper's trained MTP module
    (train distribution == serve distribution), so live MTP rows measure a
    realistic acceptance rate instead of chance."""
    global _live_mtp_params
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fit_draft_head, init_mtp_params

    if _live_mtp_params is None:
        cfg, params = live_model()
        mtp = init_mtp_params(jax.random.PRNGKey(1), cfg)
        rng = np.random.RandomState(0)          # == live_smoke_serve stream
        prompts = jnp.asarray(
            [rng.randint(0, cfg.vocab_size, LIVE_PROMPT_LEN)
             for _ in range(LIVE_REQUESTS)], jnp.int32)
        mtp = fit_draft_head(params, cfg, mtp, jax.random.PRNGKey(2),
                             prompts=prompts, gen_len=32, steps=400)
        _live_mtp_params = mtp
    return _live_mtp_params


def live_smoke_serve(*, decode_batch: int, tpot_budget_ms=None,
                     admission: str = "shed", decode_chunk: int = 1,
                     max_new: int = LIVE_MAX_NEW, use_mtp: bool = False,
                     mtp_fused: bool = False):
    """Serve the canonical smoke request stream; returns (results,
    scheduler). The ServingSystem (and its jitted prefill/decode steps) is
    cached per (decode_batch, decode_chunk, mtp mode) — only the scheduler,
    which traces no computation, is rebuilt per sweep point. The decode
    cost model is calibrated from the arch's dry-run roofline record when
    one exists (placeholder defaults otherwise); MTP runs use the distilled
    draft head from :func:`live_mtp_params`."""
    import numpy as np

    from repro.serving import Request, SchedulerConfig, ServingSystem

    cfg, params = live_model()
    rng = np.random.RandomState(0)
    reqs = [Request(i, list(rng.randint(0, cfg.vocab_size, LIVE_PROMPT_LEN)),
                    max_new) for i in range(LIVE_REQUESTS)]
    key = (decode_batch, decode_chunk, max_new, use_mtp, mtp_fused)
    system = _live_systems.get(key)
    if system is None:
        system = ServingSystem(
            params, cfg, n_prefill=2, decode_batch=decode_batch,
            capacity=LIVE_PROMPT_LEN + max_new + 16,
            decode_chunk=decode_chunk, use_mtp=use_mtp,
            mtp_params=live_mtp_params() if use_mtp else None,
            mtp_fused=mtp_fused)
        _live_systems[key] = system
    system.reconfigure_scheduler(
        SchedulerConfig(tpot_budget_ms=tpot_budget_ms, admission=admission,
                        decode_chunk=decode_chunk, use_mtp=use_mtp,
                        decode_cost=calibrated_decode_cost(LIVE_ARCH)))
    results = system.serve(reqs)
    return results, system.scheduler


def live_pool_serve(*, policy: str = "least_loaded_slots",
                    decode_engines: int = 2, decode_batch: int = 2,
                    tpot_budget_ms=None, admission: str = "shed",
                    rebalance_every: int = 0, max_new: int = LIVE_MAX_NEW,
                    shared_prefix: int = 8):
    """Serve a shared-prefix smoke stream through a decode pool; returns
    (results, scheduler, system). The pooled ServingSystem (one jit per
    engine) is cached per shape key; the routing policy and rebalance
    cadence are control-plane and swap via ``reconfigure_scheduler``, so a
    policy sweep reuses one compiled pool. Prompts share a prefix and the
    system carries an EMS context cache, so ``cache_affinity`` has real
    block keys to route on."""
    import numpy as np

    from repro.mempool import ContextCache, MemoryPool
    from repro.serving import Request, SchedulerConfig, ServingSystem

    cfg, params = live_model()
    rng = np.random.RandomState(0)
    prefix = list(rng.randint(0, cfg.vocab_size, shared_prefix))
    reqs = [Request(i, prefix + list(rng.randint(
                0, cfg.vocab_size, LIVE_PROMPT_LEN - shared_prefix)),
                    max_new) for i in range(LIVE_REQUESTS)]
    key = ("pool", decode_engines, decode_batch, max_new)
    system = _live_systems.get(key)
    if system is None:
        cc = ContextCache(MemoryPool(n_nodes=4), block_tokens=4,
                          model_tag=cfg.name)
        system = ServingSystem(
            params, cfg, n_prefill=2, decode_batch=decode_batch,
            capacity=LIVE_PROMPT_LEN + max_new + 16,
            decode_engines=decode_engines, context_cache=cc)
        # Warm the EMS context cache (and the jit caches) on the same
        # stream before any measured run: otherwise the first policy in a
        # sweep pays cold-prefix prefill while later ones reuse it, and
        # the per-policy rows would compare cache warmth, not routing.
        system.serve([Request(r.rid, list(r.prompt), r.max_new_tokens)
                      for r in reqs])
        _live_systems[key] = system
    system.reconfigure_scheduler(
        SchedulerConfig(tpot_budget_ms=tpot_budget_ms, admission=admission,
                        decode_policy=policy,
                        decode_rebalance_every=rebalance_every,
                        decode_cost=calibrated_decode_cost(LIVE_ARCH)))
    results = system.serve(reqs)
    return results, system.scheduler, system


AUTOSCALE_MAX_NEW = 8


def autoscale_burst(n_requests: int = 12, rate_rps: float = 400.0,
                    max_new: int = AUTOSCALE_MAX_NEW, seed: int = 5):
    """The canonical autoscale bench burst. One definition, shared by the
    autoscaling run and its fixed-pool token-identity reference, so the
    two provably serve the same stream."""
    from repro.serving.workload import poisson_requests

    cfg, _ = live_model()
    return poisson_requests(n_requests, rate_rps, LIVE_PROMPT_LEN, max_new,
                            cfg.vocab_size, seed=seed)


def live_autoscale_serve(*, requests=None, min_engines: int = 1,
                         max_engines: int = 3, decode_batch: int = 2,
                         max_new: int = AUTOSCALE_MAX_NEW,
                         tpot_budget_ms=None):
    """Open-loop burst (default: :func:`autoscale_burst`) through an
    *autoscaling* decode pool; returns (results, scheduler, system). Not
    cached: autoscaling mutates the pool's engine roster, so every call
    builds a fresh system (smoke engines are cheap) — determinism of the
    scale-event sequence is part of what the benches report."""
    from repro.serving import SchedulerConfig, ServingSystem

    cfg, params = live_model()
    reqs = autoscale_burst(max_new=max_new) if requests is None else requests
    system = ServingSystem(
        params, cfg, n_prefill=2, decode_batch=decode_batch,
        capacity=LIVE_PROMPT_LEN + max_new + 16,
        decode_engines=min_engines, autoscale=True,
        min_engines=min_engines, max_engines=max_engines,
        tpot_budget_ms=tpot_budget_ms,
        scheduler_config=SchedulerConfig(
            decode_cost=calibrated_decode_cost(LIVE_ARCH)))
    results = system.serve(reqs, open_loop=True)
    return results, system.scheduler, system


#: The canonical bench fault plan: one mid-decode crash (engine 1), two
#: consecutive transfer timeouts (exercises backoff + retry), and a 2×
#: straggler window on engine 0. Shared by bench_decode_throughput and
#: bench_tpot_slo so both report the same failure sequence.
FAULT_PLAN_EVENTS = (
    {"kind": "engine_crash", "engine": 1, "at": 0.02},
    {"kind": "transfer_timeout", "op": "transfer", "after": 2, "count": 2},
    {"kind": "slow_engine", "engine": 0, "at": 0.01, "factor": 2.0,
     "duration": 0.01},
)


def live_fault_serve(*, events=FAULT_PLAN_EVENTS, requests=None,
                     min_engines: int = 2, max_engines: int = 3,
                     decode_batch: int = 2, max_new: int = AUTOSCALE_MAX_NEW,
                     degrade_shed_queue_s=None):
    """Open-loop burst (default: the autoscale bench burst, so the
    fault-free reference is the same stream) through a 2-engine autoscaling
    pool under a deterministic fault plan; returns (results, scheduler,
    system, injector). ``events=None`` runs the identical system fault-free
    — the token-identity reference. Not cached: crashes mutate the engine
    roster. ``min_engines=2`` guarantees the crash drops the pool below the
    floor, so the bench provably exercises the respawn path."""
    from repro.serving import SchedulerConfig, ServingSystem
    from repro.serving.faults import FaultEvent, FaultInjector, FaultPlan

    cfg, params = live_model()
    reqs = autoscale_burst(max_new=max_new) if requests is None else requests
    injector = None
    if events is not None:
        injector = FaultInjector(
            FaultPlan([FaultEvent(**dict(e)) for e in events]))
    system = ServingSystem(
        params, cfg, n_prefill=2, decode_batch=decode_batch,
        capacity=LIVE_PROMPT_LEN + max_new + 16,
        decode_engines=2, autoscale=True,
        min_engines=min_engines, max_engines=max_engines,
        degrade_shed_queue_s=degrade_shed_queue_s,
        fault_injector=injector,
        scheduler_config=SchedulerConfig(
            decode_cost=calibrated_decode_cost(LIVE_ARCH)))
    results = system.serve(reqs, open_loop=True)
    return results, system.scheduler, system, injector


CB_CHUNK = 4       # scan width for the continuous-batching comparison
CB_MAX_NEW = 6     # != 1 (mod CB_CHUNK): every request ends mid-chunk, so
#                    the wave-shaped loop provably burns masked iterations


def continuous_burst(n_requests: int = 12, rate_rps: float = 300.0,
                     max_new: int = CB_MAX_NEW, seed: int = 7):
    """The canonical continuous-batching bench burst: one definition shared
    by the CB-on, CB-off, and per-step reference runs, so all three
    provably serve the identical arrival trace."""
    from repro.serving.workload import poisson_requests

    cfg, _ = live_model()
    return poisson_requests(n_requests, rate_rps, LIVE_PROMPT_LEN, max_new,
                            cfg.vocab_size, seed=seed)


def live_continuous_serve(*, continuous: bool, decode_chunk: int = CB_CHUNK,
                          tpot_budget_ms=9.0, admission: str = "queue",
                          decode_batch: int = 3, max_new: int = CB_MAX_NEW,
                          requests=None):
    """Open-loop burst (default: :func:`continuous_burst`) through the
    chunked decode fast path with continuous batching on or off; returns
    (results, scheduler). The system is cached per (chunk, batch) shape —
    ``continuous_batching`` is control-plane and flips via
    ``reconfigure_scheduler``, so the on/off comparison reuses one
    compiled system (adaptive widths jit lazily on the first CB-on run).
    ``decode_chunk=1`` gives the per-step token-identity reference."""
    from repro.serving import SchedulerConfig, ServingSystem

    cfg, params = live_model()
    reqs = continuous_burst(max_new=max_new) if requests is None \
        else requests
    key = ("cb", decode_chunk, decode_batch, max_new)
    system = _live_systems.get(key)
    if system is None:
        system = ServingSystem(
            params, cfg, n_prefill=2, decode_batch=decode_batch,
            capacity=LIVE_PROMPT_LEN + max_new + 16,
            decode_chunk=decode_chunk)
        _live_systems[key] = system
    system.reconfigure_scheduler(
        SchedulerConfig(tpot_budget_ms=tpot_budget_ms, admission=admission,
                        decode_chunk=decode_chunk,
                        continuous_batching=continuous,
                        decode_cost=calibrated_decode_cost(LIVE_ARCH)))
    results = system.serve(reqs, open_loop=True)
    return results, system.scheduler


OVERLOAD_BUDGET_MS = 6.0        # interactive TPOT budget. Under the
#                                 placeholder cost model (4 ms fixed +
#                                 1 ms/req) this caps the batch at 2 while
#                                 a class-blind batch-of-3 steps at 7 ms —
#                                 so the baseline provably violates what
#                                 the controlled run holds. The overload
#                                 section pins the placeholder cost on
#                                 purpose: its acceptance property
#                                 (held-with vs violated-without control)
#                                 must be stable across containers, not a
#                                 function of whichever dry-run record
#                                 happens to exist.
OVERLOAD_BATCH_BUDGET_MS = 30.0
OVERLOAD_MAX_NEW = 6


def overload_burst(n_batch: int = 6, n_interactive: int = 4, seed: int = 5):
    """The canonical mixed-class overload burst: a batch-tier flood arrives
    first and fills the decode slots, then an interactive trickle lands
    mid-decode. One definition, shared by bench_tpot_slo's per-class rows
    and bench_decode_throughput's slo_classes section (controlled and
    class-blind runs), so every variant provably serves the same stream."""
    import numpy as np

    from repro.serving import Request

    cfg, _ = live_model()
    rng = np.random.RandomState(seed)
    reqs = [Request(i, list(rng.randint(0, cfg.vocab_size, LIVE_PROMPT_LEN)),
                    OVERLOAD_MAX_NEW, arrival=5e-4 * i, slo_class="batch")
            for i in range(n_batch)]
    reqs += [Request(100 + i,
                     list(rng.randint(0, cfg.vocab_size, LIVE_PROMPT_LEN)),
                     LIVE_MAX_NEW, arrival=4e-3 + 2e-3 * i,
                     slo_class="interactive")
             for i in range(n_interactive)]
    return reqs


def live_overload_serve(*, class_aware: bool, brownout: bool = False,
                        requests=None, decode_batch: int = 3):
    """Serve the mixed-class overload burst with or without SLO-class
    control; returns (results, scheduler, system). The controlled run gives
    interactive the 6 ms budget (queue mode), batch a relaxed 30 ms budget,
    and enables batch preemption; the brownout variant instead lets the
    ladder escalate (preemption arrives at level 2, so the ladder itself is
    what's measured); the class-blind baseline serves the identical stream
    gate-open. Not cached: preemption replays through the prefill plane and
    the comparison wants a clean per-run trace, so each call builds a fresh
    system. Uses the placeholder decode cost (see OVERLOAD_BUDGET_MS)."""
    from repro.serving import ServingSystem

    cfg, params = live_model()
    reqs = overload_burst() if requests is None else requests
    kw = {}
    if class_aware:
        kw = dict(tpot_budget_ms=OVERLOAD_BUDGET_MS,
                  batch_tpot_budget_ms=OVERLOAD_BATCH_BUDGET_MS)
        if brownout:
            kw.update(brownout=True)
        else:
            kw.update(preempt_batch=True)
    system = ServingSystem(
        params, cfg, n_prefill=2, decode_batch=decode_batch,
        capacity=LIVE_PROMPT_LEN + OVERLOAD_MAX_NEW + 16, **kw)
    results = system.serve(reqs, open_loop=True)
    return results, system.scheduler, system


STREAM_CHUNK = 4          # streamed-handoff chunk width for the bench
STREAM_PROMPT_LEN = 24    # long enough for several chunks per request
STREAM_RATE_RPS = 500.0


def stream_burst(n_requests: int = 10, seed: int = 11):
    """The canonical pipelined-handoff bench burst: one definition shared
    by the streamed and synchronous runs, so the TTFT split and the
    token-identity check provably compare the same stream."""
    from repro.serving.workload import poisson_requests

    cfg, _ = live_model()
    return poisson_requests(n_requests, STREAM_RATE_RPS, STREAM_PROMPT_LEN,
                            LIVE_MAX_NEW, cfg.vocab_size, seed=seed)


def live_stream_serve(*, streamed: bool, requests=None,
                      stream_chunk: int = STREAM_CHUNK,
                      decode_batch: int = 4):
    """Open-loop burst (default: :func:`stream_burst`) with the KV handoff
    either synchronous (whole-request, on the TTFT critical path) or
    pipelined (chunked streaming overlapped behind prefill compute);
    returns (results, scheduler). ``stream_handoff`` is control-plane, so
    both runs share one cached compiled system and flip the handoff mode
    via ``reconfigure_scheduler`` — the decode path is bit-identical by
    construction of the comparison, and the bench asserts it."""
    from repro.serving import SchedulerConfig, ServingSystem

    cfg, params = live_model()
    reqs = stream_burst() if requests is None else requests
    key = ("stream", decode_batch)
    system = _live_systems.get(key)
    if system is None:
        system = ServingSystem(
            params, cfg, n_prefill=2, decode_batch=decode_batch,
            capacity=STREAM_PROMPT_LEN + LIVE_MAX_NEW + 16)
        _live_systems[key] = system
    system.reconfigure_scheduler(
        SchedulerConfig(stream_handoff=streamed, stream_chunk=stream_chunk,
                        decode_cost=calibrated_decode_cost(LIVE_ARCH)))
    results = system.serve(reqs, open_loop=True)
    return results, system.scheduler


JOINT_TTFT_BUDGET_MS = 2.0
JOINT_TPOT_BUDGET_MS = 6.0


def joint_burst(seed: int = 3):
    """The canonical phase-skewed joint-autoscale burst: a prefill-heavy
    opening phase (long prompts, 2-token generations, tight arrivals)
    followed by a decode-heavy phase (short prompts, long generations), so
    a correct joint controller must shift an engine decode->prefill and
    then back."""
    import numpy as np

    from repro.serving import Request

    cfg, _ = live_model()
    rng = np.random.RandomState(seed)
    reqs = [Request(i, list(rng.randint(0, cfg.vocab_size, 48)), 2,
                    arrival=5e-4 * i) for i in range(8)]
    reqs += [Request(100 + i, list(rng.randint(0, cfg.vocab_size, 6)), 24,
                     arrival=0.15 + 2e-4 * i) for i in range(8)]
    return reqs


def live_joint_serve(*, joint: bool = True, requests=None,
                     decode_batch: int = 2):
    """The phase-skewed burst through a joint P/D-autoscaling system
    (1 prefill + 2 decode engines initially, clamps 1..3 per role);
    returns (results, scheduler, system). ``joint=False`` serves the
    identical stream with the roster fixed — the token-identity reference.
    Not cached: the controller mutates both engine rosters."""
    from repro.serving import SchedulerConfig, ServingSystem

    cfg, params = live_model()
    reqs = joint_burst() if requests is None else requests
    kw = dict(joint_autoscale=True, min_prefill=1, max_prefill=3,
              min_engines=1, max_engines=3,
              ttft_budget_ms=JOINT_TTFT_BUDGET_MS,
              tpot_budget_ms=JOINT_TPOT_BUDGET_MS,
              admission="queue") if joint else {}
    system = ServingSystem(
        params, cfg, prefill_engines=1, decode_batch=decode_batch,
        capacity=96, decode_engines=2, **kw)
    results = system.serve(reqs, open_loop=True)
    return results, system.scheduler, system


EMS_SESSIONS = 3
EMS_TURNS = 3


def live_ems_serve(*, n_sessions: int = EMS_SESSIONS, turns: int = EMS_TURNS,
                   hit_aware: bool = False, seed: int = 13,
                   decode_batch: int = 4, tpot_budget_ms=None):
    """Multi-turn session trace through a ServingSystem backed by the
    shared :class:`~repro.mempool.EMSService` tier with ``cache_affinity``
    routing; returns (results, scheduler, system, reqs). Not cached: the
    EMS hit-rate trajectory across turns (cold first turns, grown-prefix
    reuse on later ones) is exactly what callers measure, so every run
    starts from an empty tier. Utterance/reply lengths are clipped tight
    to bound the set of compiled prefill shapes at smoke scale."""
    from repro.mempool import EMSService, MemoryPool
    from repro.serving import SchedulerConfig, ServingSystem
    from repro.serving.workload import multi_turn_sessions

    cfg, params = live_model()
    reqs = multi_turn_sessions(
        n_sessions, seed=seed, vocab_size=cfg.vocab_size,
        session_rate_rps=200.0, turns=turns, turn_tokens_median=8,
        turn_tokens_sigma=0.4, turn_tokens_max=12,
        max_new_median=3, max_new_sigma=0.3, max_new_max=4)
    cap = max(len(r.prompt) + r.max_new_tokens for r in reqs) + 8
    ems = EMSService(MemoryPool(n_nodes=4), block_tokens=4,
                     model_tag=cfg.name)
    system = ServingSystem(
        params, cfg, n_prefill=2, decode_batch=decode_batch,
        capacity=cap, decode_engines=2, decode_router="cache_affinity",
        context_cache=ems, tpot_budget_ms=tpot_budget_ms,
        hit_aware_admission=True if hit_aware else None,
        scheduler_config=SchedulerConfig(
            decode_cost=calibrated_decode_cost(LIVE_ARCH)))
    results = system.serve(reqs, open_loop=True)
    return results, system.scheduler, system, reqs


def live_poisson_serve(*, rate_rps: float, tpot_budget_ms=None,
                       admission: str = "queue", n_requests: int = 16,
                       decode_batch: int = 4, max_new: int = LIVE_MAX_NEW,
                       seed: int = 0):
    """Open-loop Poisson wave through the cached live system — the
    admission gate under bursts. Returns (results, scheduler)."""
    from repro.serving import SchedulerConfig, ServingSystem
    from repro.serving.workload import poisson_requests

    cfg, params = live_model()
    reqs = poisson_requests(n_requests, rate_rps, LIVE_PROMPT_LEN, max_new,
                            cfg.vocab_size, seed=seed)
    key = (decode_batch, 1, max_new, False, False)
    system = _live_systems.get(key)
    if system is None:
        system = ServingSystem(
            params, cfg, n_prefill=2, decode_batch=decode_batch,
            capacity=LIVE_PROMPT_LEN + max_new + 16)
        _live_systems[key] = system
    system.reconfigure_scheduler(
        SchedulerConfig(tpot_budget_ms=tpot_budget_ms, admission=admission,
                        decode_cost=calibrated_decode_cost(LIVE_ARCH)))
    results = system.serve(reqs, open_loop=True)
    return results, system.scheduler
