"""The serve path's measured spans (``serving/obs.py``): off by default and
free when off, and when on one nested span tree per wave whose request ids
match the requests served."""
import tracemalloc
from collections import Counter

import jax
import numpy as np
import pytest

from conftest import smoke
from repro.mempool import EMSService, MemoryPool
from repro.models import init_params
from repro.serving import Request, ServingSystem, obs

PREFILL_PARTS = ("prefill.ems_fetch", "prefill.ems_insert", "prefill.compute",
                 "prefill.ems_pack", "prefill.ems_store",
                 "prefill.first_token")


@pytest.fixture(scope="module")
def granite():
    cfg = smoke("granite-3-2b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def tracer():
    obs.reset()
    yield obs
    obs.enable(False)
    obs.reset()


def requests():
    """Six requests, four behind one shared 16-token prefix (EMS reuse),
    more than the decode batch holds at once (a queue before decode)."""
    rng = np.random.RandomState(3)
    shared = list(rng.randint(0, 200, 16))
    prompts = [shared + list(rng.randint(0, 200, 8)) for _ in range(4)]
    prompts += [list(rng.randint(0, 200, 20)) for _ in range(2)]
    return [Request(i, p, 3 + i % 3) for i, p in enumerate(prompts)]


def serve(cfg, params):
    cc = EMSService(MemoryPool(n_nodes=2), block_tokens=8,
                    model_tag=cfg.name)
    system = ServingSystem(params, cfg, n_prefill=2, decode_batch=2,
                           capacity=48, context_cache=cc, decode_chunk=4)
    results = system.serve(requests())
    return {r.rid: r.tokens for r in results if not r.shed}


def test_off_records_nothing_and_on_serves_the_same_tokens(granite, tracer):
    cfg, params = granite
    off = serve(cfg, params)
    assert tracer.snapshot() == []
    tracer.enable(True)
    on = serve(cfg, params)
    assert on == off and len(on) == len(requests())
    assert tracer.snapshot()


def test_span_tree_of_a_wave(granite, tracer):
    cfg, params = granite
    tracer.enable(True)
    served = serve(cfg, params)
    recs = tracer.snapshot()
    assert all(r.t1 is not None and r.t1 >= r.t0 for r in recs)
    by_rid = Counter((r.name, r.rid) for r in recs)
    for rid in served:
        for name in ("prefill", "queue.prefill", "queue.decode",
                     "handoff.transfer", "handoff.insert"):
            assert by_rid[(name, rid)] == 1, (name, rid)
    assert sum(1 for r in recs if r.name == "serve.wave") == 1
    # four prompts after the first found the shared prefix in EMS
    assert sum(1 for r in recs if r.name == "prefill.ems_insert") >= 3
    for i, r in enumerate(recs):
        if r.parent is None:
            continue
        p = recs[r.parent]
        assert p.t0 <= r.t0 and r.t1 <= p.t1, (r, p)
        assert r.parent < i
        if r.name.startswith("prefill."):
            assert p.name == "prefill" and p.rid == r.rid
        if r.name.startswith("decode.") and r.name != "decode.chunk":
            assert p.name == "decode.chunk"
    for i, r in enumerate(recs):
        if r.name == "prefill":
            assert recs[r.parent].name == "serve.wave"
            kids = [c for c in recs if c.parent == i]
            assert {c.name for c in kids} <= set(PREFILL_PARTS)
            assert sum(c.t1 - c.t0 for c in kids) <= r.t1 - r.t0
        if r.name.startswith("queue."):
            assert r.parent is None
    # the queue waits end where the prefill and the slot insert begin
    first = {(r.name, r.rid): r for r in recs}
    for rid in served:
        assert first[("queue.prefill", rid)].t1 <= first[("prefill", rid)].t0
        assert first[("queue.decode", rid)].t1 \
            <= first[("handoff.insert", rid)].t0


def test_off_span_is_one_shared_noop_and_allocates_nothing(tracer):
    assert tracer.span("prefill", 3) is tracer.span("decode.chunk")
    # every object the calls return is held, so one they allocated would
    # still be counted against obs.py when the second snapshot is taken
    held = [None] * 1000
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(1000):
            held[i] = tracer.span("prefill", i)
            with held[i]:
                tracer.begin("queue.decode", i)
                tracer.end("queue.decode", i)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [s for s in after.compare_to(before, "filename")
             if s.traceback[0].filename == obs.__file__ and s.size_diff > 0]
    assert grown == []
    assert len({id(h) for h in held}) == 1
    assert tracer.snapshot() == []


def test_waits_pair_by_name_and_rid(tracer):
    tracer.enable(True)
    tracer.end("queue.decode", 1)            # never begun: ignored
    tracer.begin("queue.decode", 1)
    tracer.begin("queue.prefill", 1)
    with tracer.span("serve.wave"):
        tracer.end("queue.decode", 1)
    recs = tracer.snapshot()
    assert [(r.name, r.rid, r.parent) for r in recs] == [
        ("serve.wave", None, None), ("queue.decode", 1, None)]
    tracer.reset()
    tracer.end("queue.prefill", 1)           # reset dropped the open wait
    assert tracer.snapshot() == []
    assert obs.summary(recs)["queue.decode"][0] == 1


def test_counts_are_timed_on_and_nothing_off(tracer):
    tracer.count("decode.held_routings", 5)          # off: nothing
    assert tracer.counters() == []
    tracer.enable(True)
    tracer.count("decode.held_routings", 5)
    tracer.count("decode.held_routings", 7)
    got = tracer.counters()
    assert [(c.name, c.n) for c in got] == [("decode.held_routings", 5),
                                             ("decode.held_routings", 7)]
    assert got[0].t <= got[1].t
    tracer.reset()
    assert tracer.counters() == []


def test_decode_engine_counts_held_routings_of_moe_models_only(granite,
                                                               tracer):
    """On, the decode engine counts a MoE model's routings onto held
    experts: with every expert held, k a MoE layer for each decoded token.
    A dense model, or the tracer off, records no count."""
    cfg = smoke("olmoe-1b-7b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    serve(cfg, params)
    assert tracer.counters() == []
    tracer.enable(True)
    serve(*granite)
    assert tracer.counters() == []
    served = serve(cfg, params)
    got = [c.n for c in tracer.counters()]
    decoded = sum(len(t) - 1 for t in served.values())
    moe_layers = cfg.num_layers - cfg.first_k_dense
    assert got and sum(got) == decoded * cfg.num_experts_per_tok * moe_layers


def test_serve_cli_trace_prints_each_span(monkeypatch, capsys, tracer):
    """``launch/serve.py --trace`` turns the tracer on for the serve call
    and prints each span's count, total and mean host time last."""
    import sys
    from repro.launch import serve as cli
    monkeypatch.setattr(cli, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "granite-3-2b", "--n-requests", "4",
        "--prompt-len", "12", "--max-new", "3", "--shared-prefix", "8",
        "--decode-batch", "2", "--trace"])
    cli.main()
    out = capsys.readouterr().out
    table = out[out.index("spans (host clock): name count total_ms mean_ms"):]
    rows = {line.split()[0]: line.split()[1:]
            for line in table.splitlines()[1:]}
    assert rows["prefill"][0] == "4" and rows["serve.wave"][0] == "1"
    assert {"queue.prefill", "queue.decode", "handoff.insert",
            "decode.chunk"} <= set(rows)
    assert not tracer.TRACER.on
