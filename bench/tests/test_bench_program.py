"""The program's own spans as the benchmark reads them
(``bench/program_spans.py`` and the readers built on it): device idle time
put down to the innermost program span, the host-clock readers against the
benchmark's own spans, and a program without the tracer."""
import dataclasses
import json
import os
import sys
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench import program_spans as P  # noqa: E402
from bench import trace_reduce as T  # noqa: E402
from bench.tests.test_bench_harness import (TINY, TINY_LIMIT,  # noqa: E402
                                            TINY_TRAFFIC)

RECORDED = os.path.join(ROOT, "bench", "tests", "data",
                        "trace_events_program.json")
MS = 1_000_000          # ns
#: the readers that read the program's spans
PROGRAM_READERS = ("prefill_queue_ms_per_req", "decode_queue_ms_per_req",
                   "ems_fetch_ms_per_req", "ems_store_ms_per_req",
                   "ems_idle_share")


def events():
    """A 100 ms window on one device, busy 5-20 and 60-80 ms; a prefill
    0-40 with its EMS and compute parts, a handoff, a decode chunk."""
    return {
        "spans": [["traced_wave", 0, 100 * MS], ["prefill", 0, 40 * MS],
                  ["handoff", 40 * MS, 45 * MS], ["decode", 50 * MS, 90 * MS]],
        "devices": {"/device:TPU:0": [
            ["fusion.1", 5 * MS, 15 * MS], ["fusion.2", 15 * MS, 20 * MS],
            ["copy.3", 12 * MS, 14 * MS], ["while.9", 60 * MS, 80 * MS]]},
        "device_modules": {"/device:TPU:0": [
            "jit_pdc_prefill_continue", "jit_pdc_prefill_continue",
            "jit_pdc_prefill_continue", "jit_pdc_decode_loop"]},
        "program": [
            ["serve.wave", 0, 100 * MS, None], ["prefill", 0, 40 * MS, 7],
            ["prefill.ems_fetch", 0, 3 * MS, 7],
            ["prefill.ems_insert", 3 * MS, 10 * MS, 7],
            ["prefill.compute", 10 * MS, 22 * MS, 7],
            ["prefill.ems_pack", 22 * MS, 30 * MS, 7],
            ["handoff.transfer", 40 * MS, 45 * MS, 7],
            ["decode.chunk", 50 * MS, 90 * MS, None],
            ["decode.dispatch", 50 * MS, 52 * MS, None],
            ["decode.sync", 52 * MS, 85 * MS, None],
            ["decode.commit", 85 * MS, 90 * MS, None]],
    }


def test_idle_time_goes_to_the_innermost_program_span():
    r = P.reduce(events())
    # idle 0-5 (fetch 0-3, insert 3-5), 20-60 (compute 20-22, pack 22-30,
    # prefill 30-40, transfer 40-45, wave 45-50, dispatch 50-52, sync
    # 52-60), 80-100 (sync 80-85, commit 85-90, wave 90-100)
    want = {"prefill.ems_fetch": 3, "prefill.ems_insert": 2,
            "prefill.compute": 2, "prefill.ems_pack": 8, "prefill": 10,
            "handoff.transfer": 5, "serve.wave": 15, "decode.dispatch": 2,
            "decode.sync": 13, "decode.commit": 5}
    assert r["idle_s_in_program"] == pytest.approx(
        {k: v * 1e-3 for k, v in want.items()})
    assert list(r["idle_s_in_program"])[0] == "serve.wave"   # longest first
    assert r["window_s"] == pytest.approx(0.1)
    assert r["device_s_by_program"] == pytest.approx(
        {"jit_pdc_prefill_continue": 0.015, "jit_pdc_decode_loop": 0.02})


def test_program_idle_gaps_are_trace_reduce_gaps_named_by_program_span():
    ev = events()
    r = P.reduce(ev)
    old = T.reduce(ev).breakdown["idle_gaps"]
    assert [s for _, s in r["program_idle_gaps"]] == [s for _, s in old]
    # 20-60 (midpoint 40: the transfer), 80-100 (90: the wave after the
    # chunk closed), 0-5 (2.5: the fetch)
    assert [k for k, _ in r["program_idle_gaps"]] == [
        "handoff.transfer", "serve.wave", "prefill.ems_fetch"]
    ev["program"] = [p for p in ev["program"] if p[0] != "serve.wave"]
    assert P.reduce(ev)["program_idle_gaps"][1][0] == T.OUTSIDE


def test_no_program_span_reduces_to_none():
    ev = events()
    ev["program"] = []
    assert P.reduce(ev) is None


def test_innermost_pieces_cover_the_window_once():
    spans = [(0, 10, "a"), (2, 4, "b"), (3, 4, "c"), (4, 12, "d"),
             (20, 30, "e")]
    pieces = P.innermost(spans, 1, 25)
    assert pieces == [(1, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 12, "d"),
                      (12, 20, T.OUTSIDE), (20, 25, "e")]


def test_recorded_trace_with_program_spans():
    """A stretch of a granite.rag-prefix traced wave on one v5e: a prefill
    with its EMS fetch, insert, compute, pack and store, then decode
    chunks; as ``program_spans.extract`` gives it (operation names
    shortened)."""
    with open(RECORDED) as f:
        ev = json.load(f)
    old = T.reduce({k: ev[k] for k in ("spans", "devices")})
    new = T.reduce(ev)
    assert dataclasses.asdict(new) == dataclasses.asdict(old)
    r = P.reduce(ev)
    idle = new.window_s - new.busy_s
    assert sum(r["idle_s_in_program"].values()) == pytest.approx(idle)
    assert [s for _, s in r["program_idle_gaps"]] == pytest.approx(
        [s for _, s in new.breakdown["idle_gaps"]])
    names = {p[0] for p in ev["program"]}
    assert names >= {"prefill", "prefill.ems_insert", "decode.chunk"}
    lo, hi = [(a, b) for k, a, b in ev["spans"] if k == T.WINDOW_SPAN][0]
    pieces = P.innermost([(a, b, n) for n, a, b, _ in ev["program"]], lo, hi)
    for (label, _), (a, b) in zip(
            r["program_idle_gaps"],
            sorted(T.gaps(T.union(T.clip(
                [(a, b) for _, a, b in ev["devices"]["/device:TPU:0"]],
                lo, hi)), lo, hi), key=lambda g: g[0] - g[1])):
        mid = (a + b) / 2
        covering = [p for p in ev["program"] if p[1] <= mid < p[2]]
        want = max(covering, key=lambda p: (p[1], -p[2]))[0] \
            if covering else T.OUTSIDE
        assert label == want
    assert sum(r["device_s_by_program"].values()) >= new.busy_s - 1e-9
    assert any(k.startswith("jit_pdc_") for k in r["device_s_by_program"])


def test_traced_run_is_read_from_the_command_line():
    assert P.traced_run(["bench/run.py", "--workload", "x", "--trace", "1"])
    assert P.traced_run(["bench/run.py", "--trace=1"])
    assert not P.traced_run(["bench/run.py", "--trace", "0", "--seed", "1"])
    assert not P.traced_run(["bench/run.py", "--seed", "1"])


# --- the readers, on a tiny run on the CPU --------------------------------


@pytest.fixture(scope="module")
def tiny():
    """A tiny rag-prefix run with the program's tracer on; its Run (what
    the readers read) and readers."""
    from repro.serving import obs
    cell = dataclasses.replace(
        harness.find_cell("granite.rag-prefix"), config=TINY,
        traffic=TINY_TRAFFIC, limits={"max_logit_gap": {"limit": TINY_LIMIT}})
    runs = []

    class Run(harness.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            runs.append(self)

    saved = harness.Run
    harness.Run = Run
    obs.reset()
    obs.enable(True)
    try:
        # v5e's peaks, so that the readers that divide by one give numbers
        peak = harness.load_json(os.path.join(ROOT, "bench", "peaks.json"))
        out = harness.run_cell(cell, 2**31 + 7, 1.0, False,
                               time.perf_counter(), jax.devices(),
                               peak=peak["TPU v5 lite"])
    finally:
        obs.enable(False)
        harness.Run = saved
    assert out["correct"], out["checks"]
    yield runs[0], cell.readers
    obs.reset()


def test_program_readers_read_the_window(tiny):
    run, readers = tiny
    got = {n: readers[n].read(run) for n in PROGRAM_READERS}
    assert got["ems_idle_share"] is None          # no traced wave on a CPU
    for n in PROGRAM_READERS[:4]:
        assert got[n] is not None and got[n] >= 0, n
    assert got["ems_fetch_ms_per_req"] > 0        # documents found in EMS


def test_program_prefill_span_agrees_with_the_benchmarks_own(tiny):
    """Same calls, same clock: the in-program ``prefill`` span inside the
    benchmark's span of ``PrefillEngine.run``; its parts cover it."""
    run, readers = tiny
    outside = readers["prefill_ms_per_req"].read(run)
    inside = P.ms_per_request(run, ("prefill",))
    assert inside <= outside
    assert inside == pytest.approx(outside, rel=0.03)
    parts = P.ms_per_request(run, (
        "prefill.ems_fetch", "prefill.ems_insert", "prefill.compute",
        "prefill.ems_pack", "prefill.ems_store", "prefill.first_token"))
    assert 0.95 * inside <= parts <= inside


def test_without_the_programs_tracer_the_new_readers_give_none(
        tiny, monkeypatch):
    """A program that predates ``repro.serving.obs``: every old reader
    reads as before, every reader of a program span gives None."""
    import repro.serving
    run, readers = tiny
    old = [n for n in readers if n not in PROGRAM_READERS]
    before = {n: readers[n].read(run) for n in old}
    monkeypatch.delattr(repro.serving, "obs")
    monkeypatch.setitem(sys.modules, "repro.serving.obs", None)
    assert P.tracer() is None
    for n in PROGRAM_READERS:
        assert readers[n].read(run) is None, n
    assert {n: readers[n].read(run) for n in old} == before


def test_decode_occupancy_is_the_engines_live_slot_share():
    """The reader's count, from the tokens the decode calls returned, is
    ``live_slot_iters`` over batch times ``iters`` of the engines."""
    from bench.metrics import decode_occupancy
    from repro.models import init_params
    cfg = harness.program_config(TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    gen = harness.load_module(
        os.path.join(ROOT, "bench", "traffic", "waves.py"),
        "bench_traffic_waves").Generator(TINY_TRAFFIC, 5, TINY["vocab_size"])
    system, _ = harness.build_system(params, cfg, TINY["deployment"],
                                     harness.capacity_of(gen))
    rec = harness.Recorder(TINY, None)
    rec.attach(system)
    engines = system.pool.engines
    live0 = sum(e.live_slot_iters for e in engines)
    iters0 = sum(e.iters for e in engines)
    for w in range(2):
        harness.serve_wave(system, rec, gen.wave(w), w)
    live = sum(e.live_slot_iters for e in engines) - live0
    iters = sum(e.iters for e in engines) - iters0
    run = harness.Run(conf=TINY, reqs=list(rec.reqs.values()),
                      decode_calls=rec.decode_calls)
    want = 100.0 * live / (TINY["deployment"]["decode_batch"] * iters)
    assert 0 < want < 100
    assert decode_occupancy.read(run) == pytest.approx(want)


def test_ems_idle_share_adds_the_program_breakdown(monkeypatch):
    from bench.metrics import ems_idle_share
    ev = events()
    red = P.reduce(ev)
    monkeypatch.setattr(P, "traced_wave", lambda run: red)
    run = harness.Run(trace=T.reduce(ev))
    # idle in the fetch 3 ms, insert 2 ms and pack 8 ms of a 100 ms wave
    assert ems_idle_share.read(run) == pytest.approx(13.0)
    assert set(run.trace.breakdown) == {
        "device_ops", "idle_gaps", "idle_s_in_program", "program_idle_gaps",
        "device_s_by_program"}
