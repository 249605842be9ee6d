"""Trace reduction: idle share as a union of intervals, device time by host
span, the breakdown; on hand-made events and on a slice of a trace
recorded on one v5e."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace_reduce as T  # noqa: E402

RECORDED = os.path.join(ROOT, "bench", "tests", "data", "trace_events.json")
MS = 1_000_000          # ns


def events():
    """A 100 ms window: a prefill span 0-40, a handoff 40-50, a decode
    50-90, the loop outside them; two devices."""
    return {
        "spans": [["traced_wave", 0, 100 * MS], ["prefill", 0, 40 * MS],
                  ["handoff", 40 * MS, 50 * MS], ["decode", 50 * MS, 90 * MS]],
        "devices": {
            "/device:TPU:0": [
                ["fusion.1", 5 * MS, 15 * MS],
                ["fusion.2", 15 * MS, 20 * MS],
                ["copy.3", 12 * MS, 14 * MS],        # nested in fusion.1
                ["fusion.1", 60 * MS, 80 * MS],
                ["while.9", 95 * MS, 120 * MS]],     # runs past the window
            "/device:TPU:1": [["fusion.1", 60 * MS, 70 * MS]]},
    }


def test_busy_is_the_union_of_operations_averaged_over_devices():
    r = T.reduce(events())
    assert r.window_s == pytest.approx(0.1)
    # TPU:0 busy 5-20, 60-80, 95-100 = 40 ms; TPU:1 10 ms; mean 25 ms
    assert r.busy_s == pytest.approx(0.025)
    assert r.idle_share == pytest.approx(0.75)


def test_device_time_attributed_by_host_span():
    r = T.reduce(events())
    # TPU:0: prefill 15 ms, decode 20 ms; TPU:1: decode 10 ms; averaged
    assert r.device_s_in["prefill"] == pytest.approx(0.0075)
    assert r.device_s_in["decode"] == pytest.approx(0.015)
    assert r.device_s_in["handoff"] == pytest.approx(0.0)
    assert r.span_s == pytest.approx({"prefill": 0.04, "handoff": 0.01,
                                      "decode": 0.04})


def test_breakdown_names_ops_and_idle_gaps():
    b = T.reduce(events()).breakdown
    ops = dict(b["device_ops"])
    # self time: fusion.1 5-15 less copy.3 12-14 nested in it, and 60-80
    assert ops["fusion.1"] == pytest.approx(0.028)
    assert ops["fusion.2"] == pytest.approx(0.005)
    assert ops["copy.3"] == pytest.approx(0.002)
    assert ops["while.9"] == pytest.approx(0.005)   # clipped to the window
    assert [k for k, _ in b["device_ops"]][0] == "fusion.1"
    gaps = b["idle_gaps"]
    # TPU:0 gaps: 0-5 prefill, 20-60 (midpoint 40: handoff), 80-95
    # (midpoint 87.5: decode)
    assert gaps[0] == ["handoff", pytest.approx(0.040)]
    assert ["decode", pytest.approx(0.015)] in gaps
    assert ["prefill", pytest.approx(0.005)] in gaps
    assert len(gaps) <= T.TOP and len(b["device_ops"]) <= T.TOP


def test_self_time_subtracts_nested_ops():
    ops = [("%while.1 = (s32[]{:T(128)}, f32[8]) while(x)", 0, 100), ("%fusion.2 = f32[8]", 10, 30),
           ("%copy.3 = f32[8]", 40, 50), ("%fusion.4", 120, 130)]
    got = {T.op_name(n): t for n, t in T.self_times(ops)}
    assert got == {"%while.1 s32[]": 70, "%fusion.2 f32[8]": 20,
                   "%copy.3 f32[8]": 10, "%fusion.4": 10}


def test_gap_outside_every_span_is_the_serve_loop():
    ev = events()
    ev["spans"] = [s for s in ev["spans"] if s[0] != "handoff"]
    labels = [k for k, _ in T.reduce(ev).breakdown["idle_gaps"]]
    assert labels[0] == T.OUTSIDE


def test_no_window_or_no_device_is_refused():
    ev = events()
    with pytest.raises(ValueError):
        T.reduce({"spans": ev["spans"][1:], "devices": ev["devices"]})
    with pytest.raises(ValueError):
        T.reduce({"spans": ev["spans"], "devices": {}})


def test_recorded_trace_slice():
    """The last handoff before the first decode call of a granite.rag-prefix
    wave, and that decode call, on one v5e: host spans and device
    operations as ``extract`` gives them (operation names shortened)."""
    with open(RECORDED) as f:
        ev = json.load(f)
    r = T.reduce(ev)
    assert 0 < r.busy_s <= r.window_s
    assert 0 <= r.idle_share < 1
    # device time inside a kind of span is at most the span's own length
    for k, s in r.device_s_in.items():
        assert 0 <= s <= r.span_s[k] + 1e-9
    assert r.device_s_in["decode"] > 0
    assert sum(r.device_s_in.values()) <= r.busy_s + 1e-9
    assert r.breakdown["device_ops"] and r.breakdown["idle_gaps"]
    assert all(v > 0 for _, v in r.breakdown["device_ops"])
