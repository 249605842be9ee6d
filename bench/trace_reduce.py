"""From a profiler trace to the numbers the benchmark reports.

A trace is first cut down to plain events (``extract``): the benchmark's own
host spans (trace annotations named ``bench.<kind>``) and, per device, the
operations that ran on it, all in nanoseconds on the trace's one clock.
``reduce`` then works on those events alone:

* the window is the ``bench.traced_wave`` span;
* a device's busy time is the union of its operations' intervals inside the
  window (nested operations count once); ``busy_s`` is the mean over the
  devices, and the idle share is ``1 - busy_s / window_s``;
* the device time of a host span kind is the part of that union that falls
  inside spans of that kind: time is attributed by the host span it
  overlaps, not by the name of the program that ran;
* the breakdown lists the ten operations that took most device time (self
  time: less the operations nested in them, summed by name and result
  shape) and the ten longest idle gaps, each named by the host
  span the gap's midpoint fell in (``serve_loop`` where none: the host was
  in the serving loop itself, between the engine calls).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "traced_wave"
OPS_LINES = ("XLA Ops",)
OUTSIDE = "serve_loop"
TOP = 10

Interval = Tuple[float, float]


def union(ivs: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(ivs: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in ivs if b > lo and a < hi]


def length(ivs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Both unions of disjoint sorted intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(name: str) -> str:
    """An HLO op's name and result shape, from the instruction text the
    trace names it by (``%copy.3 = f32[40,8]{1,0} copy(...)``)."""
    head, _, rest = name.partition(" = ")
    shape = re.split(r"[{ ]", rest.lstrip("("), maxsplit=1)[0]
    return f"{head} {shape}".strip()


def clip_ops(ops, lo: float, hi: float):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ops if b > lo and a < hi]


def self_times(ops) -> List[Tuple[str, float]]:
    """Each operation's time less the time of the operations nested in it
    (a loop's body ops run inside the loop op on the same line)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out: List[Tuple[str, float]] = []
    stack: List[list] = []          # [name, start, end, child time]
    for name, a, b in ops:
        while stack and stack[-1][2] <= a:
            n, s, e, c = stack.pop()
            out.append((n, e - s - c))
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    while stack:
        n, s, e, c = stack.pop()
        out.append((n, e - s - c))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    device_s_in: Dict[str, float]        # host span kind -> device seconds
    span_s: Dict[str, float]             # host span kind -> host seconds
    breakdown: dict

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def extract(xplane_path: str) -> dict:
    """Host spans and device operations of one ``.xplane.pb``, in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append([e.name[6:], e.start_ns, e.end_ns])
        elif plane.name.startswith("/device:"):
            ops = [[e.name, e.start_ns, e.end_ns]
                   for line in plane.lines if line.name in OPS_LINES
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
    return {"spans": spans, "devices": devices}


def reduce(events: dict) -> Reduced:
    spans = events["spans"]
    win = [(a, b) for k, a, b in spans if k == WINDOW_SPAN]
    if not win or not events["devices"]:
        raise ValueError("trace has no traced window or no device operations")
    lo, hi = win[0]
    by_kind: Dict[str, List[Interval]] = defaultdict(list)
    for k, a, b in spans:
        if k != WINDOW_SPAN:
            by_kind[k].append((a, b))
    kinds = {k: union(clip(v, lo, hi)) for k, v in by_kind.items()}

    busy_total, in_kind = 0.0, defaultdict(float)
    first = None
    for name in sorted(events["devices"]):
        ops = events["devices"][name]
        busy = union(clip([(a, b) for _, a, b in ops], lo, hi))
        busy_total += length(busy)
        for k, ivs in kinds.items():
            in_kind[k] += length(intersect(busy, ivs))
        if first is None:
            first = (ops, busy)
    n = len(events["devices"])

    ops, busy = first
    per_op = defaultdict(float)
    for name, t in self_times(clip_ops(ops, lo, hi)):
        per_op[op_name(name)] += t
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]

    def label(t: float) -> str:
        for k, ivs in kinds.items():
            if any(a <= t < b for a, b in ivs):
                return k
        return OUTSIDE

    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=busy_total / n * ns,
        device_s_in={k: v / n * ns for k, v in in_kind.items()},
        span_s={k: length(v) * ns for k, v in kinds.items()},
        breakdown={
            "device_ops": [[k, v * ns] for k, v in top_ops],
            "idle_gaps": [[label((a + b) / 2), (b - a) * ns]
                          for a, b in idle]})


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def reduce_dir(trace_dir: str) -> Reduced:
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(extract(path))
