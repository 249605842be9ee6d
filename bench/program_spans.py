"""The program's own spans (``repro.serving.obs``), as the benchmark reads them.

The serving program records a span at each layer boundary of its serve path
when its tracer is on: in memory on the host clock, and as a profiler
annotation named ``pdc.<span>`` on the device trace's clock. The tracer is
off by default. A ``bench/run.py ... --trace 1`` run switches it on when
this module is first imported, which ``find_cell`` does when it loads the
readers, before set-up; a ``--trace 0`` run leaves it off, so no number of a
``--trace 0`` run pays for it. Readers clip host-clock spans to the window
and device-trace spans to the traced wave.

A program without the tracer (one that predates it) gives no spans: every
function here then returns None, and so does each reader built on it.

From a ``.xplane.pb`` (``extract``) come the events ``trace_reduce.extract``
gives, plus the program's spans with their request id and the program (HLO
module) of each device operation. ``reduce`` then puts each stretch of
device idle time down to the innermost program span the host was in
(``serve_loop`` where none), and sums device time by program.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: where ``harness.run_cell`` writes each cell's trace
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")
PREFIX = "pdc."
#: the spans of the EMS cache tier inside a prefill
EMS_SPANS = ("prefill.ems_fetch", "prefill.ems_insert", "prefill.ems_pack",
             "prefill.ems_store")
#: the line of a device plane whose events are the programs run
MODULE_LINES = ("XLA Modules",)


def tracer():
    """The program's tracer module, or None where the program has none."""
    try:
        from repro.serving import obs
    except ImportError:
        return None
    return obs


def traced_run(argv: Sequence[str]) -> bool:
    """Whether ``argv`` asks ``bench/run.py`` for a traced run."""
    args = list(argv[1:])
    return "--trace=1" in args or any(
        a == "--trace" and b == "1" for a, b in zip(args, args[1:]))


if traced_run(sys.argv):
    _obs = tracer()
    if _obs is not None:
        _obs.reset()
        _obs.enable(True)


# ---------------------------------------------------------------------------
# Host clock
# ---------------------------------------------------------------------------


def window_spans(run, names) -> Optional[List[tuple]]:
    """Closed spans and waits named in ``names``, clipped to the window:
    ``(name, t0, t1, rid)``. None where the program recorded none there."""
    obs = tracer()
    if obs is None:
        return None
    lo, hi = run.window
    out = [(r.name, max(r.t0, lo), min(r.t1, hi), r.rid)
           for r in obs.snapshot()
           if r.name in names and r.t1 is not None and r.t1 > lo
           and r.t0 < hi]
    return out or None


def ms_per_request(run, names) -> Optional[float]:
    """Host time of the spans ``names`` in the window over the window's
    requests (those that got a first token), in ms."""
    spans = window_spans(run, names)
    done = [r for r in run.reqs if r.first is not None]
    if spans is None or not done:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1, _ in spans) / len(done)


# ---------------------------------------------------------------------------
# Device trace
# ---------------------------------------------------------------------------


def extract(xplane_path: str) -> dict:
    """``trace_reduce.extract``'s events of one ``.xplane.pb``, plus
    ``program``: the program's spans ``[name, t0, t1, rid]`` (name without
    the ``pdc.`` prefix), and ``device_modules``: per device, the program
    of each of its operations, in the order of ``devices``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    spans, program, devices, modules = [], [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append([e.name[6:], e.start_ns, e.end_ns])
                    elif e.name.startswith(PREFIX):
                        rid = dict(e.stats).get("rid")
                        program.append([e.name[len(PREFIX):], e.start_ns,
                                        e.end_ns, rid])
        elif plane.name.startswith("/device:"):
            ops, runs = [], []
            for line in plane.lines:
                if line.name in T.OPS_LINES:
                    ops.extend([e.name, e.start_ns, e.end_ns]
                               for e in line.events)
                elif line.name in MODULE_LINES:
                    runs.extend((e.start_ns, e.end_ns, e.name)
                                for e in line.events)
            if ops:
                devices[plane.name] = ops
                modules[plane.name] = _programs(ops, runs)
    return {"spans": spans, "devices": devices, "program": program,
            "device_modules": modules}


def _programs(ops, runs) -> List[str]:
    """Each operation's program: the program run (module line event) its
    start falls in, less the fingerprint the name carries
    (``jit_pdc_decode_loop(8819747975499627394)``), so that the widths of
    one program sum together."""
    runs = sorted(runs)
    starts = [a for a, _, _ in runs]
    out = []
    for _, a, _ in ops:
        i = bisect.bisect_right(starts, a) - 1
        m = runs[i][2] if i >= 0 and a < runs[i][1] else "unknown"
        out.append(re.sub(r"\(\d+\)$", "", m))
    return out


def innermost(spans: Sequence[Tuple[float, float, str]], lo: float,
              hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into pieces ``(a, b, name)``, each named by the
    innermost span covering it (spans nest: those of one host thread), or
    ``serve_loop`` where none does."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []          # (end, name) of open spans
    t = lo

    def emit(upto: float) -> None:
        nonlocal t
        if upto > t:
            pieces.append((t, upto, stack[-1][1] if stack else T.OUTSIDE))
            t = upto

    for a, b, name in sorted(((max(a, lo), min(b, hi), n)
                              for a, b, n in spans if b > lo and a < hi),
                             key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            emit(stack[-1][0])
            stack.pop()
        emit(a)
        stack.append((b, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return pieces


def _label(pieces, t: float) -> str:
    i = bisect.bisect_right([a for a, _, _ in pieces], t) - 1
    return pieces[i][2] if i >= 0 else T.OUTSIDE


def reduce(events: dict) -> Optional[dict]:
    """Device idle time by innermost program span, the ten longest idle
    gaps (``trace_reduce``'s own ten) named by the program span at their
    midpoint, and device time by program; over the traced wave, averaged
    over the devices, in s. None where the trace holds no program span."""
    win = [(a, b) for k, a, b in events["spans"] if k == T.WINDOW_SPAN]
    program = events.get("program") or []
    if not win or not events["devices"] or not program:
        return None
    lo, hi = win[0]
    pieces = innermost([(a, b, name) for name, a, b, _ in program], lo, hi)
    idle_in: Dict[str, float] = defaultdict(float)
    by_program: Dict[str, float] = defaultdict(float)
    top = None
    names = sorted(events["devices"])
    for plane in names:
        ops = events["devices"][plane]
        busy = T.union(T.clip([(a, b) for _, a, b in ops], lo, hi))
        idle = T.gaps(busy, lo, hi)
        for a, b, name in _cut(idle, pieces):
            idle_in[name] += b - a
        runs = defaultdict(list)
        for (_, a, b), m in zip(ops, events["device_modules"][plane]):
            runs[m].append((a, b))
        for m, ivs in runs.items():
            by_program[m] += T.length(T.union(T.clip(ivs, lo, hi)))
        if top is None:
            top = sorted(idle, key=lambda g: g[0] - g[1])[:T.TOP]
    n, ns = len(names), 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "idle_s_in_program": {k: v / n * ns for k, v in
                              sorted(idle_in.items(), key=lambda kv: -kv[1])},
        "program_idle_gaps": [[_label(pieces, (a + b) / 2), (b - a) * ns]
                              for a, b in top],
        "device_s_by_program": {k: v / n * ns for k, v in
                                sorted(by_program.items(),
                                       key=lambda kv: -kv[1])},
    }


def _cut(ivs, pieces):
    """``ivs`` (sorted, disjoint) cut at the pieces' bounds, each part
    named by its piece: ``(a, b, name)``."""
    out, j = [], 0
    for a, b in ivs:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            if min(b, pb) > max(a, pa):
                out.append((max(a, pa), min(b, pb), name))
            k += 1
    return out


def traced_wave(run) -> Optional[dict]:
    """``reduce`` of this run's trace: the newest ``.xplane.pb`` the
    harness wrote. None without a traced wave or a program span in it."""
    if run.trace is None:
        return None
    found = sorted(glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return reduce(extract(found[-1])) if found else None
