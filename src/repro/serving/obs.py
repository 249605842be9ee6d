"""Measured spans of the serve path, on the host clock and in the profiler.

One tracer per process, off by default (``enable``, ``reset``,
``snapshot``). ``span(name, rid)`` wraps one call of the serve path. On, it
records ``Record(name, t0, t1, parent, rid)`` with ``time.perf_counter`` and
opens ``jax.profiler.TraceAnnotation("pdc." + name, rid=rid)``, so that a
profiler trace holds the same span on the device operations' clock, with the
request id as an event stat. ``parent`` is the index of the innermost span
open around it. ``begin``/``end`` time a wait that starts in one call and
ends in another (the ``queue.*`` waits); those stay in memory only, with no
parent. Off, ``span`` returns one shared no-op context manager and records
nothing, and ``begin``/``end`` return at once.

``count(name, n)`` records a timed counter, ``Count(name, t, n)``, read
back by ``counters()``; off, it returns at once like ``span``.

Spans are per call, never per block or per layer. The scheduler's
``RequestTrace`` is the virtual clock, a deterministic test oracle; these are
the measured times.

Spans, with the span they open inside:

* ``serve.wave``: ``ServingSystem.serve``;
* ``queue.prefill`` (wait): request taken into ``serve`` -> its prefill;
* ``prefill`` (in ``serve.wave``): ``PrefillEngine.run``, and inside it
  ``prefill.ems_fetch`` (EMS prefix match and fetch), ``prefill.ems_insert``
  (fetched blocks into a fresh cache), ``prefill.compute`` (the prefill
  programs' dispatch), ``prefill.ems_pack`` (``cache_ops.pack_blocks`` and
  its copy to the host), ``prefill.ems_store`` (``EMSService.store``),
  ``prefill.first_token`` (the first token's read to the host);
* ``handoff.transfer`` (in ``serve.wave``): the KV transfer to decode;
* ``queue.decode`` (wait): handed off -> admitted into a decode slot;
* ``serve.admit`` (in ``serve.wave``): one admission pass, and inside it
  ``handoff.insert``: ``DecodePool.add`` into a slot;
* ``decode.chunk`` (in ``serve.wave``): ``DecodeEngine.step_chunk``, and
  inside it, on the scanned path, ``decode.dispatch`` (the decode loop's
  call), ``decode.sync`` (its outputs' read to the host) and
  ``decode.commit`` (the host's per-slot bookkeeping).

Counters:

* ``decode.held_routings``: per scanned decode call of a MoE model, the
  (token, k) routings of live slots onto the experts held here, summed over
  the MoE layers and the call's iterations (``decode_loop``'s ``held``, read
  in the ``decode.sync`` transfer).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax

#: prefix of every span's name in a profiler trace
TRACE_PREFIX = "pdc."


class Record(NamedTuple):
    name: str
    t0: float
    t1: Optional[float]      # None while the span is still open
    parent: Optional[int]    # index of the enclosing span's record
    rid: Any


class Count(NamedTuple):
    name: str
    t: float                 # host clock when counted
    n: int


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Span:
    __slots__ = ("tracer", "name", "rid", "record", "annotation")

    def __init__(self, tracer: "Tracer", name: str, rid):
        self.tracer, self.name, self.rid = tracer, name, rid

    def __enter__(self):
        tr = self.tracer
        if self.rid is None:
            self.annotation = jax.profiler.TraceAnnotation(
                TRACE_PREFIX + self.name)
        else:
            self.annotation = jax.profiler.TraceAnnotation(
                TRACE_PREFIX + self.name, rid=self.rid)
        self.annotation.__enter__()
        parent = tr._stack[-1] if tr._stack else None
        self.record = [self.name, time.perf_counter(), None, parent, self.rid]
        tr._stack.append(len(tr.records))
        tr.records.append(self.record)
        return self

    def __exit__(self, *exc):
        self.record[2] = time.perf_counter()
        if self.tracer._stack:
            self.tracer._stack.pop()
        self.annotation.__exit__(*exc)
        return False


class Tracer:
    def __init__(self):
        self.on = False
        self.records: List[list] = []
        self._stack: List[int] = []                       # open span indices
        self._open: Dict[Tuple[str, Any], float] = {}     # waits begun
        self.counts: List[Count] = []

    def enable(self, on: bool = True) -> None:
        self.on = bool(on)

    def reset(self) -> None:
        """Drop every record, count and open span or wait."""
        self.records, self._stack, self._open = [], [], {}
        self.counts = []

    def snapshot(self) -> List[Record]:
        return [Record(*r) for r in self.records]

    def span(self, name: str, rid=None):
        if not self.on:
            return _NOOP
        return _Span(self, name, rid)

    def count(self, name: str, n: int) -> None:
        if self.on:
            self.counts.append(Count(name, time.perf_counter(), n))

    def counters(self) -> List[Count]:
        return list(self.counts)

    def begin(self, name: str, rid) -> None:
        if self.on:
            self._open[(name, rid)] = time.perf_counter()

    def end(self, name: str, rid) -> None:
        """Close the wait ``begin`` opened; one never begun is ignored."""
        if self.on:
            t0 = self._open.pop((name, rid), None)
            if t0 is not None:
                self.records.append([name, t0, time.perf_counter(), None, rid])


TRACER = Tracer()
enable = TRACER.enable
reset = TRACER.reset
snapshot = TRACER.snapshot
span = TRACER.span
count = TRACER.count
counters = TRACER.counters
begin = TRACER.begin
end = TRACER.end


def summary(records: List[Record]) -> Dict[str, Tuple[int, float]]:
    """Count and total host seconds of each closed span or wait, by name."""
    out: Dict[str, Tuple[int, float]] = {}
    for r in records:
        if r.t1 is not None:
            n, s = out.get(r.name, (0, 0.0))
            out[r.name] = (n + 1, s + r.t1 - r.t0)
    return out
