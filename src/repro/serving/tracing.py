"""Spans and counters of the serving engine's step, on the profiler's clock.

One `Tracer` per `Engine` (`Engine.tracer`).  `span(name)` enters a
`jax.profiler.TraceAnnotation(name)`, so under a profiler session the span
lands on the trace's host plane on the clock the device ops are placed on,
and times itself with the engine's clock:

  * its duration is added, under its name, to the open step record;
  * process-lifetime totals per name keep its count, seconds and self
    seconds (duration less what its child spans cover), with the name of
    the span it ran inside.

`step(index, slots)` opens the record of one `Engine.step` and its
`engine.step` span.  A record holds the step's index, start and end on the
engine clock, seconds per span name, and the step's counters (`decode_rows`,
`slots`, `prefill_tokens`, `emitted`, `admitted` / `finished` / `preempted`
request uids, `pages_in_use`, `pages_total`, `tables_refreshed`).  The uids
link a request to the steps that served it.

Records live in a ring of RING_STEPS steps.  `records(t0, t1)` returns the
steps that ended in [t0, t1], and raises where the ring has dropped a step
that ended at or after t0, so that a reader never averages over part of its
window.  `tracers()` lists the live tracers of this process, for a reader
that holds no engine (a benchmark's metric files, an exporter).

Spans sit at phase boundaries only, never per slot or per token.  Without a
profiler session a TraceAnnotation costs about a microsecond.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import weakref
from typing import Callable

import jax

# About two hours of 104 ms decode steps; fixed, so memory stays bounded.
RING_STEPS = 65536

# Counters summed into the tracer's lifetime totals (list-valued ones by
# their length).  pages_in_use / pages_total / slots are gauges.
ADDITIVE = ("decode_rows", "prefill_tokens", "emitted", "admitted", "finished",
            "preempted", "tables_refreshed")

_LIVE: weakref.WeakSet = weakref.WeakSet()


def tracers() -> list["Tracer"]:
    """The live tracers of this process, oldest first."""
    return sorted(_LIVE, key=lambda t: t.serial)


@dataclasses.dataclass(slots=True)
class StepRecord:
    index: int
    t0: float
    t1: float = float("nan")
    spans: dict[str, float] = dataclasses.field(default_factory=dict)
    slots: int = 0
    decode_rows: int = 0        # rows that decoded in the step's dispatch
    prefill_tokens: int = 0     # real prompt tokens prefilled
    emitted: int = 0            # tokens committed
    admitted: list[int] = dataclasses.field(default_factory=list)
    finished: list[int] = dataclasses.field(default_factory=list)
    preempted: list[int] = dataclasses.field(default_factory=list)
    pages_in_use: int | None = None
    pages_total: int | None = None
    tables_refreshed: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def span_s(self, name: str) -> float:
        return self.spans.get(name, 0.0)


@dataclasses.dataclass(slots=True)
class SpanTotal:
    parent: str | None
    count: int = 0
    seconds: float = 0.0
    self_seconds: float = 0.0


class _Span:
    __slots__ = ("tracer", "name", "annotation", "t0", "t1", "children")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.annotation = jax.profiler.TraceAnnotation(name, **args)
        self.t0 = self.t1 = 0.0
        self.children = 0.0

    def __enter__(self) -> "_Span":
        self.annotation.__enter__()
        self.tracer._stack.append(self)
        self.t0 = self.tracer.clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self.tracer
        self.t1 = tr.clock()
        dur = self.t1 - self.t0
        tr._stack.pop()
        parent = tr._stack[-1] if tr._stack else None
        if parent is not None:
            parent.children += dur
        tot = tr.totals.get(self.name)
        if tot is None:
            tot = tr.totals[self.name] = SpanTotal(
                parent.name if parent is not None else None)
        tot.count += 1
        tot.seconds += dur
        tot.self_seconds += dur - self.children
        rec = tr.current
        if rec is not None:
            rec.spans[self.name] = rec.spans.get(self.name, 0.0) + dur
        self.annotation.__exit__(*exc)
        return False


class _Step:
    __slots__ = ("tracer", "record", "span")

    def __init__(self, tracer: "Tracer", index: int, slots: int):
        self.tracer = tracer
        self.record = StepRecord(index=index, t0=0.0, slots=slots)
        self.span = _Span(tracer, "engine.step", {"step": index})

    def __enter__(self) -> StepRecord:
        self.tracer.current = self.record
        self.span.__enter__()
        self.record.t0 = self.span.t0
        return self.record

    def __exit__(self, *exc) -> bool:
        self.span.__exit__(*exc)
        rec = self.record
        rec.t1 = self.span.t1
        tr = self.tracer
        tr.current = None
        if len(tr._ring) == RING_STEPS:
            tr._dropped_t1 = tr._ring[0].t1
        tr._ring.append(rec)
        tr.steps += 1
        for name in ADDITIVE:
            v = getattr(rec, name)
            tr.counters[name] += len(v) if isinstance(v, list) else v
        return False


class Tracer:
    """Spans, step records and counters of one engine (module docstring)."""

    _serial = 0

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.current: StepRecord | None = None
        self.totals: dict[str, SpanTotal] = {}
        self.counters = dict.fromkeys(ADDITIVE, 0)
        self.steps = 0
        self._stack: list[_Span] = []
        self._ring: collections.deque[StepRecord] = collections.deque(maxlen=RING_STEPS)
        self._dropped_t1: float | None = None
        Tracer._serial += 1
        self.serial = Tracer._serial
        _LIVE.add(self)

    def span(self, name: str, **args) -> _Span:
        """Context manager: a span named `name` (annotation args `args`)."""
        return _Span(self, name, args)

    def step(self, index: int, slots: int) -> _Step:
        """Context manager: the record and `engine.step` span of one step;
        yields the StepRecord, which the engine fills."""
        return _Step(self, index, slots)

    def add(self, counter: str, n: int = 1) -> None:
        """Add `n` to an integer counter of the open step (none: ignored)."""
        if self.current is not None:
            setattr(self.current, counter, getattr(self.current, counter) + n)

    def note(self, counter: str, uid: int) -> None:
        """Append request `uid` to a uid counter of the open step."""
        if self.current is not None:
            getattr(self.current, counter).append(uid)

    def records(self, t0: float, t1: float) -> list[StepRecord]:
        """Step records that ended in [t0, t1] (engine clock), oldest first.
        Raises where the ring has dropped a step that ended at or after t0."""
        if self._dropped_t1 is not None and self._dropped_t1 >= t0:
            raise LookupError(
                f"the ring of {RING_STEPS} steps dropped steps that ended at or "
                f"after {t0} (newest dropped ended {self._dropped_t1})")
        return [r for r in self._ring if t0 <= r.t1 <= t1]

    def recent(self, n: int) -> list[StepRecord]:
        """The newest `n` step records, oldest first."""
        return list(itertools.islice(reversed(self._ring), n))[::-1]

    def summary(self) -> dict:
        """Per-span lifetime totals and the additive counters' totals."""
        return {
            "steps": self.steps,
            "spans": {name: {"count": t.count, "seconds": t.seconds,
                             "self_seconds": t.self_seconds, "parent": t.parent}
                      for name, t in self.totals.items()},
            "counters": dict(self.counters),
        }
