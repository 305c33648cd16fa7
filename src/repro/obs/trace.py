"""Span/event tracing for the serving plane (DESIGN.md §6).

A ``Tracer`` records a flat list of ``TraceEvent``s — complete spans
(``ph="X"``: name, start, duration) and instant events (``ph="i"``) —
against an injected clock (``repro.obs.clock``). Every span also enters
a ``jax.profiler.TraceAnnotation`` of its name, so when the JAX
profiler is on, the program's spans land on the host plane of the same
trace as the device's ops, on the profiler's clock; with the profiler
off an annotation records nothing.

Instrumented call sites (``CachedBlockStore``, ``AsyncFetchQueue``,
``HostSegmentServer``, ``SegmentServer``, ``QueryCoordinator``,
``RepackScheduler``) all take the tracer as an *optional*
collaborator: the default is ``None`` and every hook is behind an
``if tracer is not None`` guard, so the untraced hot path pays one
attribute test — results and counters are identical with tracing on or
off (asserted in tests).

Naming conventions (DESIGN.md §6): event names are dotted
``plane.what`` — ``coord.batch``, ``coord.segment``, ``host.search``,
``server.dispatch``, ``server.wait``, ``io.read``, ``io.fetch_submit``,
``io.fetch_complete``, ``sched.eval``, ``sched.repack``. Categories
group planes: ``serve`` | ``io`` | ``sched``.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator, List

import jax

from repro.obs.clock import ManualClock, WallClock


@dataclasses.dataclass
class TraceEvent:
    """One trace record (times in µs)."""
    name: str
    cat: str
    ph: str                 # "X" complete span | "i" instant
    ts_us: float            # start timestamp
    dur_us: float = 0.0     # span duration (X only)
    track: str = "main"     # the row it belongs to (a segment, "coord")
    args: Dict = dataclasses.field(default_factory=dict)


class Tracer:
    """Bounded in-memory trace buffer with span/event recording.

    ``max_events`` caps memory on long-lived serving processes: the
    buffer keeps the *first* ``max_events`` records and counts the
    rest in ``dropped`` (head-capture semantics — a trace documents a
    window, it is not a ring of the most recent past)."""

    def __init__(self, clock=None, max_events: int = 100_000):
        self.clock = clock if clock is not None else WallClock()
        self.max_events = int(max_events)
        self.events: List[TraceEvent] = []
        self.dropped = 0

    # ----------------------------------------------------------- record
    def _push(self, ev: TraceEvent) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(ev)

    def event(self, name: str, cat: str = "serve", track: str = "main",
              **args) -> None:
        """Record an instant event at the current clock."""
        self._push(TraceEvent(name=name, cat=cat, ph="i",
                              ts_us=self.clock.now_us(), track=track,
                              args=args))

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "serve", track: str = "main",
             **args) -> Iterator[Dict]:
        """Record a complete span around the ``with`` body, inside a
        profiler ``TraceAnnotation`` of the same name.

        Yields the args dict so the body can attach outcomes
        (``sp["tier"] = 1``) that land in the finished event."""
        with jax.profiler.TraceAnnotation(name):
            t0 = self.clock.now_us()
            try:
                yield args
            finally:
                t1 = self.clock.now_us()
                self._push(TraceEvent(name=name, cat=cat, ph="X",
                                      ts_us=t0, dur_us=max(t1 - t0, 0.0),
                                      track=track, args=args))

    # ------------------------------------------------------------ access
    def __len__(self) -> int:
        return len(self.events)

    def clear(self) -> None:
        self.events.clear()
        self.dropped = 0

    def by_name(self, name: str) -> List[TraceEvent]:
        return [e for e in self.events if e.name == name]


def manual_tracer(auto_tick_us: float = 1.0) -> Tracer:
    """A tracer on a ``ManualClock`` — the deterministic test/CI
    configuration the clock-injection rule (DESIGN.md §6) prescribes."""
    return Tracer(clock=ManualClock(auto_tick_us=auto_tick_us))
