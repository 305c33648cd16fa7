"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

* The window: the host span the benchmark wraps its measured window in
  (a ``jax.profiler.TraceAnnotation``), on the trace's clock.
* Per device plane (``/device:TPU:<i>``): the busy time, the union of
  the intervals of its XLA operations inside the window; and each
  operation's self time (its intervals less those of operations nested
  inside it on the same line), so that a loop and the operations it runs
  are not counted twice; apart from them, the same for the line of
  asynchronous operations (copies, collectives), which overlap the
  others and count toward neither the busy time nor the breakdown.
* Host spans: every event on the host plane's threads, as (name, start,
  end), for attributing device idle gaps to what the host was doing.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


@dataclasses.dataclass
class DeviceTrace:
    name: str
    busy_ns: float                       # union of op intervals in window
    self_ns: Dict[str, float]            # op name -> self time in window
    count: Dict[str, int]                # op name -> events in window
    intervals: List[Interval]            # merged busy intervals in window
    async_ns: Dict[str, float] = dataclasses.field(default_factory=dict)
    async_count: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TraceSummary:
    window: Interval                     # ns, trace clock
    devices: List[DeviceTrace]
    host_spans: List[Tuple[str, float, float]]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def op_ns(self, pattern: str, lines=("sync",)) -> List[float]:
        """Per device: the self time of every op whose name matches
        ``pattern`` (a regular expression, searched), on the line of
        XLA ops (``"sync"``) and/or of asynchronous ones (``"async"``)."""
        rx = re.compile(pattern)
        return [sum(t for key in lines for name, t in
                    (d.self_ns if key == "sync" else d.async_ns).items()
                    if rx.search(name)) for d in self.devices]

    def op_count(self, pattern: str, lines=("sync",)) -> List[int]:
        rx = re.compile(pattern)
        return [sum(c for key in lines for name, c in
                    (d.count if key == "sync" else d.async_count).items()
                    if rx.search(name)) for d in self.devices]


def _clip(iv: Interval, window: Interval) -> Optional[Interval]:
    lo, hi = max(iv[0], window[0]), min(iv[1], window[1])
    return (lo, hi) if hi > lo else None


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _self_times(events: List[Tuple[str, float, float]], window: Interval):
    """Self time and count per name inside ``window``: an event's own
    interval less those of the events nested directly in it (same
    line), clipped to the window."""
    self_ns: Dict[str, float] = collections.defaultdict(float)
    count: Dict[str, int] = collections.defaultdict(int)
    # longest first among equal starts, so a parent precedes its child
    order = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    stack: List[list] = []          # [name, start, end, children]

    def close(top):
        name, lo, hi, children = top
        if _clip((lo, hi), window) is None:
            return
        cur, own = lo, 0.0
        for c_lo, c_hi in children + [(hi, hi)]:
            seg = _clip((cur, c_lo), window)
            if seg is not None:
                own += seg[1] - seg[0]
            cur = max(cur, c_hi)
        self_ns[name] += own
        count[name] += 1

    for name, lo, hi in order:
        while stack and lo >= stack[-1][2]:
            close(stack.pop())
        if stack:
            stack[-1][3].append((lo, min(hi, stack[-1][2])))
        stack.append([name, lo, hi, []])
    while stack:
        close(stack.pop())
    return dict(self_ns), dict(count)


def load(path: str, window_span: str = "bench.window") -> TraceSummary:
    """Read ``path`` and reduce it to the window named by the host span
    ``window_span`` (its first occurrence)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[Tuple[str, float, float]] = []
    raw: Dict[int, Tuple[str, Dict[str, list]]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
        elif m:
            raw[int(m.group(1))] = (plane.name, {
                line.name: [(ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, ASYNC_LINE)})
    spans = [s for s in host if s[0] == window_span]
    if not spans:
        raise ValueError(f"no host span {window_span!r} in {path}")
    window = (spans[0][1], spans[0][2])
    devices = []
    for idx in sorted(raw):
        name, lines = raw[idx]
        clipped = [c for c in (_clip((lo, hi), window)
                               for _, lo, hi in lines.get(OPS_LINE, []))
                   if c]
        merged = _union(clipped)
        self_ns, count = _self_times(lines.get(OPS_LINE, []), window)
        async_ns, async_count = _self_times(lines.get(ASYNC_LINE, []),
                                            window)
        devices.append(DeviceTrace(
            name=name, busy_ns=sum(hi - lo for lo, hi in merged),
            self_ns=self_ns, count=count, intervals=merged,
            async_ns=async_ns, async_count=async_count))
    return TraceSummary(window=window, devices=devices, host_spans=host)


def idle_gaps(summary: TraceSummary, device: int = 0,
              phases: Tuple[str, ...] = ("bench.",)
              ) -> List[Tuple[str, float]]:
    """The device's idle time inside the window, by what the host was
    doing: each gap between busy intervals is named by the innermost
    host span that covers its midpoint and whose name starts with one of
    ``phases`` (``"idle"`` where none does), and the gaps are summed
    per name, longest total first. Seconds."""
    d = summary.devices[device]
    lo_w, hi_w = summary.window
    edges = [lo_w] + [x for iv in d.intervals for x in iv] + [hi_w]
    spans = [s for s in summary.host_spans
             if s[0].startswith(phases)]
    totals: Dict[str, float] = collections.defaultdict(float)
    for lo, hi in zip(edges[0::2], edges[1::2]):
        if hi <= lo:
            continue
        mid = (lo + hi) / 2
        cover = [s for s in spans if s[1] <= mid <= s[2]]
        name = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
            else "idle"
        totals[name] += (hi - lo) / 1e9
    return sorted(totals.items(), key=lambda kv: -kv[1])


def short_name(hlo: str, limit: int = 160) -> str:
    """An XLA op's event name (its HLO instruction) without layouts,
    operand names and attributes: ``%fusion.294 = f32[761856]
    fusion(f32[256,16,256], s32[761856])``."""
    s = re.sub(r"\{[^{}]*\}", "", re.sub(r"\{[^{}]*\}", "", hlo))
    s = re.sub(r" %[\w.\-]+", "", s)
    cut = s.find("), ")
    return (s[:cut + 1] if cut >= 0 else s)[:limit]


def top_ops(summary: TraceSummary, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The ``n`` ops with the most self time, by ``short_name``, seconds
    averaged over the devices."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for d in summary.devices:
        for name, t in d.self_ns.items():
            tot[short_name(name)] += t / 1e9 / len(summary.devices)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
