"""Reduce a profiler trace to intervals, and intervals to numbers.

:func:`load_xplane` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a :class:`Trace`: the operations and XLA programs that ran on the
device, and the benchmark's own host spans (``jax.profiler.TraceAnnotation``
names starting with ``bench.``). Everything after that works on plain
``(name, start_ns, end_ns)`` events, so it can be checked on a trace made
by hand.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)        # device operations
    modules: List[Event] = field(default_factory=list)    # device programs
    spans: List[Event] = field(default_factory=list)      # benchmark host spans

    def window(self, span: str) -> Optional[Tuple[float, float]]:
        """(start_ns, end_ns) of the first host span named ``span``."""
        for e in self.spans:
            if e.name == span:
                return e.start_ns, e.end_ns
        return None


def load_xplane(path) -> Trace:
    """Device operations and programs from every TPU plane, and the host
    spans the benchmark annotated."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                dest = {OPS_LINE: tr.ops, MODULES_LINE: tr.modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append(Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        tr.spans.append(
                            Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        )
    tr.spans.sort(key=lambda e: e.start_ns)
    return tr


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to ``[lo, hi]``; those outside it are dropped."""
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def merge(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as disjoint sorted intervals."""
    iv = sorted((e.start_ns, e.end_ns) for e in events if e.end_ns > e.start_ns)
    out: List[List[float]] = []
    for s, t in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_seconds(events: Iterable[Event], lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which at least one event runs."""
    return sum(t - s for s, t in merge(clip(events, lo, hi))) * 1e-9


def idle_gaps(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The intervals of ``[lo, hi]`` in which no event runs."""
    gaps, at = [], lo
    for s, t in merge(clip(events, lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, t)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def label_gap(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The innermost host span that holds the gap's midpoint, else
    ``"outside bench spans"``."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for e in spans:
        if e.start_ns <= mid <= e.end_ns and (
            best is None or e.end_ns - e.start_ns < best.end_ns - best.start_ns
        ):
            best = e
    return best.name if best is not None else "outside bench spans"


def longest_gaps(events, lo, hi, spans, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps as ``[label, seconds]``, longest first."""
    gaps = sorted(idle_gaps(events, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[label_gap(g, spans), (g[1] - g[0]) * 1e-9] for g in gaps]


def leaves(events: Iterable[Event]) -> List[Event]:
    """The events that hold no other event. On the TPU's op line a loop
    (``while``) is an event that spans the ops of its body, which would
    otherwise be counted twice."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start_ns >= e.end_ns or nxt.end_ns > e.end_ns:
            out.append(e)
    return out


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(name: str) -> str:
    """An HLO op's event name is its whole instruction text; keep its id,
    its first result shape and, for a custom call, the target."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = _SHAPE.search(rest)
    out = head + (" " + shape.group(0) if shape else "")
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return out + (f" custom-call {target.group(1)}" if target else "")


def top_ops(events: Iterable[Event], n: int = 10) -> List[list]:
    """The ``n`` operations that took most device time, as ``[name, seconds]``,
    counted over :func:`leaves` and named by :func:`short_name`."""
    tot: dict = defaultdict(float)
    for e in leaves(events):
        tot[short_name(e.name)] += e.seconds
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def matches(name: str, patterns: Sequence[str]) -> bool:
    """Whether ``name`` contains any of ``patterns``."""
    return any(p in name for p in patterns)
