"""The program's own marks in a profiler trace.

The engine and the scheduler mark the profiler's trace with host spans
named ``engine.<span>`` and ``sched.<span>`` (``repro.obs.trace``): one
``sched.step`` per scheduler tick, holding ``engine.tick``, which holds
``engine.dispatch`` (the jitted step's call) and ``engine.token_wait`` (the
tick's one wait for the device). :func:`load_spans` reads them from an
``.xplane.pb``; the reductions below work on plain :class:`Event` lists, so
they can be checked on a trace made by hand.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

from bench.harness.tracing import Event, busy_seconds

PREFIXES = ("engine.", "sched.")
STEP = "sched.step"
WAIT = "engine.token_wait"


def load_spans(path) -> List[Event]:
    """The program's host spans (names starting ``engine.`` or ``sched.``),
    by start."""
    from jax.profiler import ProfileData

    spans = [
        Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(PREFIXES)
    ]
    return sorted(spans, key=lambda e: e.start_ns)


def within(e: Event, lo: float, hi: float) -> bool:
    return lo <= e.start_ns and e.end_ns <= hi


def tick_host_ms(spans: Iterable[Event], lo: float, hi: float) -> Optional[float]:
    """Host ms per scheduler tick: the mean, over the ``sched.step`` spans
    wholly inside ``[lo, hi]``, of the step's time less the time its
    ``engine.token_wait`` spans cover. ``None`` where no step lies wholly
    inside."""
    spans = list(spans)
    steps = [e for e in spans if e.name == STEP and within(e, lo, hi)]
    if not steps:
        return None
    waits = [e for e in spans if e.name == WAIT]
    host = [s.seconds - busy_seconds(waits, s.start_ns, s.end_ns) for s in steps]
    return 1e3 * sum(host) / len(host)
