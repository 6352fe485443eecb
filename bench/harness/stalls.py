"""What the host was doing while a window tick ran long.

A daemon thread wakes every ``every`` seconds. Each time it reads the host's
counters (:func:`host_counters`: the measuring thread's CPU and run-queue
time, the process's page faults and context switches, the machine's stolen
CPU time), and once the current tick has run ``after`` seconds it also keeps
the measuring thread's innermost Python frames. After the window, a long
tick's samples say where it stood (in a jitted call's dispatch or tracing,
waiting on the device for a token, or in plain Python), and the counters
around it say whether the process ran at all: a wake of this thread that
comes far later than ``every`` means that no Python thread of the process
ran in between. The wakes take the GIL for microseconds, ten times a second.
"""
from __future__ import annotations

import resource
import sys
import threading
import time
import traceback
from collections import Counter
from typing import Dict, List, Optional, Tuple


def host_counters(tid: int) -> Dict[str, float]:
    """Counters of thread ``tid`` and of its process and machine; a counter
    the platform does not give is left out."""
    out: Dict[str, float] = {}
    try:
        with open(f"/proc/self/task/{tid}/schedstat") as f:
            run_ns, wait_ns, _ = f.read().split()
        out["thread_cpu_s"] = int(run_ns) * 1e-9
        out["thread_runqueue_s"] = int(wait_ns) * 1e-9
    except (OSError, ValueError):
        pass
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        if cpu[0] == "cpu" and len(cpu) > 8:
            out["machine_steal_ticks"] = int(cpu[8])
    except (OSError, ValueError, IndexError):
        pass
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out.update(minor_faults=ru.ru_minflt, major_faults=ru.ru_majflt,
               voluntary_switches=ru.ru_nvcsw, involuntary_switches=ru.ru_nivcsw)
    return out


class StallWatch:
    def __init__(self, after: float = 0.5, every: float = 0.1, depth: int = 8):
        self.after, self.every, self.depth = after, every, depth
        self.samples: Dict[int, List[str]] = {}
        self.trail: List[Tuple[float, Dict[str, float]]] = []
        self._tick: Optional[Tuple[int, float]] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._target = 0
        self._tid = 0

    def start(self) -> None:
        """Watch the calling thread."""
        self._target = threading.get_ident()
        self._tid = threading.get_native_id()
        self._thread = threading.Thread(target=self._look, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()

    def tick(self, index: Optional[int]) -> None:
        """Tick ``index`` starts now; ``None``: no tick is running."""
        self._tick = None if index is None else (index, time.perf_counter())

    def _look(self) -> None:
        while True:
            self.trail.append((time.perf_counter(), host_counters(self._tid)))
            if self._stop.wait(self.every):
                # one reading more, after the last tick
                self.trail.append((time.perf_counter(), host_counters(self._tid)))
                return
            tick = self._tick
            if tick is None or time.perf_counter() - tick[1] < self.after:
                continue
            frame = sys._current_frames().get(self._target)
            if frame is None:
                continue
            where = traceback.extract_stack(frame)[-self.depth:]
            self.samples.setdefault(tick[0], []).append(" < ".join(
                f"{f.filename.rsplit('/', 1)[-1]}:{f.lineno} {f.name}"
                for f in reversed(where)))

    def report(self, index: int) -> List[list]:
        """Tick ``index``'s distinct samples, innermost frame first, with the
        number of looks that saw each."""
        return [[s, n] for s, n in Counter(self.samples.get(index, [])).most_common()]

    def host(self, start: float, end: float) -> Dict[str, float]:
        """How the counters moved from the last wake at or before ``start``
        to the first at or after ``end`` (``perf_counter`` times), and the
        longest time between two wakes in that span, ``watch_gap_s``."""
        before = [s for s in self.trail if s[0] <= start]
        after = [s for s in self.trail if s[0] >= end]
        if not before or not after:
            return {}
        a, b = before[-1], after[0]
        inside = [t for t, _ in self.trail if a[0] <= t <= b[0]]
        out = {k: b[1][k] - a[1][k] for k in a[1] if k in b[1]}
        out["span_s"] = b[0] - a[0]
        out["watch_gap_s"] = max(y - x for x, y in zip(inside, inside[1:]))
        return out
