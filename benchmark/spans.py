"""The benchmark's own tracer, behind libs/trace.set_tracer.

The program's StageTracer keeps totals and a ring of 1,024 intervals.
This one keeps every span's start and end on time.perf_counter, so that
a window's totals can be cut exactly at its two marks, and so that the
idle gaps of a profiler trace can be named by the stage the host was in
(xplane.py maps the trace's clock onto this one).
"""

from __future__ import annotations

import threading
import time


class SpanTracer:
    """record() is the one method libs/trace's spans call."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: list = []          # (subsystem.stage, start, end)

    def record(self, subsystem: str, stage: str, seconds: float,
               end: float | None = None, fields=None) -> None:
        t1 = end if end is not None else time.perf_counter()
        with self._lock:
            self._spans.append((f"{subsystem}.{stage}", t1 - seconds, t1))

    def spans(self, t0: float = float("-inf"),
              t1: float = float("inf")) -> list:
        """Spans that END inside [t0, t1], oldest first."""
        with self._lock:
            return [s for s in self._spans if t0 <= s[2] <= t1]

    def totals(self, t0: float = float("-inf"),
               t1: float = float("inf")) -> dict:
        """{"subsystem.stage": {"count": n, "seconds": s}} of the spans
        that end inside [t0, t1]."""
        out: dict = {}
        for name, a, b in self.spans(t0, t1):
            rec = out.setdefault(name, {"count": 0, "seconds": 0.0})
            rec["count"] += 1
            rec["seconds"] += b - a
        return out
