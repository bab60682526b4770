"""Stage-span tracing: per-stage wall-clock timers for the protocol
hot paths (decode -> verify-dispatch -> device -> apply -> store), and
below them the inside of `apply` (state.*) and every RLC dispatch of the
verify plane (verify.*).

The host-residual breakdown that blocksync_profile_r5.jsonl measured
with a one-off script becomes a first-class observable: reactors and
the light client open spans around each stage, a process-wide
StageTracer accumulates (count, seconds) per (subsystem, stage), and —
when the node runs with instrumentation — every span also lands in the
libs/metrics.py registry as a histogram observation
(cometbft_trace_stage_duration_seconds{subsystem, stage}).

No reference analog: the reference profiles with pprof; here the
interesting question is how much of a block's wall time is host work
around the single device dispatch, so the stages are first-class.

The seam mirrors libs/metrics.set_device_metrics: a module-level
tracer the crypto/reactor layers reach without any node wiring.  With
no tracer installed a span is a shared no-op object — the hot paths
pay one global read and an `is None` test.

Two clocks round the device, both the host's.  `<subsystem>.device` is
opened by the verify pipeline round a whole WINDOW's dispatch and
readback (crypto/dispatch.py); a batch that reaches the device through
the synchronous seam (crypto/batch.py: the apply-time LastCommit
remainder) never passes it.  `verify.dispatch` and `verify.readback`
are the per-dispatch ones: they sit in crypto/ed25519's rlc_verify*
funnel, which windows and seam batches both pass, and split a dispatch
into the host's enqueue and the host's wait for the verdict bit.

Spans nest.  A span opened inside another on the same thread carries
the enclosing span's "subsystem.stage" as `parent` in its interval's
fields, and the tracer keeps each span's children's seconds, so
self_seconds() says what of a span no child names.
"""

from __future__ import annotations

import threading
import time

from . import lockrank

# canonical stage names for the blocksync ingest pipeline; other
# subsystems (light) reuse the subset that applies to them
BLOCKSYNC_STAGES = ("decode", "verify_dispatch", "device", "apply",
                    "store")
# extra stages emitted by the overlapped verify pipeline
# (crypto/dispatch.py): collect runs in the submitter, host_pack in
# the staging thread — concurrent with the previous window's device
PIPELINE_STAGES = ("collect", "host_pack")
# validate (light/verifier.verify_adjacent: every check of a header
# that is not a signature) runs inside collect; verdict_wait is the
# client blocked on a window's verdict; divergence the witness
# cross-check
LIGHT_STAGES = ("fetch", "verify_dispatch", "validate", "device",
                "verdict_wait", "divergence", "store")
# subsystem "state": the inside of BlockExecutor's validate + apply
# (state/execution.py), one span a block each but `save` and `events`,
# which the crash-safety order opens twice
APPLY_STAGES = ("validate", "abci_finalize", "save", "update",
                "abci_commit", "events")
# subsystem "verify": one RLC batch through the device funnel.
# host_pack only where the batch did not come packed from the
# pipeline's staging thread (that one is <subsystem>.host_pack)
VERIFY_STAGES = ("host_pack", "dispatch", "readback")
# what an RLC reject adds (crypto/batch._device_verify): localize round
# the whole per-signature arm, inside it host_pack (packer=python: every
# h hashed in the interpreter, where the batch did not come parsed), the
# pack, and the per-signature program's own enqueue and wait
LOCALIZE_STAGES = ("localize", "persig_pack", "persig_dispatch",
                   "persig_readback")
# subsystem "blocksync", closed with close(): reject runs from a
# window's false verdict to the heights it named verified true, refetch
# inside it from the pairs' redo to their blocks back in the pool
REJECT_STAGES = ("reject", "refetch")

# interval ring size per (subsystem, stage): enough to prove overlap
# across a bench run without unbounded growth on long-lived nodes.
# Per stage, not over all: a dozen state.*/verify.* spans a block
# would otherwise push the few device/collect intervals a window
# leaves out of a shared ring before overlap_seconds reads them
MAX_INTERVALS = 1024


class StageTracer:
    """Accumulates span durations per (subsystem, stage); optionally
    mirrors every observation into a metrics.TraceMetrics bundle.
    Also keeps a bounded ring of (start, end) INTERVALS per
    (subsystem, stage) so concurrency between stages — the overlapped
    pipeline's whole claim — is provable from the record, not
    asserted."""

    def __init__(self, metrics=None):
        self._mtx = lockrank.RankedLock("trace.stage")
        self._totals: dict[tuple[str, str], list] = {}
        # "subsystem.stage" of a parent -> seconds its children took
        self._child_seconds: dict[str, float] = {}
        # (sub, stage) -> [(t0, t1, fields)], oldest first
        self._intervals: dict[tuple[str, str], list] = {}
        self.dropped_intervals = 0      # ring overflow, no longer silent
        self.metrics = metrics

    def record(self, subsystem: str, stage: str, seconds: float,
               end: float | None = None, fields=None) -> None:
        t1 = end if end is not None else time.perf_counter()
        overflow = 0
        key = (subsystem, stage)
        parent = fields.get("parent") if fields else None
        with self._mtx:
            t = self._totals.setdefault(key, [0, 0.0])
            t[0] += 1
            t[1] += seconds
            if parent is not None:
                self._child_seconds[parent] = \
                    self._child_seconds.get(parent, 0.0) + seconds
            ring = self._intervals.setdefault(key, [])
            ring.append((t1 - seconds, t1, fields))
            if len(ring) > MAX_INTERVALS:
                overflow = len(ring) - MAX_INTERVALS
                del ring[:overflow]
                self.dropped_intervals += overflow
        if self.metrics is not None:
            self.metrics.stage_duration_seconds.labels(
                subsystem, stage).observe(seconds)
            if overflow:
                self.metrics.intervals_dropped.add(overflow)

    def intervals(self, subsystem: str | None = None,
                  stage: str | None = None) -> list[dict]:
        """Retained span intervals, in the order they ended."""
        with self._mtx:
            raw = [(t1, t0, sub, st, f)
                   for (sub, st), ring in self._intervals.items()
                   if (subsystem is None or sub == subsystem)
                   and (stage is None or st == stage)
                   for (t0, t1, f) in ring]
        raw.sort(key=lambda r: r[0])
        return [{"subsystem": sub, "stage": st, "start": t0, "end": t1,
                 **(dict(f) if f else {})}
                for (t1, t0, sub, st, f) in raw]

    def overlap_seconds(self, subsystem: str, stage_a: str,
                        stage_b: str) -> float:
        """Total wall-clock during which a stage_a span and a stage_b
        span of `subsystem` ran CONCURRENTLY — the proof that a device
        span overlapped the next window's collect/pack span."""
        a = self.intervals(subsystem, stage_a)
        b = self.intervals(subsystem, stage_b)
        total = 0.0
        for ia in a:
            for ib in b:
                lo = max(ia["start"], ib["start"])
                hi = min(ia["end"], ib["end"])
                if hi > lo:
                    total += hi - lo
        return total

    def self_seconds(self, subsystem: str, stage: str) -> float:
        """A stage's seconds less what the spans opened inside it, on
        the same thread, cover: the part of it no child names.  From
        the running totals, not the ring, so nothing ages out of it."""
        with self._mtx:
            t = self._totals.get((subsystem, stage))
            return (t[1] if t else 0.0) - self._child_seconds.get(
                f"{subsystem}.{stage}", 0.0)

    def snapshot(self) -> dict:
        """{"subsystem.stage": {"count": n, "seconds": s}} — the shape
        the simnet benches report alongside their e2e rates."""
        with self._mtx:
            return {
                f"{sub}.{stage}": {"count": c, "seconds": round(s, 6)}
                for (sub, stage), (c, s) in sorted(self._totals.items())}

    def reset(self) -> None:
        with self._mtx:
            self._totals.clear()
            self._child_seconds.clear()


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **fields) -> None:
        pass


_NULL_SPAN = _NullSpan()


# the spans open on this thread, innermost last ("subsystem.stage")
_open = threading.local()


class _TimedSpan:
    __slots__ = ("_tracer", "_subsystem", "_stage", "_t0", "_fields")

    def __init__(self, tracer: StageTracer, subsystem: str, stage: str,
                 fields=None):
        self._tracer = tracer
        self._subsystem = subsystem
        self._stage = stage
        self._fields = fields

    def __enter__(self):
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        if stack:
            self.note(parent=stack[-1])
        stack.append(f"{self._subsystem}.{self._stage}")
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        _open.stack.pop()
        self._tracer.record(self._subsystem, self._stage,
                            t1 - self._t0, end=t1, fields=self._fields)
        return False

    def note(self, **fields) -> None:
        """Fields known only once the span is open (a cache hit, a
        width) land on its interval like span()'s own."""
        if self._fields is None:
            self._fields = fields
        else:
            self._fields.update(fields)


# process-wide tracer seam (same pattern as metrics.set_device_metrics)
_tracer: StageTracer | None = None


def set_tracer(t: StageTracer | None) -> None:
    global _tracer
    _tracer = t


def tracer() -> StageTracer | None:
    return _tracer


def span(subsystem: str, stage: str, **fields):
    """Context manager timing one stage; free when no tracer is set.
    Keyword fields (e.g. inflight=, depth=) land on the interval
    record so pipeline depth is visible next to the timing."""
    t = _tracer
    if t is None:
        return _NULL_SPAN
    return _TimedSpan(t, subsystem, stage, fields or None)


def close(subsystem: str, stage: str, started: float, **fields) -> None:
    """Record a span that outlived the frame it began in: `started` is
    the time.perf_counter() of its beginning, its end is now.  For an
    episode that a later call finishes (a blocksync reject runs from a
    window's false verdict to the same heights verified true, several
    passes of the pool routine later); it takes no part in nesting."""
    t = _tracer
    if t is not None:
        t1 = time.perf_counter()
        t.record(subsystem, stage, t1 - started, end=t1,
                 fields=fields or None)
