"""Streaming signature verification: the deadline-flushed accumulator
between live consensus and the device (SURVEY §7 "latency vs
throughput"; the per-vote hot path is the reference's
types/vote_set.go:219-232 -> ed25519.go:181).

Gossiped votes are PRE-verified off the consensus-state thread: the
reactor submits (pubkey, sign_bytes, sig) as soon as a VoteMessage
arrives and attaches the resulting future to the vote; VoteSet.add_vote
consumes the verdict if (and only if) the submitted triple matches what
it would itself verify.  The verifier batches concurrent submissions:

- a worker collects submissions until the oldest has waited
  flush_interval or the batch hits max_batch;
- small flushes take the host fast path (OpenSSL verify with ZIP-215
  fallback, crypto/ed25519.PubKey.verify_signature) — one vote in
  steady-state consensus must not pay a device round-trip;
- flushes >= device_threshold go to the device RLC kernel with
  per-signature localization (crypto/batch._device_verify) — vote
  floods (late-joiner catchup, large validator sets) amortize onto the
  accelerator.

This mirrors MConnection's flush throttle (reference
p2p/conn/connection.go 10ms flushThrottle): latency-bounded batching at
the seam where throughput spikes.
"""

from __future__ import annotations

import os
import threading
import time
from ..libs import lockrank
from concurrent.futures import Future

from ..libs.service import BaseService

_FLUSH_INTERVAL = float(os.environ.get("COMETBFT_TPU_VOTE_FLUSH_MS", "2")) \
    / 1000.0
_DEVICE_THRESHOLD = int(os.environ.get(
    "COMETBFT_TPU_VOTE_DEVICE_THRESHOLD", "256"))
_MAX_BATCH = 4096
# how often the accumulating worker re-checks the pipeline's QoS seal
# advisory (qos_seal_due) while a batch is forming; only matters when
# flush_interval is large relative to it.  5ms keeps the worker's
# wake rate low (the advisory's empty-queue fast path makes each
# check a couple of attribute reads) while staying well inside the
# 50ms consensus SLO
_SEAL_POLL_S = 0.005


class StreamingVerifier(BaseService):
    """Deadline-flushed ed25519 verify accumulator."""

    def __init__(self, flush_interval: float = _FLUSH_INTERVAL,
                 device_threshold: int = _DEVICE_THRESHOLD,
                 max_batch: int = _MAX_BATCH, pipeline=None,
                 warmup: bool | None = None):
        super().__init__("StreamingVerifier")
        self.flush_interval = flush_interval
        self.device_threshold = device_threshold
        self.max_batch = max_batch
        # overlapped dispatch engine (crypto/dispatch.py); None = the
        # process-wide default, created lazily at first device flush
        self._pipeline = pipeline
        # pre-warm the device vote path at start (see _prewarm); None
        # defers to COMETBFT_TPU_VOTE_PREWARM, else warms only when a
        # real accelerator is attached
        self.warmup = warmup
        self.warmed = threading.Event()
        # (pubkey, msg, sig, future, trace_ctx_or_None)
        self._pending: list[tuple] = []
        # in-flight dedupe: triple -> the future already queued for it,
        # so two peers flooding the same vote share one batch slot
        self._inflight: dict[tuple, Future] = {}
        self._cv = lockrank.RankedCondition(name="votestream.cv")
        self._thread: threading.Thread | None = None
        self._stopping = False
        self.flushes = 0
        self.device_flushes = 0
        self.verified = 0
        self.coalesced = 0
        self.cache_hits = 0

    # -- service -----------------------------------------------------------

    def on_start(self) -> None:
        self._stopping = False
        self._thread = threading.Thread(
            target=self._worker, name="vote-verify-stream", daemon=True)
        self._thread.start()
        if self._should_warm():
            threading.Thread(target=self._prewarm,
                             name="vote-verify-warmup",
                             daemon=True).start()
        else:
            self.warmed.set()

    def _should_warm(self) -> bool:
        if self.warmup is not None:
            return self.warmup
        env = os.environ.get("COMETBFT_TPU_VOTE_PREWARM")
        if env is not None:
            return env == "1"
        # default policy: warm only with a real accelerator attached.
        # On the XLA-CPU backend the warmup COMPILE is itself the only
        # cold cost, and paying it at every test-process start would
        # dwarf what it saves.
        import jax

        return jax.default_backend() != "cpu"

    def _prewarm(self) -> None:
        """Compile + dispatch one dummy device batch at start so the
        first real vote flood hits warm kernels: the cold p99 outlier
        on the flush=1ms latency ladder (latency_bench_r5.jsonl) was
        one first-flush compile+dispatch, paid at the worst possible
        time.  Distinct keys size the A-side MSM
        width like a real device_threshold-sized flood, so the warmed
        RLC program shape is the one floods actually hit."""
        try:
            from . import ed25519_ref as ref
            from .dispatch import default_pipeline

            n = max(2, min(self.device_threshold, 256))
            items = []
            for i in range(n):
                seed, pub = ref.keygen(i.to_bytes(32, "little"))
                msg = b"cometbft-tpu-vote-prewarm-" + i.to_bytes(
                    4, "little")
                items.append((pub, msg, ref.sign(seed, msg)))
            pipe = self._pipeline if self._pipeline is not None \
                else default_pipeline()
            # lat=() opts the warmup window out of the latency ledger:
            # a 300s compile row would poison the consensus p99
            handle = pipe.submit(items, subsystem="consensus",
                                 device_threshold=2, lat=())
            handle.result(timeout=300)
        except Exception:  # pragma: no cover - warmup must never wedge
            pass
        finally:
            self.warmed.set()

    def on_stop(self) -> None:
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=2)

    # -- API ---------------------------------------------------------------

    def submit(self, pubkey: bytes, msg: bytes, sig: bytes,
               ctx=None) -> Future:
        """Queue one signature; the future resolves to a bool verdict.
        The caller keeps (pubkey, msg, sig) to check the verdict applies
        to what it meant to verify.  ``ctx`` is an optional trace
        context (libs/tracetl.py) tagging the flush events with the
        consensus height/round that triggered the verify.

        Two fast exits before a batch slot is occupied:
        - verdict-cache hit (crypto/sigcache.py): the triple was
          already proved somewhere in the process — the returned
          future is ALREADY RESOLVED;
        - in-flight duplicate: the same triple is already queued (a
          second peer flooding the same vote) — the existing future is
          returned, one device verification serves both."""
        from . import sigcache
        from ..libs import latledger

        fut: Future = lockrank.TrackedFuture()
        # one latency-ledger request per submitted vote: resolved at
        # whichever seam answers (cache here, host/device at flush, or
        # coalesced onto the original's resolution)
        req = latledger.submit(1, consumer="consensus")
        if sigcache.enabled():
            v = sigcache.get(pubkey, msg, sig, key_type="ed25519",
                             label="consensus")
            if v is not None:
                self.cache_hits += 1
                fut.set_result(v)
                if req is not None:
                    req.resolve("cache")
                return fut
        with self._cv:
            if self._stopping or self._thread is None:
                fut.set_result(_host_verify(pubkey, msg, sig))
                if req is not None:
                    req.resolve("host")
                return fut
            triple = (pubkey, msg, sig)
            existing = self._inflight.get(triple)
            if existing is not None and not existing.done():
                self.coalesced += 1
                from ..libs import metrics as libmetrics

                cm = libmetrics.cache_metrics()
                if cm is not None:
                    cm.votestream_coalesced.inc()
                if req is not None:
                    # the duplicate's whole wait is the original's
                    # resolution: its row lands as coalesce_wait, and
                    # the original keeps its own decomposition
                    existing.add_done_callback(
                        lambda f, r=req: r.resolve_coalesced())
                return existing
            self._inflight[triple] = fut
            # the done-callback fires on resolve AND on cancel, so a
            # canceled slot stops absorbing new duplicates
            fut.add_done_callback(
                lambda f, t=triple: self._forget(t, f))
            self._pending.append((pubkey, msg, sig, fut, ctx, req))
            self._cv.notify()
        return fut

    def _forget(self, triple: tuple, fut: Future) -> None:
        with self._cv:
            if self._inflight.get(triple) is fut:
                del self._inflight[triple]

    def _seal_due(self) -> bool:
        """QoS preemption signal (VerifyPipeline.qos_seal_due): should
        the in-formation vote window seal now instead of waiting out
        the flush interval?  Peeks the pipeline this verifier would
        flush through — WITHOUT lazily creating one — and defers to
        its scheduler.  Rank-legal under self._cv: votestream.cv
        orders below dispatch.cv (libs/lockrank.py)."""
        pipe = self._pipeline
        if pipe is None:
            from . import dispatch

            pipe = dispatch._default
        # getattr: injected test pipelines are plain stubs with only
        # submit(); no advisory means no early seal
        seal = getattr(pipe, "qos_seal_due", None) \
            if pipe is not None else None
        if seal is None:
            return False
        return seal("consensus")

    # -- worker ------------------------------------------------------------

    def _worker(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._stopping:
                    self._cv.wait(timeout=0.1)
                if self._stopping:
                    batch, self._pending = self._pending, []
                else:
                    # deadline accumulation: let the batch grow until the
                    # OLDEST submission has waited flush_interval — or
                    # until the pipeline's QoS scheduler says sealing
                    # now beats batching further (cross-class work is
                    # queued behind us), so a single late vote never
                    # rides out the full interval behind a blocksync
                    # burst
                    deadline = time.monotonic() + self.flush_interval
                    while (len(self._pending) < self.max_batch
                           and not self._stopping):
                        left = deadline - time.monotonic()
                        if left <= 0:
                            break
                        if self._seal_due():
                            break
                        self._cv.wait(timeout=min(left, _SEAL_POLL_S))
                    batch, self._pending = self._pending, []
            if batch:
                self._flush(batch)
            if self._stopping:
                with self._cv:
                    leftover, self._pending = self._pending, []
                if leftover:
                    self._flush(leftover)
                return

    def _flush(self, batch) -> None:
        from . import sigcache
        from ..libs import devprof as libdevprof

        # devprof accounting (libs/devprof.py): below device_threshold
        # the worker thread IS the verify engine — account it under
        # device "0" like the pipeline's single-device loop does, so a
        # live consensus run (4-val simnet bench) still reads an
        # occupancy + idle-cause partition.  The gap since the last
        # mark was spent collecting the flood batch (or, on the early
        # returns below, the cache absorbed the whole flush) — either
        # way the engine was starved of work, not slow: no_work.
        dp = libdevprof.recorder()
        if dp is not None:
            dp.advance("0", libdevprof.IDLE_NO_WORK)

        # consumers cancel futures they already verified inline
        batch = [b for b in batch if not b[3].cancelled()]
        if not batch:
            return
        # late cache hits: verdicts inserted since submit (blocksync,
        # a previous flush, an inline verify) resolve here without
        # occupying a batch slot.  Misses were already counted at
        # submit time, so this re-check only accounts hits.
        cache_hits = 0
        if sigcache.enabled():
            verdicts, miss_idx = sigcache.partition(
                [(b[0], b[1], b[2]) for b in batch],
                label="consensus", count_misses=False)
            for b, v in zip(batch, verdicts):
                if v is not None and b[3].set_running_or_notify_cancel():
                    b[3].set_result(v)
                    if b[5] is not None:
                        b[5].resolve("cache")
            cache_hits = len(batch) - len(miss_idx)
            batch = [batch[i] for i in miss_idx]
            if not batch:
                return
        self.flushes += 1
        self.verified += len(batch)
        from ..libs import flightrec
        from ..libs import metrics as libmetrics
        from ..libs import trace as libtrace
        from ..libs import tracetl

        t0 = time.monotonic()
        if len(batch) >= self.device_threshold:
            try:
                # the vote-verify dispatch IS the consensus hot path
                # the stage-span framework exists for.  submit() is
                # non-blocking past backpressure: the worker returns to
                # COLLECTING the next flood batch while this window
                # packs/dispatches — the flood path no longer stalls on
                # a synchronous device round-trip.
                with libtrace.span("consensus", "verify_dispatch"), \
                        tracetl.span_for(self, "consensus",
                                         "verify_dispatch",
                                         cache=cache_hits):
                    self._flush_device(batch)
                return
            except Exception as e:
                # submit-time trouble (device errors mid-flight are
                # handled inside the pipeline's drain path): host
                # verdicts are still correct, but the operator must be
                # able to see it
                rec = flightrec.recorder()
                if rec is not None:
                    rec.record(flightrec.EV_DEVICE_FALLBACK,
                               batch=len(batch),
                               error=type(e).__name__)
                    rec.dump_to_log(
                        "device verify flush failed: %r" % e)
        path = "host"
        with libtrace.span("consensus", "verify_dispatch"), \
                tracetl.span_for(self, "consensus", "verify_dispatch",
                                 cache=cache_hits):
            for pk, msg, sig, fut, _, req in batch:
                # verdict first, future second: a consumer that
                # cancel-raced this flush (Preverified.verdict_for)
                # still gets the verdict CACHED, so its inline
                # re-verify is the last time the triple costs anything
                # (earlier votes' verify time IS this vote's queue
                # wait — the dispatch stamp cuts per vote)
                if req is not None:
                    req.stamp("dispatch")
                v = _host_verify(pk, msg, sig)
                sigcache.insert(pk, msg, sig, v, key_type="ed25519",
                                label="consensus")
                if req is not None:
                    req.stamp("compute_end")
                if fut.set_running_or_notify_cancel():
                    fut.set_result(v)
                if req is not None:
                    req.resolve(path)
        if dp is not None:
            dp.advance("0", libdevprof.BUSY, path=path)
        dm = libmetrics.device_metrics()
        if dm is not None:
            dm.flushes.labels(path).inc()
            dm.batch_size.labels(path).observe(len(batch))
            dm.flush_latency_seconds.labels(path).observe(
                time.monotonic() - t0)
        flightrec.record(flightrec.EV_VERIFY_FLUSH, path=path,
                         batch=len(batch), inflight=0, staged=0,
                         cache_hits=cache_hits,
                         **tracetl.ctx_fields(_batch_ctx(batch)))

    def _flush_device(self, batch) -> None:
        """Submit the flood batch through the overlapped pipeline and
        resolve the vote futures from its completion callback; the
        pipeline records the flush metrics/flightrec event (with its
        in-flight + staging depths) when the window resolves, and its
        drain path guarantees host verdicts on any device failure —
        the futures ALWAYS resolve to a bool."""
        from .dispatch import default_pipeline

        self.device_flushes += 1
        pipe = self._pipeline if self._pipeline is not None \
            else default_pipeline()
        # the per-vote ledger requests ride the window: the pipeline
        # stamps staging/dispatch/compute and resolves each with the
        # window's path, so queue_wait covers the pending-queue wait
        # from the ORIGINAL submit, not the flush
        lat = [b[5] for b in batch if b[5] is not None] or None
        handle = pipe.submit(
            [(pk, msg, sig) for pk, msg, sig, *_ in batch],
            subsystem="consensus", device_threshold=2,
            ctx=_batch_ctx(batch), lat=lat)

        def _resolve(h):
            from . import sigcache

            try:
                _, verdicts = h.result(timeout=0)
            except Exception:           # pragma: no cover - defensive
                verdicts = None
            if verdicts is None:
                for pk, msg, sig, fut, _, _ in batch:
                    v = _host_verify(pk, msg, sig)
                    sigcache.insert(pk, msg, sig, v,
                                    key_type="ed25519",
                                    label="consensus")
                    if fut.set_running_or_notify_cancel():
                        fut.set_result(v)
                return
            # verdicts for cancel-raced futures were inserted into the
            # verdict cache by the pipeline at window publication —
            # nothing re-verifies them even though set_running fails
            for (_, _, _, fut, _, _), ok in zip(batch, verdicts):
                if fut.set_running_or_notify_cancel():
                    fut.set_result(bool(ok))

        handle.add_done_callback(_resolve)


def _batch_ctx(batch):
    """First non-None trace context in the batch: a flush is one event,
    and the oldest submission is the one whose latency it bounds."""
    for entry in batch:
        if entry[4] is not None:
            return entry[4]
    return None


def _host_verify(pk: bytes, msg: bytes, sig: bytes) -> bool:
    from .ed25519 import PUBKEY_SIZE, PubKey

    if len(pk) != PUBKEY_SIZE:
        return False
    try:
        return PubKey(pk).verify_signature(msg, sig)
    except Exception:
        return False


# -- process-wide default instance ------------------------------------------

_default: StreamingVerifier | None = None
_default_lock = lockrank.RankedLock("votestream.default")


def default_verifier() -> StreamingVerifier:
    """Lazily-started shared instance (all reactors in a process feed
    one accumulator, maximizing batch opportunities)."""
    global _default
    with _default_lock:
        if _default is None or not _default.is_running():
            _default = StreamingVerifier()
            _default.start()
        return _default


class Preverified:
    """Verdict attached to a Vote by the reactor: the consumed-by
    VoteSet contract is exact-triple equality."""

    __slots__ = ("pubkey", "msg", "sig", "future")

    def __init__(self, pubkey: bytes, msg: bytes, sig: bytes,
                 future: Future):
        self.pubkey = pubkey
        self.msg = msg
        self.sig = sig
        self.future = future

    def verdict_for(self, pubkey: bytes, msg: bytes, sig: bytes):
        """Bool verdict if this preverification covers (pubkey, msg,
        sig) exactly AND already resolved; None otherwise.  Never
        blocks: the caller's inline verify costs microseconds, so a
        pending future is CANCELED (dropping it from the worker's
        batch — no duplicated work) and the caller verifies inline.
        During floods the state thread lags the verifier and futures
        are resolved by the time they are consumed — that is the case
        this path accelerates."""
        if (pubkey, msg, sig) != (self.pubkey, self.msg, self.sig):
            return None
        fut = self.future
        if fut.done() and not fut.cancelled():
            try:
                return bool(fut.result(timeout=0))
            except Exception:
                return None
        fut.cancel()
        return None
