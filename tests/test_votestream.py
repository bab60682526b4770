"""Streaming vote pre-verification (crypto/votestream): the
deadline-flushed accumulator between gossip and the device, plus its
consumption contract in VoteSet (reference hot path
types/vote_set.go:219-232; SURVEY §7 'latency vs throughput')."""

import threading
import time

from cometbft_tpu.crypto.ed25519 import PrivKey
from cometbft_tpu.crypto.votestream import (
    Preverified, StreamingVerifier, default_verifier)


def make_sig(i=0, msg=b"streaming-vote"):
    priv = PrivKey.generate(bytes([i + 1]) * 32)
    return priv.pub_key().bytes(), msg, priv.sign(msg)


class TestStreamingVerifier:
    def test_good_and_bad(self):
        sv = StreamingVerifier(flush_interval=0.002)
        sv.start()
        try:
            pk, msg, sig = make_sig()
            good = sv.submit(pk, msg, sig)
            bad = sv.submit(pk, b"other msg", sig)
            short = sv.submit(b"\x01" * 5, msg, sig)
            assert good.result(timeout=2) is True
            assert bad.result(timeout=2) is False
            assert short.result(timeout=2) is False
        finally:
            sv.stop()

    def test_concurrent_submissions_batch(self):
        sv = StreamingVerifier(flush_interval=0.05)
        sv.start()
        try:
            items = [make_sig(i) for i in range(12)]
            futs = []
            barrier = threading.Barrier(4)

            def submitter(chunk):
                barrier.wait()
                for pk, msg, sig in chunk:
                    futs.append(sv.submit(pk, msg, sig))

            threads = [threading.Thread(
                target=submitter, args=(items[i::4],)) for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            deadline = time.monotonic() + 5
            while len(futs) < 12 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert all(f.result(timeout=2) for f in futs)
            # the 50ms window must have coalesced them into few flushes
            assert sv.flushes <= 4, sv.flushes
            assert sv.verified == 12
        finally:
            sv.stop()

    def test_device_threshold_routes_to_device(self, monkeypatch):
        sv = StreamingVerifier(flush_interval=0.05, device_threshold=4)
        calls = []

        def fake_device(batch):
            calls.append(len(batch))
            for pk, m, s, fut in batch:
                fut.set_result(True)

        monkeypatch.setattr(sv, "_flush_device", fake_device)
        sv.start()
        try:
            items = [make_sig(i) for i in range(6)]
            futs = [sv.submit(*it) for it in items]
            assert all(f.result(timeout=2) for f in futs)
            assert calls and calls[0] >= 4
            assert sv.device_flushes == 0  # counter bumps inside the real one
        finally:
            sv.stop()

    def test_submit_after_stop_still_answers(self):
        sv = StreamingVerifier()
        sv.start()
        sv.stop()
        pk, msg, sig = make_sig()
        assert sv.submit(pk, msg, sig).result(timeout=1) is True

    def test_default_verifier_restarts(self):
        v1 = default_verifier()
        assert v1.is_running()
        v1.stop()
        v2 = default_verifier()
        assert v2.is_running() and v2 is not v1


class _StubPipeline:
    """Captures prewarm submissions; resolves every window True."""

    def __init__(self):
        self.windows = []

    def submit(self, items, subsystem=None, device_threshold=None,
               lat=None):
        from concurrent.futures import Future

        self.windows.append((list(items), subsystem, device_threshold))
        h = Future()
        h.set_result((True, [True] * len(items)))
        return h


class TestPrewarm:
    def test_warmup_dispatches_dummy_batch(self):
        """warmup=True: start() compiles+dispatches one dummy device
        batch (the cold p99 outlier was the first flush paying
        compile+dispatch); the warm batch must use
        DISTINCT keys so the A-side MSM width matches a real flood."""
        stub = _StubPipeline()
        sv = StreamingVerifier(device_threshold=16, pipeline=stub,
                               warmup=True)
        sv.start()
        try:
            assert sv.warmed.wait(timeout=30)
            assert len(stub.windows) == 1
            items, subsystem, thr = stub.windows[0]
            assert subsystem == "consensus" and thr == 2
            assert len(items) == 16          # min(device_threshold, 256)
            assert len({pk for pk, _, _ in items}) == len(items)
        finally:
            sv.stop()

    def test_cpu_backend_skips_warm_by_default(self):
        """On the XLA-CPU test backend the warmup compile IS the only
        cold cost, so the default policy skips it — warmed is set
        synchronously at start with no window submitted."""
        stub = _StubPipeline()
        sv = StreamingVerifier(pipeline=stub)
        sv.start()
        try:
            assert sv.warmed.is_set()
            assert stub.windows == []
        finally:
            sv.stop()

    def test_env_knob_forces_warm(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_VOTE_PREWARM", "1")
        stub = _StubPipeline()
        sv = StreamingVerifier(device_threshold=4, pipeline=stub)
        sv.start()
        try:
            assert sv.warmed.wait(timeout=30)
            assert len(stub.windows) == 1
        finally:
            sv.stop()
        monkeypatch.setenv("COMETBFT_TPU_VOTE_PREWARM", "0")
        sv2 = StreamingVerifier(device_threshold=4,
                                pipeline=_StubPipeline())
        sv2.start()
        try:
            assert sv2.warmed.is_set()
        finally:
            sv2.stop()

    def test_warm_start_kills_cold_outlier(self):
        """The assertable warm-start contract: after warmed, the first
        REAL flood flush finds the pipeline already exercised — here
        measured as the stub pipeline having seen the dummy window
        BEFORE the first real submission arrives."""
        stub = _StubPipeline()
        sv = StreamingVerifier(flush_interval=0.002, device_threshold=2,
                               pipeline=stub, warmup=True)
        sv.start()
        try:
            assert sv.warmed.wait(timeout=30)
            pk, msg, sig = make_sig()
            fut = sv.submit(pk, msg, sig)
            assert fut.result(timeout=5) is True
            # the prewarm window was first in line
            assert stub.windows and len(stub.windows[0][0]) >= 2
        finally:
            sv.stop()


class TestPreverifiedContract:
    def test_exact_triple_match_only(self):
        pk, msg, sig = make_sig()
        sv = StreamingVerifier(flush_interval=0.001)
        sv.start()
        try:
            fut = sv.submit(pk, msg, sig)
            fut.result(timeout=2)        # resolved -> consumable
            pv = Preverified(pk, msg, sig, fut)
            assert pv.verdict_for(pk, msg, sig) is True
            assert pv.verdict_for(pk, b"different", sig) is None
            assert pv.verdict_for(b"\x02" * 32, msg, sig) is None
        finally:
            sv.stop()

    def test_pending_future_cancels_not_blocks(self):
        from concurrent.futures import Future

        pk, msg, sig = make_sig()
        fut = Future()                   # never resolved
        pv = Preverified(pk, msg, sig, fut)
        import time as _t
        t0 = _t.monotonic()
        assert pv.verdict_for(pk, msg, sig) is None
        assert _t.monotonic() - t0 < 0.005   # no blocking wait
        assert fut.cancelled()               # dropped from worker batch

    def test_vote_set_consumes_preverified(self):
        """A vote carrying a preverified verdict for a DIFFERENT triple
        must still be verified inline (and pass); one whose matching
        verdict is False must be rejected."""
        from concurrent.futures import Future

        import pytest

        from cometbft_tpu.types.vote import PREVOTE_TYPE
        from cometbft_tpu.types.vote_set import (
            ErrVoteInvalidSignature, VoteSet)
        from tests.test_vote_set import (
            CHAIN, block_id, make_valset, signed_vote)

        vals, privs = make_valset(3)
        vs = VoteSet(CHAIN, 5, 0, PREVOTE_TYPE, vals)
        bid = block_id()
        vote = signed_vote(privs[0], 0, PREVOTE_TYPE, 5, 0, bid)
        # non-matching marker -> ignored, inline verify accepts
        f = Future()
        f.set_result(False)
        vote.preverified = Preverified(b"\x07" * 32, b"x", b"y", f)
        assert vs.add_vote(vote)

        vote2 = signed_vote(privs[1], 1, PREVOTE_TYPE, 5, 0, bid)
        pk = vals.validators[1].pub_key.bytes()
        msg = vote2.sign_bytes(CHAIN)
        f2 = Future()
        f2.set_result(False)      # matching triple, negative verdict
        vote2.preverified = Preverified(pk, msg, vote2.signature, f2)
        with pytest.raises(ErrVoteInvalidSignature):
            vs.add_vote(vote2)
        # without the marker the same vote is valid
        vote2.preverified = None
        assert vs.add_vote(vote2)


class TestDeferredSigBatch:
    def test_failed_ctx_attribution(self):
        """A bad signature raises with .failed_ctx naming the commit's
        context (the blocksync window uses the height for peer blame)."""
        import pytest

        from cometbft_tpu.types.validation import (
            DeferredSigBatch, ErrInvalidSignature)
        from cometbft_tpu.types.vote import PRECOMMIT_TYPE
        from cometbft_tpu.types.vote_set import commit_to_vote_set
        from tests.test_vote_set import (
            CHAIN, block_id, make_valset, signed_vote)
        from cometbft_tpu.types.vote_set import VoteSet

        vals, privs = make_valset(3)
        batch = DeferredSigBatch()
        commits = []
        for h in (5, 6, 7):
            vs = VoteSet(CHAIN, h, 0, PRECOMMIT_TYPE, vals)
            bid = block_id(h)
            for i, p in enumerate(privs):
                vs.add_vote(signed_vote(p, i, PRECOMMIT_TYPE, h, 0, bid))
            commits.append(vs.make_commit())
        # corrupt height 6's commit
        import dataclasses
        bad = commits[1]
        bad.signatures = [
            dataclasses.replace(
                cs, signature=cs.signature[:6]
                + bytes([cs.signature[6] ^ 1]) + cs.signature[7:])
            if cs.signature else cs
            for cs in bad.signatures]
        for h, commit in zip((5, 6, 7), commits):
            vals.verify_commit_light(CHAIN, commit.block_id, h, commit,
                                     defer_to=batch)
        with pytest.raises(ErrInvalidSignature) as ei:
            batch.verify()
        assert ei.value.failed_ctx == 6


class TestQosSealAdvisory:
    def test_late_vote_seals_early_behind_bulk_burst(self):
        """Regression for the QoS seal advisory: a single vote arriving
        while a blocksync staging burst occupies the shared pipeline
        must NOT ride out the full flush interval — qos_seal_due cuts
        the accumulation short (cross-class work is queued), so the
        vote resolves at the first poll tick.  The bulk windows are
        HELD in the queue (their dispatch waits on an event) until it
        has, so nothing here depends on how long the host takes to
        verify them."""
        import threading

        from cometbft_tpu.crypto import dispatch as vd
        from cometbft_tpu.crypto import sigcache
        from tests.test_dispatch import make_items, serial_verdicts

        sigcache.reset()
        flush = 5.0
        release = threading.Event()

        def held_dispatch(win):
            release.wait(60)
            v = serial_verdicts(win.items)
            return all(v) and bool(v), v

        with vd.VerifyPipeline(depth=8, dispatch_fn=held_dispatch,
                               name="SealPipe") as pipe:
            feeds = [make_items(12, seed=60 + i, msg=b"seal-bulk")
                     for i in range(4)]
            bulk = [pipe.submit(list(f), subsystem="blocksync",
                                device_threshold=1)
                    for f in feeds]
            sv = StreamingVerifier(flush_interval=flush,
                                   device_threshold=10**9,
                                   pipeline=pipe, warmup=False)
            sv.start()
            try:
                pk, msg, sig = make_sig(0, msg=b"late-vote")
                t0 = time.monotonic()
                fut = sv.submit(pk, msg, sig)
                assert fut.result(timeout=60) is True
                elapsed = time.monotonic() - t0
            finally:
                release.set()
                sv.stop()
            for f, h in zip(feeds, bulk):
                assert h.result(timeout=60)[1] == serial_verdicts(f)
        assert sv.verified == 1
        # without the advisory the vote waits out the whole 5 s
        # interval; the seal fires on the first poll tick instead
        assert elapsed < flush / 2, elapsed

    def test_idle_or_stopped_pipeline_never_seals(self):
        """Edge cases of the advisory: an empty queue keeps batching
        (the flush interval is the designed latency — sealing per-vote
        whenever the pipeline goes idle would defeat coalescing), and
        a stopped pipeline never advises (the own-class backpressure
        case lives in tests/test_sched.py)."""
        from cometbft_tpu.crypto import dispatch as vd

        with vd.VerifyPipeline(depth=4, name="OwnClassPipe") as pipe:
            items = [make_sig(i, msg=b"own-class") for i in range(6)]
            assert not pipe.qos_seal_due("consensus")  # idle queue
            h = pipe.submit([items[0]], subsystem="consensus",
                            device_threshold=10**9)
            h.result(timeout=30)
        assert not pipe.qos_seal_due("consensus")  # stopped pipeline
