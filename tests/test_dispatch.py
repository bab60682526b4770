"""Overlapped host/device verify pipeline (crypto/dispatch.py):
ordering, verdict parity vs the serial path on identical fixtures,
parallel parse+hash byte parity, backpressure, and the drain path —
a mid-flight device failure must fall back to host verdicts for the
faulted window and everything staged behind it, with no lost or
misordered windows.
"""

import threading
import time

import pytest

from cometbft_tpu.crypto import batch as cb
from cometbft_tpu.crypto import dispatch as vd
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.crypto.ed25519 import PrivKey, PubKey


def make_items(n, seed=0, msg=b"pipeline-item", bad=()):
    """n (pubkey_bytes, msg, sig) triples; indices in `bad` get a
    corrupted signature.  Deterministic: same (n, seed) -> same
    fixture, the serial/pipelined parity contract."""
    items = []
    for i in range(n):
        priv = PrivKey.generate(bytes([seed & 0xFF, i & 0xFF,
                                       (i >> 8) & 0xFF]) + b"\x05" * 29)
        m = msg + i.to_bytes(4, "little")
        sig = priv.sign(m)
        if i in bad:
            sig = sig[:6] + bytes([sig[6] ^ 1]) + sig[7:]
        items.append((priv.pub_key().bytes(), m, sig))
    return items


def serial_verdicts(items):
    """The serial oracle: per-signature host verify, the same
    safe-verify semantics DeferredSigBatch's host path uses."""
    return [cb.safe_verify(PubKey(pk), m, s) if len(pk) == 32
            else False
            for pk, m, s in items]


class TestPipelineVerdicts:
    def test_verdict_parity_good_and_bad(self):
        """Host lane and (stubbed-dispatch) device lane must both
        match the serial oracle on the identical fixture.  The stub
        seam replaces ONLY the final device call — staging still runs
        the real RLC pack, and the stub judges what staging kept for
        the device stage (keys, messages, signatures), so a staging
        bug shows up as a parity break here.  (The real XLA dispatch
        costs minutes of cold compile on the CPU tier; the slow tier
        pins it.)"""
        items = make_items(24, seed=7, bad=(3, 20))
        want = serial_verdicts(items)
        assert want.count(False) == 2

        def judge_from_staging(win):
            # the batch is structurally sound, so staging packed it
            assert win.mode == "ed" and win.packed is not None
            out = [cb.safe_verify(PubKey(pk), m, s)
                   for pk, m, s in zip(win.pks, win.msgs, win.sigs)]
            return all(out), out

        # the oracle and each pipeline arm share triples; flush the
        # process-wide verdict cache between them so every arm
        # genuinely exercises its own lane (a hit would short-circuit
        # to path "cache")
        sigcache.reset()
        with vd.VerifyPipeline(depth=2) as pipe:
            ok_h, host = pipe.submit(list(items),
                                     device_threshold=1 << 30).result(
                                         timeout=60)
        sigcache.reset()
        with vd.VerifyPipeline(
                depth=2, dispatch_fn=judge_from_staging) as pipe:
            h = pipe.submit(list(items), device_threshold=1)
            ok_d, dev = h.result(timeout=60)
        assert host == want and not ok_h
        assert dev == want and not ok_d
        assert h.path == "device"

    @pytest.mark.slow
    def test_verdict_parity_real_device_dispatch(self):
        """The real dispatch chain (pack_rlc ->
        rlc_verify -> per-signature kernel fallback) against the
        serial oracle; cold-compiles the XLA kernels, so slow tier."""
        items = make_items(24, seed=7, bad=(3, 20))
        want = serial_verdicts(items)
        # the oracle cached every verdict — flush so the submit really
        # drives the device chain instead of resolving from cache
        sigcache.reset()
        with vd.VerifyPipeline(depth=2) as pipe:
            ok, dev = pipe.submit(list(items),
                                  device_threshold=1).result(
                                      timeout=1800)
        assert dev == want and not ok

    def test_ordering_strict_across_windows(self):
        """Verdicts resolve in submission order even when later
        windows finish staging first."""
        order = []
        lock = threading.Lock()

        def slow_first(win):
            # the first window's device dispatch sleeps; later windows
            # must still resolve after it
            if win.handle.ctx == 0:
                time.sleep(0.15)
            return True, [True] * len(win.items)

        with vd.VerifyPipeline(depth=4,
                               dispatch_fn=slow_first) as pipe:
            handles = []
            for w in range(4):
                h = pipe.submit(make_items(4, seed=w), ctx=w,
                                device_threshold=1)
                h.add_done_callback(
                    lambda hh: (lock.__enter__(),
                                order.append(hh.ctx),
                                lock.__exit__(None, None, None)))
                handles.append(h)
            for h in handles:
                h.result(timeout=60)
        assert order == [0, 1, 2, 3]

    def test_empty_window_resolves_immediately(self):
        with vd.VerifyPipeline(depth=2) as pipe:
            ok, verdicts = pipe.submit([]).result(timeout=5)
        assert (ok, verdicts) == (False, [])

    def test_submit_after_stop_still_answers(self):
        pipe = vd.VerifyPipeline(depth=2)
        pipe.start()
        pipe.stop()
        items = make_items(3, seed=9, bad=(1,))
        ok, verdicts = pipe.submit(items).result(timeout=5)
        assert verdicts == serial_verdicts(items)
        assert not ok

    def test_backpressure_bounds_inflight(self):
        release = threading.Event()

        def gated(win):
            release.wait(timeout=30)
            return True, [True] * len(win.items)

        pipe = vd.VerifyPipeline(depth=2, dispatch_fn=gated)
        pipe.start()
        try:
            submitted = []

            def feeder():
                for w in range(4):
                    submitted.append(pipe.submit(
                        make_items(2, seed=w), device_threshold=1))

            th = threading.Thread(target=feeder, daemon=True)
            th.start()
            time.sleep(0.3)
            # depth 2: the feeder must be blocked before window 3
            assert len(submitted) <= 3
            assert pipe.inflight <= 2
            release.set()
            th.join(timeout=30)
            assert len(submitted) == 4
            for h in submitted:
                assert h.result(timeout=30)[0] is True
        finally:
            pipe.stop()


class TestPipelineDrain:
    def test_device_fault_drains_to_host_with_parity(self):
        """A device failure on an in-flight window: that window AND
        everything staged behind it resolve through the host path with
        verdicts identical to the serial oracle — then the pipeline
        recovers (device dispatch resumes once drained)."""
        fixtures = [make_items(12, seed=w, bad=((2,) if w == 1 else ()))
                    for w in range(3)]
        boom = {"armed": True}

        def flaky(win):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("injected device failure")
            return (all(serial_verdicts(win.items)),
                    serial_verdicts(win.items))

        from cometbft_tpu.libs import flightrec

        rec = flightrec.FlightRecorder()
        flightrec.set_recorder(rec)
        try:
            with vd.VerifyPipeline(depth=3, dispatch_fn=flaky) as pipe:
                handles = [pipe.submit(list(f), device_threshold=1)
                           for f in fixtures]
                results = [h.result(timeout=60) for h in handles]
                paths = [h.path for h in handles]
                # recovery: a window submitted after the drain goes
                # back to the device path
                again = pipe.submit(make_items(4, seed=11),
                                    device_threshold=1)
                assert again.result(timeout=60)[0] is True
                assert again.path == "device"
        finally:
            flightrec.set_recorder(None)
        for f, (ok, verdicts) in zip(fixtures, results):
            assert verdicts == serial_verdicts(f)
        assert results[1][0] is False        # the corrupted window
        assert results[0][0] and results[2][0]
        assert paths[0] == "drain"           # the faulted window
        assert pipe.faults == 1
        kinds = [e["kind"] for e in rec.events()]
        assert flightrec.EV_PIPELINE_DRAIN in kinds
        assert flightrec.EV_DEVICE_FALLBACK in kinds
        drain_ev = next(e for e in rec.events()
                        if e["kind"] == flightrec.EV_PIPELINE_DRAIN)
        assert "inflight" in drain_ev and "staged" in drain_ev

    def test_flush_events_carry_depth_fields(self):
        from cometbft_tpu.libs import flightrec

        rec = flightrec.FlightRecorder()
        flightrec.set_recorder(rec)
        try:
            with vd.VerifyPipeline(depth=2) as pipe:
                pipe.submit(make_items(3, seed=2),
                            device_threshold=1 << 30).result(timeout=30)
        finally:
            flightrec.set_recorder(None)
        ev = next(e for e in rec.events()
                  if e["kind"] == flightrec.EV_VERIFY_FLUSH)
        assert "inflight" in ev and "staged" in ev
        assert ev["batch"] == 3


class TestPipelineMetricsAndSpans:
    def test_device_metrics_gauges_driven(self):
        from cometbft_tpu.libs import metrics as libmetrics
        from cometbft_tpu.libs.metrics import DeviceMetrics, Registry

        reg = Registry("cometbft_tpu")
        dm = DeviceMetrics(reg)
        libmetrics.set_device_metrics(dm)
        try:
            def flaky(win):
                raise RuntimeError("boom")

            with vd.VerifyPipeline(depth=2,
                                   dispatch_fn=flaky) as pipe:
                pipe.submit(make_items(2, seed=4),
                            device_threshold=1).result(timeout=30)
        finally:
            libmetrics.set_device_metrics(None)
        text = reg.expose()
        assert "cometbft_tpu_device_pipeline_inflight_windows" in text
        assert "cometbft_tpu_device_pipeline_staging_depth" in text
        assert "cometbft_tpu_device_pipeline_drains 1" in text

    def test_spans_land_under_submitter_subsystem(self):
        from cometbft_tpu.libs import trace as libtrace

        tr = libtrace.StageTracer()
        prev = libtrace.tracer()
        libtrace.set_tracer(tr)
        try:
            with vd.VerifyPipeline(depth=2) as pipe:
                pipe.submit(make_items(3, seed=5),
                            subsystem="blocksync",
                            device_threshold=1 << 30).result(timeout=30)
                pipe.drain(timeout=10)
        finally:
            libtrace.set_tracer(prev)
        snap = tr.snapshot()
        assert snap["blocksync.host_pack"]["count"] >= 1
        assert snap["blocksync.device"]["count"] >= 1

    @pytest.mark.parametrize("library", [True, False])
    def test_window_host_pack_span_names_its_packer(self, library,
                                                    monkeypatch):
        """A window the staging thread packs says who packed it; a
        window below the threshold is packed by no one and says
        nothing."""
        from cometbft_tpu.crypto import rlcpack
        from cometbft_tpu.libs import trace as libtrace

        if not library:
            monkeypatch.setattr(rlcpack, "_lib", None)
            monkeypatch.setattr(rlcpack, "_failed", True)
        want = "native" if rlcpack.enabled() else "python"
        sigcache.reset()
        tr = libtrace.StageTracer()
        prev = libtrace.tracer()
        libtrace.set_tracer(tr)
        try:
            with vd.VerifyPipeline(depth=2,
                                   dispatch_fn=judge_staged) as pipe:
                pipe.submit(make_items(6, seed=15), subsystem="light",
                            device_threshold=1).result(timeout=30)
                pipe.submit(make_items(3, seed=16), subsystem="light",
                            device_threshold=1 << 30).result(timeout=30)
        finally:
            libtrace.set_tracer(prev)
        packed, unpacked = tr.intervals("light", "host_pack")
        assert packed["packer"] == want
        assert "packer" not in unpacked


class TestTraceIntervals:
    def test_overlap_seconds_detects_concurrency(self):
        from cometbft_tpu.libs import trace as libtrace

        tr = libtrace.StageTracer()
        # two intervals that overlap by construction
        tr.record("blocksync", "device", 0.5, end=1.0)
        tr.record("blocksync", "collect", 0.4, end=1.2)
        # [0.5, 1.0] vs [0.8, 1.2] -> 0.2 s of overlap
        assert tr.overlap_seconds("blocksync", "device",
                                  "collect") == pytest.approx(0.2)
        assert tr.overlap_seconds("blocksync", "device",
                                  "apply") == 0.0

    def test_span_fields_on_interval(self):
        from cometbft_tpu.libs import trace as libtrace

        tr = libtrace.StageTracer()
        prev = libtrace.tracer()
        libtrace.set_tracer(tr)
        try:
            with libtrace.span("blocksync", "collect", inflight=3):
                pass
        finally:
            libtrace.set_tracer(prev)
        iv = tr.intervals("blocksync", "collect")
        assert len(iv) == 1 and iv[0]["inflight"] == 3
        assert iv[0]["end"] >= iv[0]["start"]


class TestOverlapProof:
    def test_device_span_concurrent_with_next_collect(self):
        """The acceptance-bar proof, deterministically: while window
        N's (stubbed, sleeping) device dispatch is in flight, the
        caller runs window N+1's collect span — the tracer's interval
        records must show the two CONCURRENT."""
        from cometbft_tpu.libs import trace as libtrace

        started = threading.Event()

        def slow_device(win):
            started.set()
            time.sleep(0.25)
            return True, [True] * len(win.items)

        tr = libtrace.StageTracer()
        prev = libtrace.tracer()
        libtrace.set_tracer(tr)
        try:
            with vd.VerifyPipeline(depth=2,
                                   dispatch_fn=slow_device) as pipe:
                h1 = pipe.submit(make_items(4, seed=1),
                                 subsystem="blocksync",
                                 device_threshold=1)
                assert started.wait(timeout=10)
                # window N is ON DEVICE right now; collect window N+1
                with libtrace.span("blocksync", "collect", inflight=1):
                    time.sleep(0.1)
                h2 = pipe.submit(make_items(4, seed=2),
                                 subsystem="blocksync",
                                 device_threshold=1)
                h1.result(timeout=30)
                h2.result(timeout=30)
        finally:
            libtrace.set_tracer(prev)
        overlap = tr.overlap_seconds("blocksync", "device", "collect")
        assert overlap > 0.05, tr.intervals("blocksync")


class TestDeferredVerifyAsync:
    def _commits_fixture(self, bad_height=None):
        from cometbft_tpu.types.validation import DeferredSigBatch
        from cometbft_tpu.types.vote import PRECOMMIT_TYPE
        from cometbft_tpu.types.vote_set import VoteSet
        from tests.test_vote_set import (
            CHAIN, block_id, make_valset, signed_vote)

        vals, privs = make_valset(3)
        batch = DeferredSigBatch()
        for h in (5, 6, 7):
            vs = VoteSet(CHAIN, h, 0, PRECOMMIT_TYPE, vals)
            bid = block_id(h)
            for i, p in enumerate(privs):
                vs.add_vote(signed_vote(p, i, PRECOMMIT_TYPE, h, 0,
                                        bid))
            commit = vs.make_commit()
            if h == bad_height:
                import dataclasses
                commit.signatures = [
                    dataclasses.replace(
                        cs, signature=cs.signature[:6]
                        + bytes([cs.signature[6] ^ 1])
                        + cs.signature[7:])
                    if cs.signature else cs
                    for cs in commit.signatures]
            vals.verify_commit_light(CHAIN, commit.block_id, h, commit,
                                     defer_to=batch)
        return batch

    def test_async_matches_serial_raise_contract(self):
        from cometbft_tpu.types.validation import ErrInvalidSignature

        batch = self._commits_fixture(bad_height=6)
        with vd.VerifyPipeline(depth=2) as pipe:
            verdict = batch.verify_async(pipe, subsystem="blocksync")
            with pytest.raises(ErrInvalidSignature) as ei:
                verdict.wait(timeout=60)
        assert ei.value.failed_ctx == 6
        assert batch.count() == 0        # entries consumed, like verify()

    def test_async_clean_window_passes(self):
        batch = self._commits_fixture()
        with vd.VerifyPipeline(depth=2) as pipe:
            batch.verify_async(pipe, subsystem="light").wait(timeout=60)


def judge_staged(win):
    """Honest stub dispatch: judge the window's items with the host
    oracle (which rejects a structurally bad entry itself).  Handles
    both raw-bytes pubkeys (real windows) and PubKey objects
    (devhealth probe windows)."""
    out = []
    for pk, m, s in win.items:
        pub = PubKey(pk) if isinstance(pk, (bytes, bytearray)) else pk
        out.append(cb.safe_verify(pub, m, s))
    return all(out) and bool(out), out


def wait_until(pred, timeout=10.0, step=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


class TestHealthWatchdog:
    def test_hung_dispatch_host_resolved_device_quarantined(self):
        """A wedged device dispatch: the watchdog must host-resolve
        the hung window within the deadline (serial-oracle parity, no
        verdict lost), quarantine the chip, and a known-answer probe
        must return it to rotation — after which dispatch goes back
        on-device."""
        from cometbft_tpu.crypto import devhealth
        from cometbft_tpu.libs import flightrec
        from cometbft_tpu.libs import metrics as libmetrics
        from cometbft_tpu.libs.metrics import DeviceMetrics, Registry

        release = threading.Event()
        state = {"hung": False}

        def hang_once(win):
            if not state["hung"]:
                state["hung"] = True
                release.wait(timeout=30)
                raise RuntimeError("released after abandonment")
            return judge_staged(win)

        health = devhealth.HealthRegistry(
            quarantine_after=1, probe_backoff_s=0.05,
            probe_backoff_max_s=0.2)
        mreg = Registry("cometbft_tpu")
        libmetrics.set_device_metrics(DeviceMetrics(mreg))
        rec = flightrec.FlightRecorder()
        flightrec.set_recorder(rec)
        fixtures = [make_items(6, seed=w, bad=((1,) if w == 0 else ()))
                    for w in range(2)]
        try:
            sigcache.reset()
            with vd.VerifyPipeline(depth=3, dispatch_fn=hang_once,
                                   health=health,
                                   dispatch_deadline_s=0.3) as pipe:
                handles = [pipe.submit(list(f), device_threshold=1)
                           for f in fixtures]
                results = [h.result(timeout=30) for h in handles]
                assert handles[0].path == "drain"
                # probe recovery: the chip returns to rotation...
                assert wait_until(lambda: health.usable("0"))
                # ...and a new window dispatches on-device again
                sigcache.reset()
                again = pipe.submit(make_items(4, seed=9),
                                    device_threshold=1)
                assert again.result(timeout=30)[0] is True
                assert again.path == "device"
        finally:
            release.set()
            flightrec.set_recorder(None)
            libmetrics.set_device_metrics(None)
        for f, (ok, verdicts) in zip(fixtures, results):
            assert verdicts == serial_verdicts(f)
        assert results[0][0] is False and results[1][0] is True
        assert health.quarantines("0") == 1
        assert len(health.recovery_seconds("0")) == 1
        kinds = [e["kind"] for e in rec.events()]
        assert flightrec.EV_WATCHDOG_TIMEOUT in kinds
        assert flightrec.EV_DEVICE_QUARANTINE in kinds
        assert flightrec.EV_DEVICE_PROBE in kinds
        wd = next(e for e in rec.events()
                  if e["kind"] == flightrec.EV_WATCHDOG_TIMEOUT)
        assert wd["device"] == "0"
        assert wd["waited_s"] >= 0.3
        text = mreg.expose()
        assert ('cometbft_tpu_device_watchdog_timeouts_total'
                '{device="0"} 1' in text)

    def test_flap_quarantines_once_not_thrash(self):
        """A flapping chip whose faults keep coming during probing:
        ONE quarantine cycle, probes fail while the flap lasts, and
        the chip returns only after a probe passes."""
        from cometbft_tpu.crypto import devhealth

        flap = {"remaining": 3}

        def flaky(win):
            if flap["remaining"] > 0:
                flap["remaining"] -= 1
                raise RuntimeError("chip flap")
            return judge_staged(win)

        health = devhealth.HealthRegistry(
            quarantine_after=1, probe_backoff_s=0.05,
            probe_backoff_max_s=0.2)
        items = make_items(5, seed=21, bad=(2,))
        sigcache.reset()
        with vd.VerifyPipeline(depth=2, dispatch_fn=flaky,
                               health=health) as pipe:
            ok, verdicts = pipe.submit(list(items),
                                       device_threshold=1).result(
                                           timeout=30)
            assert wait_until(lambda: health.usable("0"))
        assert verdicts == serial_verdicts(items) and not ok
        snap = health.snapshot()["0"]
        assert health.quarantines("0") == 1     # no thrash
        assert snap["probes_failed"] >= 1       # flap hit the probes
        assert snap["probes_ok"] == 1
        assert snap["state"] == "healthy"

    def test_brownout_all_quarantined_still_answers_on_host(self):
        """Every chip dead (all dispatches fault, probes kept away by
        a long backoff): the pipeline must enter brownout — host-only
        verify, shrunken max window — and keep resolving submissions
        with oracle parity."""
        from cometbft_tpu.crypto import devhealth
        from cometbft_tpu.libs import flightrec

        def dead(win):
            raise RuntimeError("dead chip")

        health = devhealth.HealthRegistry(
            quarantine_after=1, probe_backoff_s=60.0)
        rec = flightrec.FlightRecorder()
        flightrec.set_recorder(rec)
        fixtures = [make_items(5, seed=w, bad=((3,) if w == 1 else ()))
                    for w in range(3)]
        try:
            sigcache.reset()
            with vd.VerifyPipeline(depth=2, dispatch_fn=dead,
                                   health=health) as pipe:
                assert pipe.max_window() is None
                first = pipe.submit(list(fixtures[0]),
                                    device_threshold=1)
                assert first.result(timeout=30)[1] == \
                    serial_verdicts(fixtures[0])
                assert wait_until(pipe.in_brownout)
                assert pipe.max_window() == vd.BROWNOUT_MAX_WINDOW
                rest = [pipe.submit(list(f), device_threshold=1)
                        for f in fixtures[1:]]
                for f, h in zip(fixtures[1:], rest):
                    assert h.result(timeout=30)[1] == serial_verdicts(f)
                    assert h.path == "host"     # never touches a chip
        finally:
            flightrec.set_recorder(None)
        brown = [e for e in rec.events()
                 if e["kind"] == flightrec.EV_BROWNOUT]
        assert brown and brown[0]["entered"] is True
        assert brown[0]["max_window"] == vd.BROWNOUT_MAX_WINDOW

    def test_mesh_quarantine_skips_chip_and_recovers(self):
        """Two-chip mesh, chip 0 flaps: its windows drain, the
        round-robin routes follow-on traffic to chip 1 (which never
        faults), and chip 0 rejoins after a probe passes."""
        from cometbft_tpu.crypto import devhealth

        flap = {"remaining": 2}

        def flaky_dev0(win):
            if win.device_index == 0 and flap["remaining"] > 0:
                flap["remaining"] -= 1
                raise RuntimeError("dev0 flap")
            return judge_staged(win)

        health = devhealth.HealthRegistry(
            quarantine_after=1, probe_backoff_s=0.05,
            probe_backoff_max_s=0.2)
        fixtures = [make_items(4, seed=w, bad=((0,) if w == 2 else ()))
                    for w in range(4)]
        sigcache.reset()
        with vd.VerifyPipeline(depth=4, dispatch_fn=flaky_dev0,
                               devices=[0, 1], health=health) as pipe:
            handles = [pipe.submit(list(f), device_threshold=1)
                       for f in fixtures]
            results = [h.result(timeout=30) for h in handles]
            assert wait_until(lambda: health.usable("0"))
        for f, (ok, verdicts) in zip(fixtures, results):
            assert verdicts == serial_verdicts(f)
        assert health.quarantines("0") == 1
        assert health.quarantines("1") == 0
        assert health.state("1") == "healthy"


class TestMixedBatchConcurrency:
    def test_mixed_verdicts_merge_in_order(self):
        """The concurrent per-keytype dispatch must preserve the
        insertion-order verdict merge (ed25519 + secp256k1 sub-batches
        run in parallel threads)."""
        from cometbft_tpu.crypto import secp256k1 as sk

        eds = make_items(6, seed=13, bad=(4,))
        sps = []
        for i in range(5):
            priv = sk.PrivKey.generate(bytes([21, i]) + b"\x03" * 30)
            m = b"secp-msg" + bytes([i])
            sig = priv.sign(m)
            if i == 2:
                sig = sig[:3] + bytes([sig[3] ^ 1]) + sig[4:]
            sps.append((priv.pub_key(), m, sig))
        bv = cb.MixedBatchVerifier(provider="cpu")
        expect = []
        for j in range(6):
            pk, m, s = eds[j]
            bv.add(PubKey(pk), m, s)
            expect.append(j != 4)
            if j < 5:
                pk2, m2, s2 = sps[j]
                bv.add(pk2, m2, s2)
                expect.append(j != 2)
        ok, verdicts = bv.verify()
        assert verdicts == expect
        assert not ok
