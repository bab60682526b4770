"""Deterministic tests for the verify-plane QoS scheduler
(cometbft_tpu/crypto/sched.py).

The scheduler is pure selection logic with an injectable clock, so
lane ordering, deadline promotion, device holds, and deficit
round-robin are all tested here against a fake clock and bare window
stand-ins — no threads, no sleeps.  The pipeline-level contracts
(preemption under a real staging burst, brownout priority admission,
held-time landing in the ledger's exact partition) run against a real
``VerifyPipeline`` on the host path.
"""

import threading
import time

from cometbft_tpu.crypto import dispatch as vd
from cometbft_tpu.crypto import sched as qs
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.libs import flightrec
from cometbft_tpu.libs import latledger
from cometbft_tpu.libs import metrics as libmetrics
from tests.test_dispatch import make_items, serial_verdicts


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class W:
    """Bare stand-in carrying exactly the fields the scheduler reads
    (the dispatch._Window duck type)."""

    def __init__(self, items: int = 1, device_index: int = 0):
        self.items = [None] * items
        self.staged = False
        self.abandoned = False
        self.dispatching = False
        self.staging_active = False
        self.result = None
        self.device_index = device_index
        self.lane = qs.DEFAULT_LANE
        self.prio = 0
        self.seq = 0
        self.enqueued_at = 0.0
        self.held_since = None


def enq(sch, subsystem, items=1, staged=True, device_index=0,
        lane=None):
    w = W(items, device_index)
    sch.note_enqueue(w, sch.lane_for(subsystem, lane))
    w.staged = staged
    return w


class TestLaneResolution:
    def test_registered_subsystem_is_its_own_lane(self):
        sch = qs.QosScheduler(clock=FakeClock())
        assert sch.lane_for("consensus") == "consensus"
        assert sch.lane_for("blocksync") == "blocksync"

    def test_unregistered_subsystems_share_the_default_lane(self):
        sch = qs.QosScheduler(clock=FakeClock())
        assert sch.lane_for("pipeline") == qs.DEFAULT_LANE
        assert sch.lane_for("whatever") == qs.DEFAULT_LANE

    def test_explicit_lane_wins_only_when_registered(self):
        sch = qs.QosScheduler(clock=FakeClock())
        assert sch.lane_for("blocksync", lane="light") == "light"
        assert sch.lane_for("blocksync", lane="bogus") == "blocksync"
        assert sch.lane_for("nobody", lane="bogus") == qs.DEFAULT_LANE

    def test_priority_order_matches_registry(self):
        sch = qs.QosScheduler(clock=FakeClock())
        order = [sch.priority(l) for l in
                 ("consensus", "evidence", "lightserve", "blocksync",
                  "crypto")]
        assert order == sorted(order)
        assert sch.priority("consensus") < sch.priority("blocksync")
        # unregistered labels land in the lowest class
        assert sch.priority(qs.DEFAULT_LANE) == \
            sigcache.DEFAULT_LANE_PRIORITY

    def test_disabled_scheduler_has_one_priority_class(self):
        sch = qs.QosScheduler(enabled=False, clock=FakeClock())
        assert sch.priority("consensus") == 0
        assert sch.priority("blocksync") == 0


class TestStagingOrder:
    def test_urgent_lane_stages_first(self):
        clk = FakeClock()
        sch = qs.QosScheduler(clock=clk)
        bulk = enq(sch, "blocksync", staged=False)
        vote = enq(sch, "consensus", staged=False)
        assert sch.next_unstaged([bulk, vote], clk()) is vote

    def test_disabled_degenerates_to_fifo(self):
        clk = FakeClock()
        sch = qs.QosScheduler(enabled=False, clock=clk)
        bulk = enq(sch, "blocksync", staged=False)
        vote = enq(sch, "consensus", staged=False)
        assert sch.next_unstaged([bulk, vote], clk()) is bulk

    def test_within_lane_order_is_fifo(self):
        clk = FakeClock()
        sch = qs.QosScheduler(clock=clk)
        a = enq(sch, "blocksync", staged=False)
        b = enq(sch, "blocksync", staged=False)
        assert sch.next_unstaged([b, a], clk()) is a


class TestDispatchOrderAndPreemption:
    def test_vote_overtakes_queued_bulk(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        bulk = enq(sch, "blocksync", items=64)
        vote = enq(sch, "consensus", items=1)
        windows = [bulk, vote]
        win, holding = sch.pick_dispatch(windows, None, clk())
        assert win is vote and not holding
        vote.dispatching = True
        ev = sch.note_dispatch(vote, windows, clk())
        assert ev["lane"] == "consensus" and ev["overtook"] == 1
        # the overtaken window starts accruing held time
        assert bulk.held_since == clk()
        clk.advance(0.25)
        win, _ = sch.pick_dispatch(windows, None, clk())
        assert win is bulk
        ev2 = sch.note_dispatch(bulk, windows, clk())
        assert abs(ev2["held_s"] - 0.25) < 1e-9
        snap = sch.snapshot()
        assert snap["consensus"]["preemptions"] == 1
        assert snap["blocksync"]["windows"] == 1
        assert abs(snap["blocksync"]["held_s"] - 0.25) < 1e-9

    def test_dispatching_window_never_blocks_its_lane(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        inflight = enq(sch, "blocksync")
        inflight.dispatching = True
        nxt = enq(sch, "blocksync")
        win, _ = sch.pick_dispatch([inflight, nxt], None, clk())
        assert win is nxt

    def test_unstaged_lane_head_blocks_only_its_lane(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        head = enq(sch, "consensus", staged=False)
        later = enq(sch, "consensus", staged=True)
        bulk = enq(sch, "blocksync", staged=True)
        # consensus lane waits on its unstaged head (within-lane FIFO);
        # blocksync proceeds
        win, _ = sch.pick_dispatch([head, later, bulk], None, clk())
        assert win is bulk

    def test_device_filter_is_per_lane_head(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        d1 = enq(sch, "blocksync", staged=False, device_index=1)
        d0 = enq(sch, "blocksync", staged=True, device_index=0)
        # lane head on chip 1 is unstaged, but chip 0's own head is
        # ready — mesh fault isolation must not couple the chips
        win, _ = sch.pick_dispatch([d1, d0], 0, clk())
        assert win is d0

    def test_disabled_scheduler_is_exact_fifo(self):
        clk = FakeClock()
        sch = qs.QosScheduler(enabled=False, hold_s=0, clock=clk)
        bulk = enq(sch, "blocksync", items=64)
        vote = enq(sch, "consensus", items=1)
        win, _ = sch.pick_dispatch([bulk, vote], None, clk())
        assert win is bulk
        ev = sch.note_dispatch(bulk, [bulk, vote], clk())
        assert ev["overtook"] == 0


class TestDeadlinePromotion:
    def test_overdue_bulk_jumps_every_class(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        bulk = enq(sch, "blocksync")
        clk.advance(latledger.target_for("blocksync") + 0.01)
        vote = enq(sch, "consensus")
        win, _ = sch.pick_dispatch([bulk, vote], None, clk())
        assert win is bulk

    def test_promoted_windows_are_fifo_among_themselves(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        a = enq(sch, "blocksync")
        b = enq(sch, "crypto")
        clk.advance(max(latledger.target_for("blocksync"),
                        latledger.target_for("crypto")) + 0.01)
        win, _ = sch.pick_dispatch([b, a], None, clk())
        assert win is a

    def test_disabled_scheduler_never_promotes(self):
        clk = FakeClock()
        sch = qs.QosScheduler(enabled=False, hold_s=0, clock=clk)
        bulk = enq(sch, "blocksync")
        vote = enq(sch, "consensus")
        clk.advance(3600.0)
        win, _ = sch.pick_dispatch([bulk, vote], None, clk())
        assert win is bulk                       # still plain FIFO


class TestDeviceHold:
    def test_device_holds_for_staging_urgent_window(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0.002, clock=clk)
        bulk = enq(sch, "blocksync")
        vote = enq(sch, "consensus", staged=False)
        vote.staging_active = True
        win, holding = sch.pick_dispatch([bulk, vote], None, clk())
        assert win is None and holding
        assert sch.holding(None)

    def test_hold_expires_and_bulk_proceeds(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0.002, clock=clk)
        bulk = enq(sch, "blocksync")
        vote = enq(sch, "consensus", staged=False)
        vote.staging_active = True
        assert sch.pick_dispatch([bulk, vote], None, clk())[1]
        clk.advance(0.003)
        win, holding = sch.pick_dispatch([bulk, vote], None, clk())
        assert win is bulk and not holding
        assert not sch.holding(None)

    def test_zero_hold_budget_disables_holding(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        bulk = enq(sch, "blocksync")
        vote = enq(sch, "consensus", staged=False)
        vote.staging_active = True
        win, holding = sch.pick_dispatch([bulk, vote], None, clk())
        assert win is bulk and not holding

    def test_hold_is_per_device(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0.002, clock=clk)
        bulk0 = enq(sch, "blocksync", device_index=0)
        vote1 = enq(sch, "consensus", staged=False, device_index=1)
        vote1.staging_active = True
        # the urgent window is pinned to chip 1: chip 0 must not idle
        win, holding = sch.pick_dispatch([bulk0, vote1], 0, clk())
        assert win is bulk0 and not holding


class TestDeficitRoundRobin:
    def _drain(self, sch, windows, clk, picks):
        """Run the dispatch loop to completion, appending (lane, sigs)
        per pick; windows resolve immediately after dispatch."""
        while True:
            win, holding = sch.pick_dispatch(windows, None, clk())
            assert not holding
            if win is None:
                assert all(w.result is not None for w in windows)
                return
            sch.note_dispatch(win, windows, clk())
            picks.append((win.lane, len(win.items)))
            win.result = (True, [], "host")

    def test_equal_class_lanes_share_by_sig_count(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, quantum=8, clock=clk)
        windows = []
        for _ in range(12):
            windows.append(enq(sch, "light", items=8))
        for _ in range(12):
            windows.append(enq(sch, "lightserve", items=1))
        picks = []
        self._drain(sch, windows, clk, picks)
        assert len(picks) == 24
        # neither lane waits for the other to fully drain: both lanes
        # appear in the first half of the schedule
        first_half = {lane for lane, _ in picks[:12]}
        assert first_half == {"light", "lightserve"}
        # and the small-window lane is not starved by the big one:
        # every 8-sig light window costs a quantum, so lightserve's
        # 1-sig windows keep landing throughout
        last_ls = max(i for i, (lane, _) in enumerate(picks)
                      if lane == "lightserve")
        assert last_ls >= 12

    def test_oversized_window_still_dispatches(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, quantum=4, clock=clk)
        windows = [enq(sch, "light", items=100),
                   enq(sch, "lightserve", items=100)]
        picks = []
        self._drain(sch, windows, clk, picks)
        assert sorted(lane for lane, _ in picks) == \
            ["light", "lightserve"]

    def test_drained_lane_deficit_is_garbage_collected(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, quantum=8, clock=clk)
        windows = [enq(sch, "light", items=8),
                   enq(sch, "lightserve", items=8)]
        picks = []
        self._drain(sch, windows, clk, picks)
        sch.pick_dispatch([], None, clk())
        assert sch._deficit == {}


class TestSealAdvisory:
    def test_empty_queue_keeps_batching(self):
        # the flush interval is the designed latency; an idle pipeline
        # is not a reason to seal per-item and defeat coalescing
        clk = FakeClock()
        sch = qs.QosScheduler(clock=clk)
        assert not sch.seal_due([], "consensus", clk())

    def test_own_class_backpressure_keeps_batching(self):
        clk = FakeClock()
        sch = qs.QosScheduler(clock=clk)
        own = [enq(sch, "consensus") for _ in range(3)]
        assert not sch.seal_due(own, "consensus", clk())

    def test_cross_class_work_seals(self):
        clk = FakeClock()
        sch = qs.QosScheduler(clock=clk)
        bulk = enq(sch, "blocksync")
        assert sch.seal_due([bulk], "consensus", clk())
        vote = enq(sch, "consensus")
        assert sch.seal_due([vote], "blocksync", clk())

    def test_resolved_and_inflight_windows_do_not_count(self):
        clk = FakeClock()
        sch = qs.QosScheduler(clock=clk)
        done = enq(sch, "blocksync")
        done.result = (True, [], "host")
        inflight = enq(sch, "blocksync")
        inflight.dispatching = True
        # neither is QUEUED cross-class work — no preemption signal
        assert not sch.seal_due([done, inflight], "consensus", clk())
        live = enq(sch, "blocksync")
        assert sch.seal_due([done, inflight, live], "consensus", clk())

    def test_disabled_never_advises(self):
        clk = FakeClock()
        sch = qs.QosScheduler(enabled=False, clock=clk)
        assert not sch.seal_due([], "consensus", clk())


class TestEmit:
    def test_emit_none_is_noop(self):
        qs.QosScheduler(clock=FakeClock()).emit(None)

    def test_preempting_dispatch_records_flightrec_event(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        bulk = enq(sch, "blocksync", items=64)
        vote = enq(sch, "consensus", items=1)
        windows = [bulk, vote]
        win, _ = sch.pick_dispatch(windows, None, clk())
        ev = sch.note_dispatch(win, windows, clk())
        rec = flightrec.FlightRecorder()
        flightrec.set_recorder(rec)
        try:
            sch.emit(ev)
        finally:
            flightrec.set_recorder(None)
        events = [e for e in rec.events()
                  if e["kind"] == flightrec.EV_SCHED_PREEMPT]
        assert len(events) == 1
        assert events[0]["lane"] == "consensus"
        assert events[0]["overtook"] == 1

    def test_emit_drives_every_scheduler_metric(self):
        clk = FakeClock()
        sch = qs.QosScheduler(hold_s=0, clock=clk)
        bulk = enq(sch, "blocksync", items=4)
        vote = enq(sch, "consensus", items=1)
        windows = [bulk, vote]
        reg = libmetrics.Registry()
        libmetrics.set_scheduler_metrics(libmetrics.SchedulerMetrics(reg))
        try:
            win, _ = sch.pick_dispatch(windows, None, clk())
            win.dispatching = True
            sch.emit(sch.note_dispatch(win, windows, clk()))
            clk.advance(0.1)
            win, _ = sch.pick_dispatch(windows, None, clk())
            win.dispatching = True
            sch.emit(sch.note_dispatch(win, windows, clk()))
        finally:
            libmetrics.set_scheduler_metrics(None)
        text = reg.expose()
        assert 'cometbft_sched_dispatched_windows{lane="consensus"} 1' in text
        assert 'cometbft_sched_dispatched_windows{lane="blocksync"} 1' in text
        assert 'cometbft_sched_dispatched_sigs{lane="blocksync"} 4' in text
        assert 'cometbft_sched_preemptions_total{lane="consensus"} 1' in text
        assert 'cometbft_sched_held_seconds_total{lane="blocksync"} 0.1' in text
        assert 'cometbft_sched_lane_deficit_sigs{lane="consensus"}' in text


class TestPipelineQos:
    """Real-pipeline contracts on the host path."""

    def test_vote_preempts_staged_bulk_backlog(self):
        """A single vote submitted behind a queued bulk backlog must
        dispatch before the queued (not yet in-flight) bulk windows —
        observable as a scheduler preemption — and every verdict must
        still match the serial oracle."""
        sigcache.reset()
        bulk_feeds = [make_items(24, seed=10 + i) for i in range(4)]
        vote_items = make_items(1, seed=99)
        with vd.VerifyPipeline(depth=8, name="QosPipe") as pipe:
            bulk = [pipe.submit(list(f), subsystem="blocksync",
                                device_threshold=10**9)
                    for f in bulk_feeds]
            vote = pipe.submit(list(vote_items), subsystem="consensus",
                               device_threshold=10**9)
            ok, verdicts = vote.result(timeout=60)
            assert ok and verdicts == serial_verdicts(vote_items)
            for f, h in zip(bulk_feeds, bulk):
                assert h.result(timeout=60)[1] == serial_verdicts(f)
            snap = pipe.scheduler_snapshot()
        assert snap["consensus"]["windows"] == 1
        assert snap["blocksync"]["windows"] == 4
        # the vote jumped at least one queued bulk window
        assert snap["consensus"]["preemptions"] >= 1
        assert snap["blocksync"]["held_s"] >= 0.0

    def test_qos_off_pipeline_keeps_fifo_and_parity(self):
        sigcache.reset()
        feeds = [make_items(4, seed=20 + i) for i in range(3)]
        with vd.VerifyPipeline(depth=4, name="FifoPipe",
                               qos=False) as pipe:
            assert not pipe.qos
            handles = [pipe.submit(list(f), subsystem=s,
                                   device_threshold=10**9)
                       for f, s in zip(feeds, ("blocksync",
                                               "consensus", "light"))]
            for f, h in zip(feeds, handles):
                assert h.result(timeout=60)[1] == serial_verdicts(f)
            snap = pipe.scheduler_snapshot()
        assert all(s["preemptions"] == 0 for s in snap.values())
        assert not pipe.qos_seal_due("consensus")

    def test_held_time_stays_inside_exact_partition(self):
        """Preemption folds held time into the overtaken window's
        queue_wait — the ledger's per-request segments must still sum
        float-exactly to the wall."""
        sigcache.reset()
        rec = latledger.LatLedgerRecorder()
        prev = latledger.recorder()
        latledger.set_recorder(rec)
        try:
            feeds = [make_items(16, seed=40 + i) for i in range(3)]
            vote_items = make_items(1, seed=77)
            with vd.VerifyPipeline(depth=8, name="LedgerPipe") as pipe:
                handles = [pipe.submit(list(f), subsystem="blocksync",
                                       device_threshold=10**9)
                           for f in feeds]
                handles.append(pipe.submit(
                    list(vote_items), subsystem="consensus",
                    device_threshold=10**9))
                for h in handles:
                    assert h.result(timeout=60)[0]
        finally:
            latledger.set_recorder(prev)
        rows = rec.rows()
        assert len(rows) >= 4
        for row in rows:
            assert row["wall"] == sum(row["segs"].values())
        agg = rec.consumers()
        assert set(agg) >= {"consensus", "blocksync"}

    def test_brownout_admission_sheds_low_lane_first(self):
        """Brownout priority admission: while the queue is at the
        brownout bound and a consensus submitter is waiting, a
        crypto-lane submitter must yield its slot — degraded capacity
        sheds the lowest lanes first."""
        sigcache.reset()
        from cometbft_tpu.crypto import devhealth

        entered, gate = threading.Event(), threading.Event()

        def blocked_dispatch(win):
            entered.set()
            gate.wait(60)
            v = serial_verdicts(win.items)
            return all(v) and bool(v), v

        def await_waiter(pipe, prio):
            # submit() registers a waiter and waits under pipe._cv
            with pipe._cv:
                deadline = time.monotonic() + 60
                while prio not in pipe._bo_waiters:
                    assert time.monotonic() < deadline, pipe._bo_waiters
                    pipe._cv.wait(timeout=0.05)

        health = devhealth.HealthRegistry(quarantine_after=1,
                                          probe_backoff_s=60.0)
        order = []
        with vd.VerifyPipeline(depth=4, dispatch_fn=blocked_dispatch,
                               health=health, name="BoPipe") as pipe:
            orig = pipe._sched.note_enqueue

            def spy(win, label):
                order.append(label)
                orig(win, label)

            pipe._sched.note_enqueue = spy
            # wedge the device loop inside a dispatch (and see it
            # there: a window the loop reaches after the quarantine
            # resolves on the host and the queue drains), then queue
            # one more window so the queue sits at BROWNOUT_DEPTH
            first = pipe.submit(make_items(2, seed=1),
                                subsystem="blocksync",
                                device_threshold=1)
            assert entered.wait(60)
            second = pipe.submit(make_items(2, seed=2),
                                 subsystem="blocksync",
                                 device_threshold=1)
            # quarantine the only chip and latch brownout
            health.note_fault("0")
            pipe._check_brownout()
            assert pipe.in_brownout()

            def submit_lane(subsystem, seed):
                h = pipe.submit(make_items(2, seed=seed),
                                subsystem=subsystem,
                                device_threshold=10**9)
                h.result(timeout=30)

            low = threading.Thread(target=submit_lane,
                                   args=("crypto", 3), daemon=True)
            low.start()
            await_waiter(pipe, 4)
            high = threading.Thread(target=submit_lane,
                                    args=("consensus", 4), daemon=True)
            high.start()
            await_waiter(pipe, 0)
            # free the wedged dispatch; the queue drains and admission
            # order decides who lands first
            gate.set()
            high.join(timeout=30)
            low.join(timeout=30)
            assert not high.is_alive() and not low.is_alive()
            first.result(timeout=30)
            second.result(timeout=30)
        assert "consensus" in order and "crypto" in order
        assert order.index("consensus") < order.index("crypto")
