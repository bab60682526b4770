"""simnet: deterministic in-process multi-node harness
(cometbft_tpu/simnet/) — transport conditioning units, a seeded
3-node blocksync smoke with faults, reactor-level e2e bench drivers,
stage-span tracing, and real consensus over conditioned links.
"""

import hashlib
import time

import pytest

from cometbft_tpu.crypto import sigcache
from cometbft_tpu.libs import trace as libtrace
from cometbft_tpu.p2p.node_info import NodeInfo
from cometbft_tpu.p2p.transport import TransportError
from cometbft_tpu.simnet import (
    SimNetwork, SimNode, SimTransport, clone_chain, grow_chain,
    make_sim_genesis,
)

SMOKE_BLOCKS = 20


def _mk_transport(net, name, network_id="condnet"):
    info = NodeInfo(node_id=name[0] * 40, network=network_id,
                    channels=bytes([0x01]), moniker=name)
    t = SimTransport(net, None, info)
    inbound = []
    t.listen(f"{name}:0",
             lambda conn, their: inbound.append((conn, their)))
    return t, inbound


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


class TestTransport:
    def test_latency_drop_partition(self):
        net = SimNetwork(seed=9)
        net.set_link("x", "y", latency=0.05)
        tx, _ = _mk_transport(net, "x")
        _ty, inbound_y = _mk_transport(net, "y")
        conn, their = tx.dial("y:0")
        assert their.moniker == "y"
        assert _wait(lambda: inbound_y)
        rconn = inbound_y[0][0]
        t0 = time.perf_counter()
        conn.write(b"hello")
        assert rconn.read() == b"hello"
        assert time.perf_counter() - t0 >= 0.04

        # total loss: frames are blackholed, framing-safe
        net.set_link("x", "y", drop=1.0)
        conn.write(b"gone")
        time.sleep(0.08)
        assert rconn._inbox.empty()

        # partition fails dials across the cut; heal restores
        net.partition({"x"}, {"y"})
        with pytest.raises(TransportError):
            tx.dial("y:0")
        net.heal()
        net.set_link("x", "y")          # clean link again
        conn2, _ = tx.dial("y:0")
        conn2.write(b"back")
        assert _wait(lambda: len(inbound_y) == 2)
        assert inbound_y[1][0].read() == b"back"

    def test_link_rng_seeded_and_stable(self):
        a = [SimNetwork(seed=4).link_rng("n0", "n1").random()
             for _ in range(3)]
        b = [SimNetwork(seed=4).link_rng("n1", "n0").random()
             for _ in range(3)]
        assert a == b                    # unordered pair, same stream
        assert a != [SimNetwork(seed=5).link_rng("n0", "n1").random()
                     for _ in range(3)]

    def test_mconn_ping_pong_framing(self):
        """Pings fire length-prefixed like every packet: several ping
        cycles must not desync the stream (the pre-fix encoding wrote
        bare ping bytes the receiver parsed as a length prefix)."""
        from cometbft_tpu.p2p.conn.connection import (
            ChannelDescriptor, MConnection)
        net = SimNetwork(seed=2)
        tp, _ = _mk_transport(net, "p")
        _tq, inbound = _mk_transport(net, "q")
        conn_a, _ = tp.dial("q:0")
        assert _wait(lambda: inbound)
        conn_b = inbound[0][0]
        got, errs = [], []
        ma = MConnection(conn_a, [ChannelDescriptor(1)],
                         lambda ch, m: None, errs.append,
                         ping_interval=0.15, pong_timeout=3.0)
        mb = MConnection(conn_b, [ChannelDescriptor(1)],
                         lambda ch, m: got.append(m), errs.append,
                         ping_interval=0.15, pong_timeout=3.0)
        ma.start()
        mb.start()
        try:
            time.sleep(0.6)              # ~4 ping cycles each way
            assert ma.send(1, b"after-pings")
            assert _wait(lambda: got)
            assert got == [b"after-pings"]
            assert not errs
            assert ma.is_running() and mb.is_running()
        finally:
            ma.stop()
            mb.stop()


class TestBlocksyncSmoke:
    def test_clean_sync_with_trace(self):
        """3-node fast smoke: 20 real blocks through the real reactor
        into the store, every pipeline stage span recorded."""
        # this test pins the verify lanes themselves; the process-wide
        # verdict cache (shared across in-process sim nodes) would
        # resolve the syncer's windows at submit and starve the device
        # stage of spans
        sigcache.set_enabled(False)
        net = SimNetwork(seed=7)
        net.set_default_link(latency=0.001)
        genesis, privs = make_sim_genesis(4, seed=7)
        src = SimNode("src", genesis, net, seed=7)
        # +1: blocksync converges one block behind the serving tip
        # (the tip's LastCommit is what verifies the target height)
        grow_chain(src, privs, SMOKE_BLOCKS + 1)
        src2 = SimNode("src2", genesis, net, seed=7)
        clone_chain(src, src2)
        assert src2.app_hash() == src.app_hash()
        syncer = SimNode("syncer", genesis, net, block_sync=True, seed=7)

        tracer = libtrace.StageTracer()
        libtrace.set_tracer(tracer)
        nodes = (src, src2, syncer)
        try:
            for n in nodes:
                n.start()
            syncer.dial(src)
            syncer.dial(src2)
            assert syncer.wait_for_height(SMOKE_BLOCKS, timeout=60), \
                f"stalled at {syncer.height()}"
        finally:
            libtrace.set_tracer(None)
            for n in nodes:
                n.stop()
        # header above the target pins the app hash the syncer reached
        assert syncer.app_hash() == \
            src.block_store.load_block(SMOKE_BLOCKS + 1).header.app_hash
        # txs really executed through ABCI on the syncing node
        assert syncer.app.kv.get(f"sim{SMOKE_BLOCKS}x0") == \
            f"v{SMOKE_BLOCKS}"
        snap = tracer.snapshot()
        for stage in libtrace.BLOCKSYNC_STAGES:
            key = f"blocksync.{stage}"
            assert key in snap and snap[key]["count"] > 0, \
                (stage, snap)

    def test_faulted_sync_deterministic(self, monkeypatch):
        """Acceptance: a seeded run with drops + one partition heal
        completes to the target height with IDENTICAL final app hash
        and height across two runs."""
        from cometbft_tpu.blocksync import pool as bpool
        from cometbft_tpu.blocksync import reactor as breactor
        monkeypatch.setattr(bpool, "PEER_TIMEOUT", 2.0)
        monkeypatch.setattr(breactor, "STATUS_UPDATE_INTERVAL", 0.5)

        r1 = self._faulted_run(seed=1234)
        r2 = self._faulted_run(seed=1234)
        assert r1 == r2
        assert r1[0] == SMOKE_BLOCKS

    @staticmethod
    def _faulted_run(seed):
        net = SimNetwork(seed=seed)
        net.set_default_link(latency=0.001)
        net.set_link("src0", "syncer", latency=0.002, jitter=0.002,
                     drop=0.08)
        genesis, privs = make_sim_genesis(4, seed=seed)
        src0 = SimNode("src0", genesis, net, seed=seed)
        grow_chain(src0, privs, SMOKE_BLOCKS + 1)
        src1 = SimNode("src1", genesis, net, seed=seed)
        clone_chain(src0, src1)
        syncer = SimNode("syncer", genesis, net, block_sync=True,
                         seed=seed)
        nodes = (src0, src1, syncer)
        for n in nodes:
            n.start()
        try:
            # persistent: an evicted-on-timeout peer redials, like the
            # reference's persistent_peers during network trouble
            syncer.dial(src0, persistent=True)
            syncer.dial(src1, persistent=True)
            net.partition({"src0", "src1"}, {"syncer"})
            time.sleep(0.3)
            net.heal()
            assert syncer.wait_for_height(SMOKE_BLOCKS, timeout=90), \
                f"stalled at {syncer.height()}"
            want = src0.block_store.load_block(
                SMOKE_BLOCKS + 1).header.app_hash
            assert syncer.app_hash() == want
            return (syncer.height(),
                    syncer.app_hash().hex(),
                    want.hex())
        finally:
            for n in nodes:
                n.stop()


class TestE2EBench:
    def test_blocksync_e2e_bench_small(self):
        sigcache.set_enabled(False)     # pin the device stage span
        from cometbft_tpu.simnet import bench as simbench
        res = simbench.bench_blocksync_e2e(
            n_blocks=8, n_vals=4, txs_per_block=1, seed=3, timeout=60)
        assert res["blocks_per_sec"] > 0
        assert res["blocks"] == 8
        assert "blocksync.device" in res["stages"]
        assert simbench.last_blocksync is res

    def test_consensus_e2e_bench_small(self):
        """Live rounds through the real consensus reactor, with the
        per-stage consensus breakdown + round-latency histogram + per
        node flight-recorder summaries in one record."""
        from cometbft_tpu.simnet import bench as simbench
        # cache=False pins the verify_dispatch lane (in-process sim
        # nodes share the verdict cache, which otherwise resolves every
        # gossiped vote at submit); the cached arm is covered by
        # tests/test_sigcache.py's A/B parity test
        res = simbench.bench_consensus_e2e(
            n_blocks=3, n_vals=3, seed=17, timeout=120, cache=False)
        assert res["blocks_per_sec"] > 0
        assert res["blocks"] == 3
        for stage in ("consensus.propose", "consensus.prevote",
                      "consensus.precommit", "consensus.commit",
                      "consensus.verify_dispatch"):
            assert stage in res["stages"] and \
                res["stages"][stage]["count"] > 0, (stage, res["stages"])
        assert res["round_latency_seconds"]["samples"] >= 1
        assert res["round_latency_seconds"]["p50"] > 0
        assert set(res["recorders"]) == {"cval0", "cval1", "cval2"}
        for summ in res["recorders"].values():
            assert summ["recorded"] > 0
        assert simbench.last_consensus is res

    def test_light_e2e_over_real_rpc(self):
        """Headers through light/client.py against a simnet node's
        REAL JSON-RPC server (HttpProvider over HTTP loopback)."""
        from cometbft_tpu.simnet import bench as simbench
        res = simbench.bench_light_e2e(
            n_headers=6, n_vals=4, seed=5, sequential_batch_size=4)
        assert res["headers_per_sec"] > 0
        assert res["headers"] == 7      # 6 synced + the grown tip
        assert "light.device" in res["stages"]
        assert "light.fetch" in res["stages"]
        assert simbench.last_light is res


class TestPipelinedBlocksync:
    def test_pipeline_depth_knob_and_stages(self):
        """bench_blocksync_e2e's pipeline_depth knob: a depth-2 run
        syncs correctly through the overlapped reactor path and the
        pipeline-only stages (collect, host_pack) land in the trace
        next to the classic five."""
        sigcache.set_enabled(False)     # pin the device stage span
        from cometbft_tpu.simnet import bench as simbench
        res = simbench.bench_blocksync_e2e(
            n_blocks=8, n_vals=4, txs_per_block=1, seed=3, timeout=60,
            pipeline_depth=2)
        assert res["blocks_per_sec"] > 0
        assert res["pipeline_depth"] == 2
        assert "overlap_efficiency" in res
        assert "device_overlap_seconds" in res
        for stage in libtrace.BLOCKSYNC_STAGES:
            assert f"blocksync.{stage}" in res["stages"], res["stages"]
        for stage in libtrace.PIPELINE_STAGES:
            assert f"blocksync.{stage}" in res["stages"], res["stages"]

    def test_depth_one_serial_path_still_syncs(self):
        from cometbft_tpu.simnet import bench as simbench
        res = simbench.bench_blocksync_e2e(
            n_blocks=8, n_vals=4, txs_per_block=1, seed=3, timeout=60,
            pipeline_depth=1)
        assert res["blocks_per_sec"] > 0
        assert res["pipeline_depth"] == 1

    def test_device_failure_mid_pipeline_drains_without_loss(
            self, monkeypatch):
        """Acceptance: a device failure injected mid-pipeline drains
        cleanly — the faulted window falls back to host verdicts, no
        block is lost or misordered, and the syncer reaches the same
        app hash the serial path would."""
        from cometbft_tpu.crypto.dispatch import VerifyPipeline
        from cometbft_tpu.libs import flightrec
        from cometbft_tpu.types import validation

        # the fault only fires if windows actually dispatch — the
        # shared in-process verdict cache would resolve them at submit
        sigcache.set_enabled(False)
        # force the ed25519 device lane so the injected dispatch_fn is
        # actually on the path (fixture sigs are far below the real
        # threshold); the stub keeps the XLA compile out of fast tier
        monkeypatch.setattr(validation.DeferredSigBatch,
                            "DEVICE_THRESHOLD", 1)
        calls = {"n": 0}

        def flaky_device(win):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError("injected mid-pipeline device fault")
            # the real staging (the RLC pack) already ran
            from cometbft_tpu.crypto.batch import safe_verify
            out = [safe_verify(pk, m, s) for pk, m, s in win.items]
            return all(out), out

        net = SimNetwork(seed=41)
        net.set_default_link(latency=0.001)
        genesis, privs = make_sim_genesis(4, seed=41)
        src = SimNode("fsrc", genesis, net, seed=41)
        grow_chain(src, privs, SMOKE_BLOCKS + 1)
        syncer = SimNode("fsync", genesis, net, block_sync=True,
                         seed=41)
        pipe = VerifyPipeline(depth=2, dispatch_fn=flaky_device,
                              name="fault-pipeline")
        pipe.start()
        syncer.blocksync_reactor._pipeline = pipe
        syncer.blocksync_reactor.pipeline_depth = 2
        rec = flightrec.FlightRecorder()
        flightrec.set_recorder(rec)
        try:
            src.start()
            syncer.start()
            syncer.dial(src)
            assert syncer.wait_for_height(SMOKE_BLOCKS, timeout=90), \
                f"stalled at {syncer.height()}"
        finally:
            flightrec.set_recorder(None)
            syncer.stop()
            src.stop()
        assert calls["n"] >= 1              # the fault really fired
        assert pipe.faults >= 1
        assert syncer.app_hash() == src.block_store.load_block(
            SMOKE_BLOCKS + 1).header.app_hash
        kinds = [e["kind"] for e in rec.events()]
        assert flightrec.EV_PIPELINE_DRAIN in kinds


class TestTrace:
    def test_tracer_metrics_export(self):
        from cometbft_tpu.libs.metrics import Registry, TraceMetrics
        reg = Registry("cometbft")
        tracer = libtrace.StageTracer(metrics=TraceMetrics(reg))
        with libtrace._TimedSpan(tracer, "blocksync", "device"):
            pass
        tracer.record("blocksync", "apply", 0.002)
        snap = tracer.snapshot()
        assert snap["blocksync.apply"]["count"] == 1
        assert snap["blocksync.device"]["count"] == 1
        text = reg.expose()
        assert "cometbft_trace_stage_duration_seconds" in text
        assert 'stage="apply"' in text

    def test_span_noop_without_tracer(self):
        libtrace.set_tracer(None)
        with libtrace.span("blocksync", "device"):
            pass                         # must not record anywhere
        assert libtrace.span("a", "b") is libtrace.span("c", "d")


class TestConsensusObservability:
    """Acceptance: scraping /metrics during a live simnet consensus run
    shows nonzero step durations and consensus trace spans, and a
    partition-faulted run leaves a flight-recorder dump containing the
    round>0 escalation timeline."""

    CORE_STEPS = ("RoundStepNewHeight", "RoundStepNewRound",
                  "RoundStepPropose", "RoundStepPrevote",
                  "RoundStepPrecommit", "RoundStepCommit")

    def test_partitioned_proposer_metrics_spans_flightrec(self):
        import json
        import urllib.request

        from cometbft_tpu.libs.metrics import (
            ConsensusMetrics, MetricsServer, P2PMetrics, Registry,
            TraceMetrics)

        # the verify_dispatch span assertion needs live verification:
        # with the in-process verdict cache shared across sim nodes,
        # every gossiped vote resolves at submit
        sigcache.set_enabled(False)
        net = SimNetwork(seed=31)
        net.set_default_link(latency=0.002, jitter=0.001)
        genesis, privs = make_sim_genesis(4, seed=31)
        nodes = [SimNode(f"obs{i}", genesis, net, priv_validator=p,
                         consensus_active=True, seed=31)
                 for i, p in enumerate(privs)]

        reg = Registry("cometbft_tpu")
        cm = ConsensusMetrics(reg)
        pm = P2PMetrics(reg)
        for n in nodes:
            n.consensus_state.metrics = cm
            n.switch.metrics = pm
        prev_tracer = libtrace.tracer()
        libtrace.set_tracer(libtrace.StageTracer(TraceMetrics(reg)))
        srv = MetricsServer(reg, "127.0.0.1:0")
        srv.start()

        live = nodes[1:]
        try:
            for n in nodes:
                n.start()
            for i, a in enumerate(nodes):
                for b in nodes[i + 1:]:
                    b.dial(a)
            # cut node 0 off: when its turn to propose comes, the live
            # trio times out, nil-polkas, and escalates past round 0
            net.partition({nodes[0].name}, {n.name for n in live})

            def escalated():
                return [n for n in live
                        if any(e["kind"] == "round_escalation"
                               for e in n.flight_recorder.events())]

            assert _wait(lambda: escalated() and
                         all(n.height() >= 1 for n in live),
                         timeout=90), \
                [n.height() for n in nodes]
            esc_node = escalated()[0]
            net.heal()
            target = max(n.height() for n in live) + 2
            assert _wait(lambda: all(n.height() >= target
                                     for n in live), timeout=60), \
                [n.height() for n in nodes]

            # -- scrape /metrics over HTTP ----------------------------
            with urllib.request.urlopen(
                    f"http://{srv.bound_addr}/metrics",
                    timeout=10) as resp:
                text = resp.read().decode()
            for step in self.CORE_STEPS:
                line = ("cometbft_tpu_consensus_step_duration_seconds"
                        f'_count{{step="{step}"}}')
                hits = [ln for ln in text.splitlines()
                        if ln.startswith(line)]
                assert hits and float(hits[0].split()[-1]) > 0, step
            for ln in ("cometbft_tpu_consensus_round_duration_seconds"
                       "_count",
                       "cometbft_tpu_consensus_proposal_receive_count"
                       '{status="accepted"}'):
                hits = [x for x in text.splitlines()
                        if x.startswith(ln)]
                assert hits and float(hits[0].split()[-1]) > 0, ln
            # consensus stage spans cover the hot path
            for stage in ("propose", "prevote", "precommit", "commit",
                          "verify_dispatch"):
                needle = ('cometbft_tpu_trace_stage_duration_seconds_'
                          'count{subsystem="consensus",stage="'
                          f'{stage}"}}')
                hits = [x for x in text.splitlines()
                        if x.startswith(needle)]
                assert hits and float(hits[0].split()[-1]) > 0, stage
            # per-channel p2p byte counters (vote channel flowed)
            assert ('cometbft_tpu_p2p_message_send_bytes_total'
                    '{chID="0x22"}') in text
            assert ('cometbft_tpu_p2p_message_receive_bytes_total'
                    '{chID="0x22"}') in text

            # -- flight-recorder escalation timeline ------------------
            evs = esc_node.flight_recorder.events()
            esc = next(e for e in evs
                       if e["kind"] == "round_escalation")
            assert esc["round"] >= 1
            before = [e for e in evs if e["seq"] < esc["seq"]
                      and e.get("height") == esc["height"]]
            assert any(e["kind"] == "timeout" for e in before), \
                "escalation timeline must show the timeouts that led up"
            assert any(e["kind"] == "step" for e in before)
            summ = esc_node.recorder_summary()
            assert summ["by_kind"]["round_escalation"] >= 1
            assert summ["max_round_seen"] >= 1

            # -- the flightrec RPC route serves the same dump ---------
            addr = esc_node.start_rpc()
            with urllib.request.urlopen(
                    f"http://{addr}/flightrec?limit=500",
                    timeout=10) as resp:
                out = json.loads(resp.read().decode())["result"]
            assert out["recorded"] > 0
            assert any(e["kind"] == "round_escalation"
                       for e in out["events"])
        finally:
            libtrace.set_tracer(prev_tracer)
            srv.stop()
            for n in nodes:
                n.stop()


class TestConsensusOverSimnet:
    def test_consensus_commits_over_simnet(self):
        """Real consensus (3 validators) over conditioned links: the
        simnet transport must carry the full gossip protocol."""
        net = SimNetwork(seed=21)
        net.set_default_link(latency=0.002, jitter=0.001)
        genesis, privs = make_sim_genesis(3, seed=21)
        nodes = [SimNode(f"val{i}", genesis, net, priv_validator=p,
                         consensus_active=True, seed=21)
                 for i, p in enumerate(privs)]
        for n in nodes:
            n.start()
        try:
            for i, a in enumerate(nodes):
                for b in nodes[i + 1:]:
                    b.dial(a)
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                if all(n.height() >= 2 for n in nodes):
                    break
                time.sleep(0.05)
            assert all(n.height() >= 2 for n in nodes), \
                [n.height() for n in nodes]
            h1 = {n.block_store.load_block(1).hash() for n in nodes}
            assert len(h1) == 1
        finally:
            for n in nodes:
                n.stop()


@pytest.mark.slow
def test_faulted_soak_long(monkeypatch):
    """Soak: 200 blocks, 7 validators, lossy jittered links, two
    partition/heal cycles mid-sync.

    Both device thresholds are pushed out of reach: this test is about
    the NETWORK fault machinery, and on the CPU tier a 48-block
    deferred window (240 sigs) would otherwise cold-compile a fresh
    XLA kernel shape per partial-window size, minutes each."""
    from cometbft_tpu.blocksync import pool as bpool
    from cometbft_tpu.blocksync import reactor as breactor
    from cometbft_tpu.types import validation
    monkeypatch.setattr(bpool, "PEER_TIMEOUT", 3.0)
    monkeypatch.setattr(breactor, "STATUS_UPDATE_INTERVAL", 0.5)
    monkeypatch.setattr(validation.DeferredSigBatch,
                        "DEVICE_THRESHOLD", 1 << 30)

    seed = 99
    net = SimNetwork(seed=seed)
    net.set_default_link(latency=0.002, jitter=0.002, drop=0.01)
    genesis, privs = make_sim_genesis(7, seed=seed)
    src0 = SimNode("src0", genesis, net, seed=seed)
    grow_chain(src0, privs, 201)
    src1 = SimNode("src1", genesis, net, seed=seed)
    clone_chain(src0, src1)
    syncer = SimNode("syncer", genesis, net, block_sync=True, seed=seed)
    nodes = (src0, src1, syncer)
    for n in nodes:
        n.start()
    try:
        syncer.dial(src0, persistent=True)
        syncer.dial(src1, persistent=True)
        for _ in range(2):
            time.sleep(1.0)
            net.partition({"src0", "src1"}, {"syncer"})
            time.sleep(0.5)
            net.heal()
        assert syncer.wait_for_height(200, timeout=300), \
            f"stalled at {syncer.height()}"
        assert syncer.app_hash() == \
            src0.block_store.load_block(201).header.app_hash
    finally:
        for n in nodes:
            n.stop()


@pytest.mark.slow
def test_pipeline_depth_sweep_soak():
    """Depth sweep on the same seed (the serial-vs-pipelined A/B the
    bench runs on hardware): every depth syncs the identical chain to
    the identical app hash, depth >= 2 records the pipeline stages,
    and the interval records show a device span concurrent with a
    collect/host_pack span of the next window.  Device thresholds are
    pushed out of reach (CPU tier: a fresh XLA shape costs minutes);
    the overlap machinery is the thing under soak, not the kernel."""
    from cometbft_tpu.simnet import bench as simbench
    from cometbft_tpu.types import validation

    import pytest as _pytest
    mp = _pytest.MonkeyPatch()
    mp.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD", 1 << 30)
    results = {}
    try:
        for depth in (1, 2, 3):
            results[depth] = simbench.bench_blocksync_e2e(
                n_blocks=48, n_vals=32, txs_per_block=1, seed=23,
                timeout=300, pipeline_depth=depth)
    finally:
        mp.undo()
    rates = {d: r["blocks_per_sec"] for d, r in results.items()}
    assert all(r["blocks"] == 48 for r in results.values()), rates
    for depth in (2, 3):
        stages = results[depth]["stages"]
        assert "blocksync.collect" in stages, (depth, stages)
        assert "blocksync.host_pack" in stages, (depth, stages)
    # the soak's overlap proof: at depth >= 2 SOME device span ran
    # concurrently with a later window's collect/pack (48 windows of
    # 32-validator commits give the scheduler every opportunity)
    assert any(results[d]["device_overlap_seconds"] > 0
               for d in (2, 3)), rates


def test_sim_genesis_deterministic():
    g1, p1 = make_sim_genesis(4, seed=6)
    g2, p2 = make_sim_genesis(4, seed=6)
    assert g1.chain_id == g2.chain_id
    assert [p.pub_key().bytes() for p in p1] == \
        [p.pub_key().bytes() for p in p2]
    digest = hashlib.sha256(
        b"".join(p.pub_key().bytes() for p in p1)).hexdigest()
    g3, p3 = make_sim_genesis(4, seed=8)
    assert hashlib.sha256(
        b"".join(p.pub_key().bytes() for p in p3)).hexdigest() != digest
