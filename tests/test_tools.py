"""Test-strategy parity tools: ABCI grammar checker (reference
test/e2e/pkg/grammar/checker_test.go), loadtime reporter
(test/loadtime/report), SQL event sink (state/indexer/sink/psql).
"""

import time

import pytest

from cometbft_tpu.abci.grammar import GrammarError, RecordingApp, verify
from cometbft_tpu.state.sink import SQLEventSink
from cometbft_tpu.tools import loadtime

from tests.test_consensus import wait_for_height


class TestGrammar:
    def test_clean_start_legal(self):
        verify(["init_chain", "finalize_block", "commit",
                "prepare_proposal", "process_proposal",
                "finalize_block", "commit"], clean_start=True)

    def test_statesync_clean_start(self):
        # failed attempt (offer only), then success with chunks
        verify(["offer_snapshot", "offer_snapshot",
                "apply_snapshot_chunk", "apply_snapshot_chunk",
                "finalize_block", "commit"], clean_start=True)

    def test_vote_extensions_round(self):
        verify(["init_chain",
                "prepare_proposal", "process_proposal", "extend_vote",
                "verify_vote_extension", "verify_vote_extension",
                "finalize_block", "commit"], clean_start=True)

    def test_recovery_without_init_chain(self):
        verify(["process_proposal", "finalize_block", "commit"],
               clean_start=False)

    def test_partial_trailing_height_allowed(self):
        verify(["init_chain", "finalize_block", "commit",
                "prepare_proposal"], clean_start=True)

    def test_info_ignored(self):
        verify(["info", "init_chain", "info", "finalize_block",
                "commit"], clean_start=True)

    def test_illegal_sequences(self):
        # commit before finalize_block
        with pytest.raises(GrammarError):
            verify(["init_chain", "commit"], clean_start=True)
        # consensus before init_chain on clean start
        with pytest.raises(GrammarError):
            verify(["finalize_block", "commit", "init_chain"],
                   clean_start=True)
        # double init_chain
        with pytest.raises(GrammarError):
            verify(["init_chain", "init_chain", "finalize_block",
                    "commit"], clean_start=True)
        # snapshot chunks without an offer
        with pytest.raises(GrammarError):
            verify(["apply_snapshot_chunk", "finalize_block", "commit"],
                   clean_start=True)

    def test_recording_app_against_live_node(self, tmp_path):
        from cometbft_tpu.apps.kvstore import KVStoreApplication
        from cometbft_tpu.config import test_config as _tcfg
        from cometbft_tpu.node import Node, init_files

        cfg = _tcfg(str(tmp_path))
        cfg.base.abci = "local"     # use OUR wrapped app instance
        init_files(cfg, chain_id="grammar-chain")
        app = RecordingApp(KVStoreApplication())
        n = Node(cfg, app=app)
        n.start()
        try:
            assert wait_for_height(n.consensus_state, 4, timeout=60)
        finally:
            n.stop()
        app.verify(clean_start=True)
        assert "finalize_block" in app.calls


class TestLoadtime:
    def test_payload_roundtrip(self):
        tx = loadtime.make_payload(7, "runx", size=128)
        assert len(tx) == 128
        body = loadtime.parse_payload(tx)
        assert body["seq"] == 7 and body["run"] == "runx"
        assert loadtime.parse_payload(b"not-a-payload") is None

    def test_report_from_block_store(self, tmp_path):
        from cometbft_tpu.config import test_config as _tcfg
        from cometbft_tpu.node import Node, init_files
        from cometbft_tpu.rpc.client import HTTPClient

        cfg = _tcfg(str(tmp_path))
        init_files(cfg, chain_id="load-chain")
        n = Node(cfg)
        n.start()
        try:
            assert wait_for_height(n.consensus_state, 2, timeout=60)
            client = HTTPClient(n.rpc_addr, timeout=30)
            gen = loadtime.LoadGenerator(client, rate=50, size=64)
            sent = gen.run(10)
            assert sent == 10
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                rep = loadtime.report_from_block_store(
                    n.block_store, run_id=gen.run_id)
                if rep.n_txs == 10:
                    break
                time.sleep(0.3)
            assert rep.n_txs == 10
            s = rep.summary()
            # BFT time = median of the PREVIOUS commit's vote times, so
            # on a fast test chain latencies sit within ~1 block of
            # zero; on production intervals they are strictly positive
            assert -1 < s["latency_s"]["p50"] < 30
            assert s["latency_s"]["max"] < 30
            assert s["latency_s"]["max"] >= s["latency_s"]["min"]
            assert len(rep.block_intervals_s) >= 1
            assert s["block_interval_s"]["avg"] > 0
        finally:
            n.stop()


class TestSQLEventSink:
    def test_sink_schema_and_node_wiring(self, tmp_path):
        from cometbft_tpu.config import test_config as _tcfg
        from cometbft_tpu.node import Node, init_files
        from cometbft_tpu.rpc.client import HTTPClient

        cfg = _tcfg(str(tmp_path))
        cfg.tx_index.indexer = "psql"
        init_files(cfg, chain_id="sink-chain")
        n = Node(cfg)
        n.start()
        try:
            assert wait_for_height(n.consensus_state, 2, timeout=60)
            client = HTTPClient(n.rpc_addr, timeout=30)
            client.broadcast_tx_commit(b"sink-k=sink-v")
            deadline = time.monotonic() + 15
            rows = []
            while time.monotonic() < deadline:
                rows = n.event_sink.query(
                    "SELECT tx_hash, block_id FROM tx_results")
                if rows:
                    break
                time.sleep(0.2)
            assert rows, "tx never reached the sink"
            # blocks table has the chain + heights
            blocks = n.event_sink.query(
                "SELECT height, chain_id FROM blocks ORDER BY height")
            assert blocks and blocks[0][1] == "sink-chain"
            # the joined view exposes composite keys
            attrs = n.event_sink.query(
                "SELECT composite_key, value FROM event_attributes "
                "WHERE composite_key LIKE 'app.%'")
            assert attrs
            # with psql indexing, kv-backed /tx_search is disabled
            assert n.tx_indexer is None
        finally:
            n.stop()


class TestWal2Json:
    def test_dump_real_wal(self, tmp_path):
        """Run a node for a few heights, then dump its WAL to JSON
        lines (reference scripts/wal2json)."""
        import json as _json
        import os
        import time

        from cometbft_tpu.config import test_config as _tcfg
        from cometbft_tpu.node import Node, init_files
        from cometbft_tpu.tools.wal2json import main as wal2json_main
        from tests.test_consensus import wait_for_height

        home = str(tmp_path)
        cfg = _tcfg(home)
        init_files(cfg, chain_id="wal-chain")
        n = Node(cfg)
        n.start()
        try:
            assert wait_for_height(n.consensus_state, 3, timeout=60)
        finally:
            n.stop()
        head = os.path.join(cfg.db_dir(), "cs.wal", "wal")
        assert os.path.exists(head)
        import contextlib
        import io
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = wal2json_main([head])
        assert rc == 0
        lines = [l for l in buf.getvalue().splitlines() if l]
        assert len(lines) > 5
        types = {_json.loads(l)["type"] for l in lines}
        assert "EndHeightMessage" in types
        assert "MsgInfo" in types
        # every line is valid JSON with a time
        rec = _json.loads(lines[0])
        assert "time" in rec and "msg" in rec

    def test_missing_wal(self, tmp_path):
        import os

        from cometbft_tpu.tools.wal2json import main as wal2json_main

        missing = str(tmp_path / "no-such-dir" / "wal")
        assert wal2json_main([missing]) == 1
        # the dump tool must not create anything (WAL() would)
        assert not os.path.exists(os.path.dirname(missing))


class TestCheckMetrics:
    """scripts/check_metrics.py: the metricsgen-style lint runs as a
    tier-1 test so a drifted metrics bundle fails CI, not a dashboard."""

    @staticmethod
    def _load():
        import importlib.util
        import pathlib
        path = pathlib.Path(__file__).resolve().parent.parent / \
            "scripts" / "check_metrics.py"
        spec = importlib.util.spec_from_file_location(
            "check_metrics", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_repo_bundles_are_clean(self):
        mod = self._load()
        assert mod.run_checks() == []

    def test_parser_sees_the_new_consensus_metrics(self):
        mod = self._load()
        metrics = mod.registered_metrics()
        assert len(metrics) >= 50
        names = {(m["subsystem"], m["name"]) for m in metrics}
        for want in ("step_duration_seconds", "round_duration_seconds",
                     "quorum_prevote_delay", "proposal_receive_count",
                     "late_votes", "duplicate_vote_count"):
            assert ("consensus", want) in names, want
        for want in ("message_send_bytes_total",
                     "message_receive_bytes_total"):
            assert ("p2p", want) in names, want

    def test_trace_ring_overflow_counter_is_linted(self, monkeypatch):
        """The StageTracer ring-overflow counter
        (trace_intervals_dropped_total) is registered AND observed —
        the lint proves libs/trace.py actually drives it on eviction,
        so silent interval loss shows on dashboards."""
        mod = self._load()
        metrics = {(m["subsystem"], m["name"]): m
                   for m in mod.registered_metrics()}
        m = metrics.get(("trace", "intervals_dropped_total"))
        assert m is not None and m["kind"] == "counter"
        assert m["attr"] == "intervals_dropped"
        assert mod.run_checks() == []
        # and the counter really counts: overflow a tiny ring
        from cometbft_tpu.libs import trace as libtrace
        monkeypatch.setattr(libtrace, "MAX_INTERVALS", 2)
        tr = libtrace.StageTracer()
        for i in range(5):
            tr.record("s", "st", 0.5)
        assert tr.dropped_intervals == 3
        assert len(tr.intervals()) == 2

    def test_parser_flags_bad_bundles(self, tmp_path):
        mod = self._load()
        bad = tmp_path / "m.py"
        bad.write_text(
            "class A:\n"
            "    def __init__(self, reg):\n"
            "        self.x = reg.counter('c', 'CamelCase', 'H.')\n"
            "        self.y = reg.gauge('c', 'dup', 'H.')\n"
            "        self.z = reg.gauge('c', 'dup', 'H.')\n")
        metrics = mod.registered_metrics(bad)
        assert {m["attr"] for m in metrics} == {"x", "y", "z"}
        full = [f"{m['subsystem']}_{m['name']}" for m in metrics]
        assert full.count("c_dup") == 2
        assert not mod.SNAKE.match("CamelCase")

    def test_devprof_bundle_is_linted(self):
        """The DevprofMetrics bundle (libs/metrics.py): per-device
        series carry the device label, cumulative-seconds counters end
        _seconds_total, and the parser captures literal labels= — the
        rules scripts/check_metrics.py enforces for the device-time
        accounting plane."""
        mod = self._load()
        metrics = {(m["subsystem"], m["name"]): m
                   for m in mod.registered_metrics()}
        busy = metrics[("devprof", "busy_seconds_total")]
        assert busy["kind"] == "counter"
        assert busy["labels"] == ["device"]
        idle = metrics[("devprof", "idle_seconds_total")]
        assert idle["labels"] == ["device", "cause"]
        occ = metrics[("devprof", "occupancy_ratio")]
        assert occ["kind"] == "gauge" and occ["labels"] == ["device"]
        assert metrics[("devprof",
                        "compile_seconds_total")]["labels"] is None
        assert metrics[("devprof",
                        "compile_count")]["labels"] == ["kind"]
        assert mod.run_checks() == []

    def test_lint_flags_devprof_rule_violations(self, tmp_path,
                                                monkeypatch):
        mod = self._load()
        bad = tmp_path / "m.py"
        bad.write_text(
            "class DevprofMetrics:\n"
            "    def __init__(self, reg):\n"
            "        self.a = reg.counter('devprof', 'busy_seconds',\n"
            "                             'H.')\n"
            "        self.b = reg.gauge('devprof', 'occupancy_ratio',\n"
            "                           'H.', labels=('BadLabel',))\n")
        monkeypatch.setattr(mod, "METRICS_PY", bad)
        findings = mod.run_checks()
        # bare _seconds counter, missing device label (on both), and
        # a non-snake_case label all surface as findings
        assert any("_seconds_total" in f for f in findings)
        assert any("'device' label" in f for f in findings)
        assert any("BadLabel" in f for f in findings)


class TestLabelRegistryLint:
    """check_metrics rule 7: every literal dispatch_scope kind and
    busy/flush-path label in cometbft_tpu/ must appear in the
    devprof.DISPATCH_KINDS / devprof.BUSY_PATHS registries — a new
    kernel cannot ship with its device time pooling under 'other'."""

    def test_registries_parse_nonempty_and_cover_msm_kinds(self):
        mod = TestCheckMetrics._load()
        kinds, paths = mod.registered_labels()
        assert {"secp256k1_msm", "secp256k1_q_tables",
                "ed25519_rlc", "other"} <= kinds
        assert {"device", "host", "cache", "drain"} <= paths

    def test_repo_call_sites_all_registered(self):
        mod = TestCheckMetrics._load()
        sites = mod.label_call_sites()
        assert len(sites) >= 10          # the lint actually sees code
        assert mod.run_label_checks() == []

    def test_lint_flags_unregistered_labels(self, tmp_path):
        mod = TestCheckMetrics._load()
        bad = tmp_path / "k.py"
        bad.write_text(
            "def f(hook, rec, d, s, shape):\n"
            "    with hook.dispatch_scope('bogus_kind', shape):\n"
            "        pass\n"
            "    rec.advance(d, s, path='bogus_path')\n"
            "    rec.event(d, s, path='device')\n")
        sites = mod.label_call_sites(tmp_path)
        assert {(s["kind"], s["value"]) for s in sites} == {
            ("dispatch", "bogus_kind"), ("path", "bogus_path"),
            ("path", "device")}
        findings = mod.run_label_checks(root=tmp_path)
        assert len(findings) == 2
        assert any("bogus_kind" in f for f in findings)
        assert any("bogus_path" in f for f in findings)

    def test_health_registries_parse_nonempty(self):
        """Rule 7 extension: the devhealth HEALTH_STATES /
        PROBE_RESULTS registries and the devprof idle-state set
        (busy + idle causes, quarantine included) parse out of the
        source."""
        mod = TestCheckMetrics._load()
        states, results = mod.registered_health_labels()
        assert states == {"healthy", "suspect", "quarantined",
                          "probing"}
        assert results == {"ok", "fail"}
        idle = mod.registered_idle_states()
        assert {"busy", "staging", "backpressure", "no_work",
                "drain", "quarantine"} <= idle

    def test_lint_flags_unregistered_health_labels(self, tmp_path):
        """A misspelled literal in transition()/probe_result()/
        advance() splits a metric series silently — the lint must
        flag each, and pass the registered spellings."""
        mod = TestCheckMetrics._load()
        bad = tmp_path / "h.py"
        bad.write_text(
            "def f(health, rec, d, now):\n"
            "    health.transition(d, 'limping')\n"
            "    health.transition(d, 'quarantined')\n"
            "    health.probe_result(d, 'maybe')\n"
            "    rec.advance(d, 'bogus_idle')\n"
            "    rec.advance(d, 'quarantine')\n")
        sites = mod.label_call_sites(tmp_path)
        assert {(s["kind"], s["value"]) for s in sites} == {
            ("health_state", "limping"),
            ("health_state", "quarantined"),
            ("probe_result", "maybe"),
            ("idle_state", "bogus_idle"),
            ("idle_state", "quarantine")}
        findings = mod.run_label_checks(root=tmp_path)
        assert len(findings) == 3
        assert any("limping" in f for f in findings)
        assert any("maybe" in f for f in findings)
        assert any("bogus_idle" in f for f in findings)


class TestBucketConsumerRegistryLint:
    """check_metrics rule 8: histogram bucket layouts and verify-
    consumer labels are CLOSED registries (metrics.BUCKET_SCHEMES /
    sigcache.CONSUMERS shared with libs/latledger.py), linted in both
    directions — call sites against the registry and the ledger's SLO
    targets back against it."""

    def test_registries_parse_nonempty(self):
        mod = TestCheckMetrics._load()
        schemes = mod.registered_bucket_schemes()
        assert {"default", "flush", "serve",
                "verify_latency"} <= schemes
        consumers = mod.registered_consumers()
        assert {"consensus", "blocksync", "light", "lightserve",
                "evidence"} <= consumers
        keys = dict(mod.slo_target_keys())
        assert keys and set(keys) <= consumers
        assert "consensus" in keys

    def test_repo_is_clean_and_sites_seen(self):
        mod = TestCheckMetrics._load()
        sites = mod.consumer_call_sites()
        assert len(sites) >= 5           # the lint actually sees code
        assert {"consensus", "lightserve"} <= {s["value"]
                                               for s in sites}
        assert mod.run_registry_checks() == []

    def test_lint_flags_adhoc_buckets_and_unknown_scheme(self,
                                                         tmp_path):
        mod = TestCheckMetrics._load()
        bad = tmp_path / "m.py"
        bad.write_text(
            "BUCKET_SCHEMES = {'default': (1, 2)}\n"
            "class A:\n"
            "    def __init__(self, reg):\n"
            "        self.a = reg.histogram('x', 'a_seconds', 'H.',\n"
            "                               buckets=(1, 2, 3))\n"
            "        self.b = reg.histogram('x', 'b_ms', 'H.',\n"
            "            buckets=BUCKET_SCHEMES['nope'])\n"
            "        self.c = reg.histogram('x', 'c_seconds', 'H.',\n"
            "            buckets=BUCKET_SCHEMES['default'])\n"
            "        self.d = reg.histogram('x', 'd_bytes', 'H.',\n"
            "                               buckets=(1, 2))\n")
        findings = mod.run_registry_checks(root=tmp_path,
                                           metrics_path=bad)
        assert any("a_seconds" in f and "closed registry" in f
                   for f in findings)
        assert any("'nope'" in f for f in findings)
        # a registered scheme and a non-duration histogram both pass
        assert not any("c_seconds" in f or "d_bytes" in f
                       for f in findings)

    def test_lint_flags_unregistered_consumer(self, tmp_path):
        mod = TestCheckMetrics._load()
        site = tmp_path / "x.py"
        site.write_text(
            "def f(sigcache, latledger):\n"
            "    with sigcache.consumer('mystery'):\n"
            "        latledger.submit(1, consumer='consensus')\n")
        findings = mod.run_registry_checks(root=tmp_path)
        assert any("'mystery'" in f for f in findings)
        assert not any("'consensus'" in f for f in findings)

    def test_lint_flags_slo_target_outside_registry(self, tmp_path):
        mod = TestCheckMetrics._load()
        lat = tmp_path / "lat.py"
        lat.write_text("DEFAULT_SLO_TARGETS = {'consensus': 0.05,\n"
                       "                       'ghost': 0.1}\n")
        findings = mod.run_registry_checks(root=tmp_path,
                                           latledger_path=lat)
        assert any("'ghost'" in f for f in findings)
        assert not any("'consensus'" in f for f in findings)


class TestLaneRegistryLint:
    """check_metrics rule 9: sigcache.LANES is the closed QoS
    lane-priority registry crypto/sched.py dispatches by — it must
    cover CONSUMERS exactly (both directions) and every literal
    lane= kwarg in the tree must name a registered lane."""

    def test_registry_parses_and_orders_lanes(self):
        mod = TestCheckMetrics._load()
        lanes = mod.registered_lanes()
        assert set(lanes) == mod.registered_consumers()
        assert lanes["consensus"] == 0 and lanes["probe"] == 0
        assert lanes["consensus"] < lanes["evidence"] \
            < lanes["light"] < lanes["blocksync"] < lanes["crypto"]
        assert lanes["light"] == lanes["lightserve"]

    def test_repo_is_clean(self):
        mod = TestCheckMetrics._load()
        assert mod.run_lane_checks() == []
        # every repo call site forwards a runtime-validated variable
        # (the SCHED_LANE knobs, coalescer claimant lanes) — literal
        # labels, when they appear, are linted by the tmp-tree test
        assert isinstance(mod.lane_call_sites(), list)

    def test_lint_flags_lane_registry_drift(self, tmp_path):
        mod = TestCheckMetrics._load()
        sig = tmp_path / "sigcache.py"
        sig.write_text(
            "CONSUMERS = frozenset({'consensus', 'blocksync'})\n"
            "LANES = {'consensus': 0, 'ghostlane': 7}\n")
        site = tmp_path / "x.py"
        site.write_text(
            "def f(pipe):\n"
            "    pipe.submit([], subsystem='blocksync',"
            " lane='mystery')\n"
            "    pipe.submit([], subsystem='blocksync',"
            " lane='consensus')\n")
        findings = mod.run_lane_checks(root=tmp_path,
                                       sigcache_path=sig)
        assert any("'blocksync'" in f and "no entry" in f
                   for f in findings)
        assert any("'ghostlane'" in f and "not a registered"
                   in f for f in findings)
        assert any("'mystery'" in f for f in findings)
        assert not any("lane label 'consensus'" in f
                       for f in findings)

    def test_lint_flags_missing_registry(self, tmp_path):
        mod = TestCheckMetrics._load()
        sig = tmp_path / "sigcache.py"
        sig.write_text("CONSUMERS = frozenset({'consensus'})\n")
        findings = mod.run_lane_checks(root=tmp_path,
                                       sigcache_path=sig)
        assert findings and "LANES not found" in findings[0]


class TestRecordKindLint:
    """check_metrics rule 10: telspool.RECORD_KINDS is the closed
    spool-record vocabulary the fleet collector routes by — every
    literal kind handed to _write_record must be registered."""

    def test_registry_parses(self):
        mod = TestCheckMetrics._load()
        kinds = mod.registered_record_kinds()
        assert {"meta", "clock", "flightrec", "tracetl", "devprof",
                "latledger", "metrics"} <= kinds

    def test_repo_is_clean(self):
        mod = TestCheckMetrics._load()
        assert mod.run_record_kind_checks() == []
        # the writer's flush path spools every layer by literal kind
        sites = mod.record_kind_call_sites()
        assert {s["value"] for s in sites} >= {"clock", "tracetl"}

    def test_lint_flags_unregistered_kind(self, tmp_path):
        mod = TestCheckMetrics._load()
        reg = tmp_path / "telspool.py"
        reg.write_text("RECORD_KINDS = ('meta', 'clock')\n")
        site = tmp_path / "x.py"
        site.write_text(
            "def f(w):\n"
            "    w._write_record('clock', {})\n"
            "    w._write_record('mystery', {})\n")
        findings = mod.run_record_kind_checks(root=tmp_path,
                                              telspool_path=reg)
        assert any("'mystery'" in f for f in findings)
        assert not any("'clock'" in f for f in findings)

    def test_lint_flags_missing_registry(self, tmp_path):
        mod = TestCheckMetrics._load()
        reg = tmp_path / "telspool.py"
        reg.write_text("OTHER = 1\n")
        findings = mod.run_record_kind_checks(root=tmp_path,
                                              telspool_path=reg)
        assert findings and "RECORD_KINDS not found" in findings[0]


class TestPerfGate:
    """scripts/perf_gate.py: the bench-trajectory regression gate runs
    as a tier-1 test so a perf cliff fails CI before a round lands."""

    @staticmethod
    def _load():
        import importlib.util
        import pathlib
        path = pathlib.Path(__file__).resolve().parent.parent / \
            "scripts" / "perf_gate.py"
        spec = importlib.util.spec_from_file_location("perf_gate", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    @staticmethod
    def _write(dirpath, name, value, extra=None):
        import json
        (dirpath / name).write_text(json.dumps(
            {"n": 1, "rc": 0,
             "parsed": {"metric": "sigs_per_sec", "value": value,
                        "unit": "sigs/s", "extra": extra or {}}}))

    def test_gate_flags_regression_and_direction(self):
        mod = self._load()
        history = [{"headline": 100.0, "chaos_recovery_seconds": 10.0}
                   for _ in range(3)]
        rows = mod.gate({"headline": 80.0,
                         "chaos_recovery_seconds": 20.0,
                         "brand_new_metric": 5.0},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        # higher-is-better fell 20% > 15% tolerance
        assert by["headline"]["status"] == "regressed"
        # lower-is-better ROSE — also a regression
        assert by["chaos_recovery_seconds"]["status"] == "regressed"
        # a metric with no history never blocks the round adding it
        assert by["brand_new_metric"]["status"] == "skipped"
        ok = mod.gate({"headline": 90.0}, history, tolerance=0.15,
                      last_n=3, min_points=2)
        assert ok[0]["status"] == "ok"      # -10% inside tolerance

    def test_median_window_absorbs_one_outlier(self):
        mod = self._load()
        history = [{"headline": v} for v in
                   (100.0, 5.0, 100.0, 100.0)]     # one bad round
        rows = mod.gate({"headline": 95.0}, history,
                        tolerance=0.15, last_n=3, min_points=2)
        assert rows[0]["status"] == "ok"
        assert rows[0]["baseline"] == 100.0        # median, not mean

    def test_current_record_cli(self, tmp_path):
        mod = self._load()
        for i, v in enumerate((100.0, 102.0, 98.0), start=1):
            self._write(tmp_path, f"BENCH_r0{i}.json", v,
                        extra={"blocksync_blocks_per_sec": 50.0,
                               "rlc_batch": 131071})
        bad = tmp_path / "BENCH_live.json"
        self._write(tmp_path, "BENCH_live.json", 50.0)
        assert mod.main(["--root", str(tmp_path),
                         "--current", str(bad)]) == 1
        good = tmp_path / "BENCH_good.json"
        self._write(tmp_path, "BENCH_good.json", 99.0)
        assert mod.main(["--root", str(tmp_path),
                         "--current", str(good), "--json"]) == 0
        # config numerics (rlc_batch) never gate
        traj = mod.trajectory(str(tmp_path))
        assert all("rlc_batch" not in m for _, m in traj)

    def test_verdict_cache_extras_gate_direction(self, tmp_path):
        """The sigcache extras: verdict_cache_hit_rate gates
        higher-is-better (a hit-rate collapse means commits started
        re-verifying), commit_reverify_sigs_per_sec gates as a normal
        rate, and critical_path_device_share never gates at all — the
        cache removes device dispatches from the critical path by
        design, so its fall is the feature, not a regression."""
        mod = self._load()
        assert "verdict_cache_hit_rate" not in mod.LOWER_IS_BETTER
        history = [{"headline": 100.0,
                    "verdict_cache_hit_rate": 0.8,
                    "commit_reverify_sigs_per_sec": 400_000.0}
                   for _ in range(3)]
        rows = mod.gate({"headline": 100.0,
                         "verdict_cache_hit_rate": 0.1,
                         "commit_reverify_sigs_per_sec": 100_000.0},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        assert by["verdict_cache_hit_rate"]["status"] == "regressed"
        assert by["commit_reverify_sigs_per_sec"]["status"] == \
            "regressed"
        # device share is filtered out at record-load time
        for i, share in enumerate((0.6, 0.55, 0.2), start=1):
            self._write(tmp_path, f"BENCH_r0{i}.json", 100.0,
                        extra={"critical_path_device_share": share,
                               "verdict_cache_hit_rate": 0.8})
        traj = mod.trajectory(str(tmp_path))
        assert all("critical_path_device_share" not in m
                   for _, m in traj)
        assert all(m["verdict_cache_hit_rate"] == 0.8 for _, m in traj)
        assert mod.main(["--root", str(tmp_path), "--check-only"]) == 0

    def test_devprof_extras_gate_direction(self, tmp_path):
        """The devprof extras: device_occupancy_fraction gates
        higher-is-better (chips going idle means the feed path
        regressed); compile_seconds_total and host_bound_fraction are
        diagnostics — SKIPped at load time, never gated (compile
        seconds flap with persistent-cache warmth)."""
        mod = self._load()
        assert "device_occupancy_fraction" not in mod.LOWER_IS_BETTER
        assert "device_occupancy_fraction" not in mod.SKIP
        assert "compile_seconds_total" in mod.SKIP
        assert "host_bound_fraction" in mod.SKIP
        history = [{"headline": 100.0,
                    "device_occupancy_fraction": 0.6}
                   for _ in range(3)]
        rows = mod.gate({"headline": 100.0,
                         "device_occupancy_fraction": 0.2},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        assert by["device_occupancy_fraction"]["status"] == "regressed"
        ok = mod.gate({"headline": 100.0,
                       "device_occupancy_fraction": 0.58},
                      history, tolerance=0.15, last_n=3, min_points=2)
        assert all(r["status"] == "ok" for r in ok)
        # the skipped diagnostics never reach the gate
        for i, (occ, comp) in enumerate(
                ((0.6, 200.0), (0.62, 1.0), (0.61, 90.0)), start=1):
            self._write(tmp_path, f"BENCH_r0{i}.json", 100.0,
                        extra={"device_occupancy_fraction": occ,
                               "compile_seconds_total": comp,
                               "host_bound_fraction": 0.1 * i})
        traj = mod.trajectory(str(tmp_path))
        assert all("compile_seconds_total" not in m for _, m in traj)
        assert all("host_bound_fraction" not in m for _, m in traj)
        assert all("device_occupancy_fraction" in m for _, m in traj)
        assert mod.main(["--root", str(tmp_path), "--check-only"]) == 0

    def test_flap_recovery_gates_lower_is_better(self):
        """chaos_flap_recovery_seconds (bench_chaos: quarantine-entry
        to probe-pass wall time on the flapped chip) gates
        lower-is-better — recovery getting SLOWER is the regression."""
        mod = self._load()
        assert "chaos_flap_recovery_seconds" in mod.LOWER_IS_BETTER
        history = [{"headline": 100.0,
                    "chaos_flap_recovery_seconds": 0.8}
                   for _ in range(3)]
        rows = mod.gate({"headline": 100.0,
                         "chaos_flap_recovery_seconds": 1.5},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        assert by["chaos_flap_recovery_seconds"]["status"] == \
            "regressed"
        ok = mod.gate({"headline": 100.0,
                       "chaos_flap_recovery_seconds": 0.4},
                      history, tolerance=0.15, last_n=3, min_points=2)
        assert all(r["status"] == "ok" for r in ok)

    def test_lightserve_p99_gates_lower_is_better(self):
        """light_serve_p99_ms (lightserve fleet A/B: ON-arm p99 serve
        latency) gates lower-is-better — the coalescer exists to cut
        the tail, so the tail growing is the regression; the
        clients/s companion gates in the default higher-is-better
        direction."""
        mod = self._load()
        assert "light_serve_p99_ms" in mod.LOWER_IS_BETTER
        assert "light_clients_served_per_sec" not in mod.LOWER_IS_BETTER
        assert "light_clients_served_per_sec" not in mod.SKIP
        history = [{"headline": 100.0,
                    "light_serve_p99_ms": 60.0,
                    "light_clients_served_per_sec": 400.0}
                   for _ in range(3)]
        rows = mod.gate({"headline": 100.0,
                         "light_serve_p99_ms": 95.0,
                         "light_clients_served_per_sec": 400.0},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        assert by["light_serve_p99_ms"]["status"] == "regressed"
        assert by["light_clients_served_per_sec"]["status"] == "ok"
        ok = mod.gate({"headline": 100.0,
                       "light_serve_p99_ms": 40.0,
                       "light_clients_served_per_sec": 420.0},
                      history, tolerance=0.15, last_n=3, min_points=2)
        assert all(r["status"] == "ok" for r in ok)
        rows = mod.gate({"headline": 100.0,
                         "light_serve_p99_ms": 60.0,
                         "light_clients_served_per_sec": 100.0},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        assert by["light_clients_served_per_sec"]["status"] == \
            "regressed"

    def test_verify_latency_p99_gates_lower_is_better(self):
        """vote_verify_p99_ms / bulk_verify_p99_ms (latledger
        contention A/B) gate lower-is-better: the ledger exists to
        keep the consensus tail short while bulk tenants share the
        pipeline, so either p99 rising is the regression."""
        mod = self._load()
        assert "vote_verify_p99_ms" in mod.LOWER_IS_BETTER
        assert "bulk_verify_p99_ms" in mod.LOWER_IS_BETTER
        assert "vote_verify_p99_ms" not in mod.SKIP
        assert "bulk_verify_p99_ms" not in mod.SKIP
        history = [{"headline": 100.0, "vote_verify_p99_ms": 50.0,
                    "bulk_verify_p99_ms": 400.0} for _ in range(3)]
        rows = mod.gate({"headline": 100.0,
                         "vote_verify_p99_ms": 80.0,
                         "bulk_verify_p99_ms": 300.0},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        assert by["vote_verify_p99_ms"]["status"] == "regressed"
        assert by["bulk_verify_p99_ms"]["status"] == "ok"  # fell = ok
        ok = mod.gate({"headline": 100.0,
                       "vote_verify_p99_ms": 45.0,
                       "bulk_verify_p99_ms": 380.0},
                      history, tolerance=0.15, last_n=3, min_points=2)
        assert all(r["status"] == "ok" for r in ok)

    def test_sched_extras_gate_direction(self, tmp_path):
        """bulk_verify_throughput_ratio (QoS scheduler fairness floor:
        contended bulk throughput over solo) gates in the default
        higher-is-better direction — the scheduler may tax bulk at
        most so far, and that ratio collapsing is the regression.  The
        sched-OFF p99 and raw bulk sigs/s are same-run diagnostics for
        the gated readings, so load_record drops them via SKIP."""
        mod = self._load()
        assert "bulk_verify_throughput_ratio" not in mod.LOWER_IS_BETTER
        assert "bulk_verify_throughput_ratio" not in mod.SKIP
        assert "vote_verify_p99_ms_sched_off" in mod.SKIP
        assert "bulk_verify_sigs_per_s" in mod.SKIP
        self._write(tmp_path, "BENCH_r01.json", 100.0,
                    extra={"bulk_verify_throughput_ratio": 0.95,
                           "vote_verify_p99_ms_sched_off": 300.0,
                           "bulk_verify_sigs_per_s": 5000.0})
        rec = mod.load_record(str(tmp_path / "BENCH_r01.json"))
        assert rec["bulk_verify_throughput_ratio"] == 0.95
        assert "vote_verify_p99_ms_sched_off" not in rec
        assert "bulk_verify_sigs_per_s" not in rec
        history = [dict(rec) for _ in range(3)]
        rows = mod.gate({"headline": 100.0,
                         "bulk_verify_throughput_ratio": 0.60},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        assert by["bulk_verify_throughput_ratio"]["status"] == \
            "regressed"
        ok = mod.gate({"headline": 100.0,
                       "bulk_verify_throughput_ratio": 0.97},
                      history, tolerance=0.15, last_n=3, min_points=2)
        assert all(r["status"] == "ok" for r in ok)

    def test_fleet_extras_gate_direction(self, tmp_path):
        """The fleetobs extras: e2e_fleet_height_coverage gates in the
        default higher-is-better direction (heights losing their
        cross-process flow edges means the in-band trace context or
        the clock-aligned merge broke); the clock-offset spread gates
        lower-is-better (widening means the edge solver degraded
        toward wall-clock anchors); the fleet critical-path device
        share is a reading — SKIPped for the same reason
        critical_path_device_share is."""
        mod = self._load()
        assert "e2e_fleet_height_coverage" not in mod.LOWER_IS_BETTER
        assert "e2e_fleet_height_coverage" not in mod.SKIP
        assert "e2e_fleet_clock_offset_spread_ms" in mod.LOWER_IS_BETTER
        assert "e2e_fleet_critical_path_device_share" in mod.SKIP
        self._write(tmp_path, "BENCH_r01.json", 100.0,
                    extra={"e2e_fleet_height_coverage": 1.0,
                           "e2e_fleet_clock_offset_spread_ms": 2.0,
                           "e2e_fleet_critical_path_device_share": 0.3})
        rec = mod.load_record(str(tmp_path / "BENCH_r01.json"))
        assert rec["e2e_fleet_height_coverage"] == 1.0
        assert "e2e_fleet_critical_path_device_share" not in rec
        history = [dict(rec) for _ in range(3)]
        rows = mod.gate({"headline": 100.0,
                         "e2e_fleet_height_coverage": 0.5,
                         "e2e_fleet_clock_offset_spread_ms": 9.0},
                        history, tolerance=0.15, last_n=3,
                        min_points=2)
        by = {r["metric"]: r for r in rows}
        assert by["e2e_fleet_height_coverage"]["status"] == "regressed"
        assert by["e2e_fleet_clock_offset_spread_ms"]["status"] == \
            "regressed"
        ok = mod.gate({"headline": 100.0,
                       "e2e_fleet_height_coverage": 1.0,
                       "e2e_fleet_clock_offset_spread_ms": 1.5},
                      history, tolerance=0.15, last_n=3, min_points=2)
        assert all(r["status"] == "ok" for r in ok)

    def test_staleness_warning(self, tmp_path):
        """A BENCH_live.json older than the newest committed round
        warns (with the capture's git rev when stamped) but never
        fails the gate; a fresher live capture stays silent."""
        import json as _json
        import os as _os
        mod = self._load()
        self._write(tmp_path, "BENCH_r1.json", 100.0)
        live = tmp_path / "BENCH_live.json"
        live.write_text(_json.dumps(
            {"metric": "x", "value": 100.0, "unit": "s",
             "extra": {"capture_git_rev": "abc1234"}}))
        now = time.time()
        _os.utime(live, (now - 60, now - 60))
        _os.utime(tmp_path / "BENCH_r1.json", (now - 120, now - 120))
        assert mod.staleness_warning(str(tmp_path), str(live)) is None
        _os.utime(tmp_path / "BENCH_r1.json", (now, now))
        warn = mod.staleness_warning(str(tmp_path), str(live))
        assert warn is not None and "stale" in warn
        assert "abc1234" in warn
        # a missing live file warns nothing rather than crashing
        assert mod.staleness_warning(
            str(tmp_path), str(tmp_path / "nope.json")) is None

    def test_usage_errors_exit_2(self, tmp_path):
        import json
        mod = self._load()
        assert mod.main(["--root", str(tmp_path)]) == 2   # no mode
        assert mod.main(["--root", str(tmp_path),
                         "--check-only"]) == 2            # no records
        unparsed = tmp_path / "BENCH_broken.json"
        unparsed.write_text(json.dumps({"rc": 124, "parsed": None}))
        assert mod.main(["--current", str(unparsed)]) == 2


class TestMultichipDryrunBudget:
    """The driver's dryrun_multichip must hold phases 1-4 in WELL
    under half its 1800 s window (MULTICHIP_r05 hit rc=124 when phase
    4 carried a ~3.5-min interpret Pallas compile).  Tier 1 guards the
    COMMITTED timing artifact — total <= 450 s (>= 2x headroom against
    the 900 s half-window) and every phase present; the live timed
    re-run is the slow-tier test below, and the artifact is refreshed
    whenever the dryrun phases change."""

    BUDGET_S = 900.0          # half the driver's 1800 s window
    PHASES = ("phase1_verify_kernel", "phase2_rlc", "phase3_cached_a",
              "phase4_sharded_msm")

    @staticmethod
    def _artifact():
        import json
        import pathlib
        path = pathlib.Path(__file__).resolve().parent.parent / \
            "MULTICHIP_local_timing.json"
        assert path.exists(), (
            "MULTICHIP_local_timing.json missing: run "
            "`python __graft_entry__.py` (or scripts/dryrun_timing.py)"
            " and commit the refreshed timing")
        return json.loads(path.read_text())

    def test_committed_timing_has_2x_headroom(self):
        art = self._artifact()
        assert art["ok"] is True
        timings = art["timings"]
        for phase in self.PHASES:
            assert phase in timings, phase
        assert timings["total"] <= self.BUDGET_S / 2, (
            f"dryrun total {timings['total']}s eats the headroom: "
            f"budget {self.BUDGET_S}s needs total <= "
            f"{self.BUDGET_S / 2}s")
        assert timings["total"] >= sum(
            timings[p] for p in self.PHASES) - 1.0

    def test_per_device_metric_series_lint(self):
        """The mesh dispatcher's per-device series exist, are
        device-labelled, and are OBSERVED outside registration (the
        check_metrics reference lint) — a renamed label or dropped
        .labels() call fails here, not on a dashboard."""
        mod = TestCheckMetrics._load()
        metrics = {(m["subsystem"], m["name"]): m
                   for m in mod.registered_metrics()}
        for want in ("mesh_dispatches",
                     "pipeline_device_inflight_windows",
                     "pipeline_device_drains"):
            assert ("device", want) in metrics, want
        assert mod.run_checks() == []

    @pytest.mark.slow
    def test_live_dryrun_within_budget(self):
        """The honest version: run dryrun_multichip(8) end-to-end and
        time it against the budget (warm persistent compile cache —
        the driver's own steady-state)."""
        import importlib.util
        import pathlib
        import time as _time

        path = pathlib.Path(__file__).resolve().parent.parent / \
            "__graft_entry__.py"
        spec = importlib.util.spec_from_file_location("graft_entry",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        t0 = _time.perf_counter()
        timings = mod.dryrun_multichip(8)
        dt = _time.perf_counter() - t0
        # 2 * BUDGET_S == the driver's 1800 s subprocess window: a cold
        # compile cache pays ~3x the warm-run time (the committed
        # artifact's 2x-headroom guard covers the warm steady state)
        assert dt < 2 * self.BUDGET_S, f"dryrun took {dt:.0f}s"
        assert timings is not None and "total" in timings


class TestCheckConcurrency:
    """scripts/check_concurrency.py — the static half of the
    concurrency sanitizer plane — as a tier-1 gate: the package must
    be clean, and the lint's own view of the rank table must agree
    with the runtime module it guards.  (The per-rule must-trip tests
    on synthetic sources live in tests/test_lockrank.py next to the
    runtime half's.)"""

    @staticmethod
    def _load():
        import importlib.util
        import pathlib
        path = pathlib.Path(__file__).resolve().parent.parent / \
            "scripts" / "check_concurrency.py"
        spec = importlib.util.spec_from_file_location(
            "check_concurrency", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_repo_is_clean(self):
        mod = self._load()
        findings = mod.run_checks()
        assert findings == [], "\n".join(findings)

    def test_rank_table_parses_and_matches_runtime(self):
        from cometbft_tpu.libs import lockrank
        mod = self._load()
        ranks = mod.lock_ranks()
        assert ranks == lockrank.LOCK_RANKS

    def test_scripts_and_tests_only_c1_exempt_dirs(self):
        """The lint walks cometbft_tpu/ by default; tests/ and
        scripts/ may use raw primitives (harness code), but the
        package itself must not — pin the default root."""
        mod = self._load()
        import pathlib
        pkg = pathlib.Path(__file__).resolve().parent.parent / \
            "cometbft_tpu"
        walked = list(mod._iter_files())
        assert walked and all(pkg in p.parents or p == pkg
                              for p in walked)
