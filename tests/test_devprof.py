"""Device-time accounting plane (libs/devprof.py): the mark-advance
exact partition (busy + idle == wall, by construction), idle-cause
attribution through the live VerifyPipeline, the XLA compile-cost
ledger (ops/compile_hook.py), the no-op seam contract, and every
surface — DevprofMetrics over a live /metrics scrape, Perfetto counter
tracks, the devprof RPC route, and /debug/pprof/devprof.
"""

import time
import urllib.error
import urllib.request

import pytest

from cometbft_tpu.crypto import dispatch as vd
from cometbft_tpu.crypto import sigcache
from cometbft_tpu.libs import devprof
from cometbft_tpu.ops import compile_hook


def assert_exact_partition(dev_snapshot):
    """The plane's core invariant: every accounted instant lands in
    exactly one bucket, so busy + idle == wall to float precision."""
    total = dev_snapshot["busy_seconds"] \
        + sum(dev_snapshot["idle_seconds"].values())
    # 5e-6 absorbs the per-bucket 6-decimal rounding of snapshot();
    # the pre-rounding partition is exact by construction
    assert total == pytest.approx(dev_snapshot["wall_seconds"],
                                  abs=5e-6)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture
def seam_recorder():
    """Install a fresh recorder on the process seam; restore after."""
    prev = devprof.recorder()
    rec = devprof.DevprofRecorder()
    devprof.set_recorder(rec)
    yield rec
    devprof.set_recorder(prev)


class TestGapAttribution:
    """Hand-built schedules through DeviceAccount / DevprofRecorder:
    the partition must be exact and each gap must land in exactly the
    cause it was attributed to."""

    def test_schedule_partitions_exactly(self):
        clk = FakeClock()
        rec = devprof.DevprofRecorder(clock=clk)
        rec.attach("0")
        # 0.0-1.0 no_work, 1.0-1.5 busy, 1.5-1.8 staging,
        # 1.8-2.0 busy, 2.0-2.25 backpressure, 2.25-3.0 drain
        for t, state in ((1.0, devprof.IDLE_NO_WORK),
                         (1.5, devprof.BUSY),
                         (1.8, devprof.IDLE_STAGING),
                         (2.0, devprof.BUSY),
                         (2.25, devprof.IDLE_BACKPRESSURE),
                         (3.0, devprof.IDLE_DRAIN)):
            clk.t = t
            rec.advance("0", state)
        d = rec.snapshot()["devices"]["0"]
        assert d["wall_seconds"] == pytest.approx(3.0)
        assert d["busy_seconds"] == pytest.approx(0.7)
        assert d["idle_seconds"] == {
            "staging": pytest.approx(0.3),
            "backpressure": pytest.approx(0.25),
            "no_work": pytest.approx(1.0),
            "drain": pytest.approx(0.75),
            "quarantine": pytest.approx(0.0),
            "sched_hold": pytest.approx(0.0)}
        assert d["dispatches"] == 2
        assert d["occupancy"] == pytest.approx(0.7 / 3.0, abs=1e-6)
        assert_exact_partition(d)

    def test_busy_by_path_splits_device_and_host(self):
        clk = FakeClock()
        rec = devprof.DevprofRecorder(clock=clk)
        rec.attach("0")
        clk.t = 1.0
        rec.advance("0", devprof.BUSY, path="device")
        clk.t = 1.25
        rec.advance("0", devprof.BUSY, path="host")
        d = rec.snapshot()["devices"]["0"]
        assert d["busy_by_path"] == {"device": pytest.approx(1.0),
                                     "host": pytest.approx(0.25)}
        assert d["busy_seconds"] == pytest.approx(1.25)
        assert_exact_partition(d)

    def test_backwards_clock_reanchors_without_negative_time(self):
        clk = FakeClock(5.0)
        rec = devprof.DevprofRecorder(clock=clk)
        rec.attach("0")
        clk.t = 4.0                       # clock went backwards
        assert rec.advance("0", devprof.BUSY) == 0.0
        clk.t = 4.5
        assert rec.advance("0", devprof.BUSY) == pytest.approx(0.5)
        d = rec.snapshot()["devices"]["0"]
        assert d["busy_seconds"] == pytest.approx(0.5)

    def test_per_device_accounts_are_independent(self):
        clk = FakeClock()
        rec = devprof.DevprofRecorder(clock=clk)
        clk.t = 1.0
        rec.advance("0", devprof.BUSY)        # auto-attach at t=1.0
        clk.t = 2.0
        rec.advance("0", devprof.IDLE_NO_WORK)
        rec.advance("1", devprof.IDLE_STAGING)  # attach at t=2.0
        clk.t = 3.0
        rec.advance("1", devprof.IDLE_STAGING)
        devs = rec.snapshot()["devices"]
        # each wall window opens at the device's OWN attach instant
        assert devs["0"]["wall_seconds"] == pytest.approx(1.0)
        assert devs["0"]["idle_seconds"]["no_work"] == pytest.approx(1.0)
        assert devs["1"]["wall_seconds"] == pytest.approx(1.0)
        assert devs["1"]["idle_seconds"]["staging"] == pytest.approx(1.0)
        for d in devs.values():
            assert_exact_partition(d)

    def test_occupancy_summary_aggregates(self):
        clk = FakeClock()
        rec = devprof.DevprofRecorder(clock=clk)
        rec.attach("0")
        rec.attach("1")
        clk.t = 1.0
        rec.advance("0", devprof.BUSY)
        rec.advance("1", devprof.IDLE_STAGING)
        occ = devprof.occupancy_summary(rec.snapshot())
        assert occ["device_occupancy_fraction"] == pytest.approx(0.5)
        assert occ["host_bound_fraction"] == pytest.approx(0.5)
        assert occ["idle_cause_seconds"]["staging"] == pytest.approx(1.0)
        assert occ["busy_seconds"] == pytest.approx(1.0)
        assert occ["wall_seconds"] == pytest.approx(2.0)

    def test_counter_samples_dedupe_and_bound(self):
        clk = FakeClock()
        rec = devprof.DevprofRecorder(sample_capacity=4, clock=clk)
        for i in range(10):
            clk.t = float(i)
            rec.counter("queue_depth", i % 2)   # level flips each step
        samples = rec.counter_samples()
        assert len(samples) == 4                # ring-bounded
        snap = rec.snapshot()["samples"]
        assert snap["recorded"] == 10 and snap["dropped"] == 6
        clk.t = 100.0
        rec.counter("queue_depth", samples[-1][2])   # same level
        assert rec.snapshot()["samples"]["recorded"] == 10  # deduped

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            devprof.DevprofRecorder(sample_capacity=0)
        with pytest.raises(ValueError):
            devprof.DevprofRecorder(ledger_capacity=0)


class TestNoopSeam:
    """The flightrec cost contract: nothing installed, nothing paid."""

    def test_global_seam_noop_when_unset(self):
        prev = devprof.recorder()
        devprof.set_recorder(None)
        try:
            assert devprof.recorder() is None
            # the pipeline's hot-path pattern must stay a no-op
            rec = devprof.recorder()
            if rec is not None:         # pragma: no cover
                rec.advance("0", devprof.BUSY)
        finally:
            devprof.set_recorder(prev)

    def test_dispatch_scope_is_shared_null_without_ledger(self):
        prev = compile_hook.ledger()
        compile_hook.uninstall()
        try:
            a = compile_hook.dispatch_scope("k", (4, 10))
            b = compile_hook.dispatch_scope("other", None)
            assert a is b               # one shared null context
            with a:
                pass                    # and it is a working CM
        finally:
            if prev is not None:
                compile_hook.install(prev)

    def test_pipeline_runs_clean_without_recorder(self):
        prev = devprof.recorder()
        devprof.set_recorder(None)
        try:
            with vd.VerifyPipeline(
                    depth=2,
                    dispatch_fn=lambda w: (True,
                                           [True] * len(w.items))) as p:
                h = p.submit([(b"pk", b"m", b"s")] * 4,
                             device_threshold=2)
                assert h.result(timeout=30)[0] is True
        finally:
            devprof.set_recorder(prev)


class TestPipelineAccounting:
    """The live VerifyPipeline drives the accounts: causes stay inside
    the taxonomy and the partition stays exact under real threads."""

    def _run(self, rec, devices=None, windows=4):
        prev_cache = sigcache._enabled_override
        sigcache.set_enabled(False)     # keep every window off the
        try:                            # cache-resolve path
            pipe = vd.VerifyPipeline(
                depth=4,
                dispatch_fn=lambda w: (True, [True] * len(w.items)),
                devices=devices, name="devprof-test")
            with pipe:
                handles = [
                    pipe.submit([(b"pk%d-%d" % (w, j), b"m", b"s")
                                 for j in range(6)],
                                device_threshold=2)
                    for w in range(windows)]
                for h in handles:
                    assert h.result(timeout=30)[0] is True
                time.sleep(0.1)         # let an idle gap accrue
        finally:
            sigcache.set_enabled(prev_cache)

    def test_single_device_partition_and_taxonomy(self, seam_recorder):
        self._run(seam_recorder)
        snap = seam_recorder.snapshot()
        assert set(snap["devices"]) == {"0"}
        d = snap["devices"]["0"]
        assert d["dispatches"] == 4
        assert d["busy_seconds"] > 0.0
        assert set(d["idle_seconds"]) == set(devprof.IDLE_CAUSES)
        assert d["idle_seconds"]["no_work"] > 0.0   # the sleep at end
        assert_exact_partition(d)

    def test_mesh_devices_get_separate_accounts(self, seam_recorder):
        self._run(seam_recorder, devices=["devA", "devB"], windows=6)
        snap = seam_recorder.snapshot()
        assert set(snap["devices"]) == {"0", "1"}
        assert sum(d["dispatches"]
                   for d in snap["devices"].values()) == 6
        for d in snap["devices"].values():
            assert set(d["idle_seconds"]) == set(devprof.IDLE_CAUSES)
            assert_exact_partition(d)

    def test_fault_attributes_drain_idle(self, seam_recorder):
        boom = {"armed": True}

        def flaky(win):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("injected device failure")
            return (True, [True] * len(win.items))

        prev_cache = sigcache._enabled_override
        sigcache.set_enabled(False)
        try:
            with vd.VerifyPipeline(depth=3, dispatch_fn=flaky) as pipe:
                hs = [pipe.submit([(b"fk%d-%d" % (w, j), b"m", b"s")
                                   for j in range(4)],
                                  device_threshold=1)
                      for w in range(3)]
                for h in hs:
                    h.result(timeout=60)
        finally:
            sigcache.set_enabled(prev_cache)
        d = seam_recorder.snapshot()["devices"]["0"]
        # the faulted window's in-flight slice lands in drain (and the
        # recovery windows resolved through host/drain paths, never
        # counted busy-by-device)
        assert d["idle_seconds"]["drain"] > 0.0
        assert_exact_partition(d)

    def test_queue_depth_counter_tracks_recorded(self, seam_recorder):
        self._run(seam_recorder)
        tracks = {t for _, t, _ in seam_recorder.counter_samples()}
        assert "occupancy_pct/dev0" in tracks
        assert "pipeline_queue_depth" in tracks


class TestCompileLedger:
    def test_first_vs_recompile_classification(self):
        rec = devprof.DevprofRecorder()
        rec.compile_event("rlc", (4, 10), 1.5)
        rec.compile_event("rlc", (4, 10), 0.5)      # same key
        rec.compile_event("rlc", (8, 10), 0.25)     # new shape
        rec.compile_event("persig", None, 0.125)
        c = rec.snapshot()["compile"]
        assert c["count"] == 4
        assert c["seconds_total"] == pytest.approx(2.375)
        assert c["first_seconds"] == pytest.approx(1.875)
        assert c["by_kind"]["rlc"] == {
            "count": 3, "seconds": pytest.approx(2.25),
            "first": 2, "recompile": 1}
        phases = [e["phase"] for e in c["entries"]]
        assert phases == ["first", "recompile", "first", "first"]

    def test_non_backend_phases_add_seconds_only(self):
        rec = devprof.DevprofRecorder()
        rec.compile_event("rlc", (4,), 0.5, backend=False)
        c = rec.snapshot()["compile"]
        assert c["seconds_total"] == pytest.approx(0.5)
        assert c["count"] == 0 and c["entries"] == []

    def test_ledger_ring_bounds_entries(self):
        rec = devprof.DevprofRecorder(ledger_capacity=2)
        for i in range(5):
            rec.compile_event("k", (i,), 0.1)
        c = rec.snapshot()["compile"]
        assert c["count"] == 5 and len(c["entries"]) == 2
        assert [e["shape"] for e in c["entries"]] == [[3], [4]]

    def test_jit_compiles_attributed_through_scope(self):
        """Real jax.jit compiles land in the ledger under the
        dispatch_scope label; a shape change recompiles as 'first' for
        its new key.  Tiny lambdas — no heavy kernel compiles here."""
        jax = pytest.importorskip("jax")
        jnp = jax.numpy
        prev = compile_hook.ledger()
        rec = devprof.DevprofRecorder()
        compile_hook.install(rec)
        try:
            fn = jax.jit(lambda x: x + 1)
            with compile_hook.dispatch_scope("devprof_test", (3,)):
                fn(jnp.zeros(3, jnp.int32)).block_until_ready()
            with compile_hook.dispatch_scope("devprof_test", (5,)):
                fn(jnp.zeros(5, jnp.int32)).block_until_ready()
        finally:
            if prev is not None:
                compile_hook.install(prev)
            else:
                compile_hook.uninstall()
        c = rec.snapshot()["compile"]
        by = c["by_kind"].get("devprof_test")
        assert by is not None and by["count"] >= 2
        assert by["first"] >= 2         # distinct shapes = distinct keys
        assert c["seconds_total"] > 0.0

    def test_unscoped_compiles_land_under_other(self):
        jax = pytest.importorskip("jax")
        jnp = jax.numpy
        prev = compile_hook.ledger()
        rec = devprof.DevprofRecorder()
        compile_hook.install(rec)
        try:
            jax.jit(lambda x: x * 2)(
                jnp.zeros(7, jnp.int32)).block_until_ready()
        finally:
            if prev is not None:
                compile_hook.install(prev)
            else:
                compile_hook.uninstall()
        assert "other" in rec.snapshot()["compile"]["by_kind"]

    def test_dispatch_scope_counts_programs_that_ran(self):
        """The program account fills on every labelled dispatch, warm
        compile cache or not: it says which programs RAN."""
        prev = compile_hook.ledger()
        rec = devprof.DevprofRecorder()
        compile_hook.install(rec)
        try:
            for _ in range(3):
                with compile_hook.dispatch_scope("devprof_test", (8, 16)):
                    pass
            with compile_hook.dispatch_scope("devprof_test", (8,)):
                pass
        finally:
            if prev is not None:
                compile_hook.install(prev)
            else:
                compile_hook.uninstall()
        assert rec.snapshot()["programs"] == [
            {"kind": "devprof_test", "shape": [8], "dispatches": 1},
            {"kind": "devprof_test", "shape": [8, 16], "dispatches": 3}]

    @pytest.mark.parametrize("env_dir", ["/somewhere/else", None])
    def test_compile_cache_dir(self, env_dir, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and the
        helper touches nothing.  Unset: <checkout>/.jax_cache."""
        import os

        jax = pytest.importorskip("jax")
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        if env_dir:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            assert compile_hook.ensure_compile_cache() == env_dir
            assert updates == []
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            checkout = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            want = os.path.join(checkout, ".jax_cache")
            assert compile_hook.ensure_compile_cache() == want
            assert updates == [("jax_compilation_cache_dir", want)]


class TestMetricsSurface:
    def test_live_metrics_scrape_has_devprof_series(self):
        """A live pipeline run under DevprofMetrics, scraped over a
        real /metrics HTTP server: per-device busy/idle counters and
        the occupancy gauge must be present and coherent."""
        from cometbft_tpu.libs import metrics as libmetrics
        from cometbft_tpu.libs.metrics import (DevprofMetrics,
                                               MetricsServer, Registry)

        reg = Registry("cometbft_tpu")
        prev_dm = libmetrics.devprof_metrics()
        prev_rec = devprof.recorder()
        libmetrics.set_devprof_metrics(DevprofMetrics(reg))
        rec = devprof.DevprofRecorder()
        devprof.set_recorder(rec)
        rec.compile_event("scrape_test", (4,), 0.25)
        srv = MetricsServer(reg, "127.0.0.1:0")
        srv.start()
        prev_cache = sigcache._enabled_override
        sigcache.set_enabled(False)
        try:
            with vd.VerifyPipeline(
                    depth=2,
                    dispatch_fn=lambda w: (True,
                                           [True] * len(w.items))) as p:
                for w in range(3):
                    p.submit([(b"mk%d-%d" % (w, j), b"m", b"s")
                              for j in range(4)],
                             device_threshold=2).result(timeout=30)
                time.sleep(0.1)
            with urllib.request.urlopen(
                    f"http://{srv.bound_addr}/metrics",
                    timeout=10) as resp:
                text = resp.read().decode()
        finally:
            sigcache.set_enabled(prev_cache)
            srv.stop()
            devprof.set_recorder(prev_rec)
            libmetrics.set_devprof_metrics(prev_dm)

        def value(needle):
            hits = [ln for ln in text.splitlines()
                    if ln.startswith(needle)]
            assert hits, needle
            return float(hits[0].split()[-1])

        busy = value('cometbft_tpu_devprof_busy_seconds_total'
                     '{device="0"}')
        assert busy > 0.0
        idle = sum(value('cometbft_tpu_devprof_idle_seconds_total'
                         f'{{device="0",cause="{c}"}}')
                   for c in devprof.IDLE_CAUSES
                   if any(f'cause="{c}"' in ln
                          for ln in text.splitlines()))
        assert idle > 0.0
        occ = value('cometbft_tpu_devprof_occupancy_ratio'
                    '{device="0"}')
        assert 0.0 < occ <= 1.0
        assert value('cometbft_tpu_devprof_compile_seconds_total') \
            == pytest.approx(0.25)
        assert value('cometbft_tpu_devprof_compile_count'
                     '{kind="scrape_test"}') == 1.0


class TestEndpoints:
    def _populated(self):
        clk = FakeClock()
        rec = devprof.DevprofRecorder(clock=clk)
        rec.attach("0")
        clk.t = 1.0
        rec.advance("0", devprof.BUSY)
        clk.t = 1.5
        rec.advance("0", devprof.IDLE_NO_WORK)
        rec.compile_event("ep_test", (2,), 0.125)
        return rec

    def test_rpc_devprof_route(self):
        from cometbft_tpu.rpc.core import Environment, ROUTES, RPCError

        rec = self._populated()

        class _CS:
            devprof = rec

        assert ROUTES["devprof"] == "devprof_handler"
        out = Environment(consensus_state=_CS()).devprof_handler()
        assert out["devices"]["0"]["busy_seconds"] == pytest.approx(1.0)
        assert out["compile"]["count"] == 1
        assert out["samples"]["recorded"] >= 1

        class _Bare:
            devprof = None

        prev = devprof.recorder()
        devprof.set_recorder(None)
        try:
            with pytest.raises(RPCError):
                Environment(consensus_state=_Bare()).devprof_handler()
            # seam fallback: the process-wide recorder serves the route
            devprof.set_recorder(rec)
            out = Environment(consensus_state=_Bare()).devprof_handler()
            assert out["compile"]["count"] == 1
        finally:
            devprof.set_recorder(prev)

    def test_pprof_devprof_endpoint(self):
        from cometbft_tpu.libs.pprof import PprofServer

        prev = devprof.recorder()
        devprof.set_recorder(self._populated())
        srv = PprofServer("127.0.0.1:0")
        srv.start()
        try:
            with urllib.request.urlopen(
                    f"http://{srv.bound_addr}/debug/pprof/devprof",
                    timeout=5) as resp:
                body = resp.read().decode()
            assert "devprof: 1 device(s), 1 compile(s)" in body
            assert "dev0: occupancy 66.7%" in body
            assert "compile ep_test: 1 (1 first)" in body
            # uninstalled -> 404, not a crash
            devprof.set_recorder(None)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://{srv.bound_addr}/debug/pprof/devprof",
                    timeout=5)
            assert ei.value.code == 404
        finally:
            srv.stop()
            devprof.set_recorder(prev)


class TestPerfettoCounters:
    def test_export_carries_counter_tracks(self):
        from cometbft_tpu.libs import tracetl

        clk = FakeClock()
        rec = devprof.DevprofRecorder(clock=clk)
        rec.attach("0")
        clk.t = 0.5
        rec.advance("0", devprof.BUSY)
        rec.counter("pipeline_queue_depth", 3)
        tl = tracetl.Timeline(node="n0", clock=clk)
        tl.instant("consensus", "proposal", t=0.1, height=1)
        trace = tracetl.perfetto_trace({"n0": tl},
                                       counters=rec.counter_samples())
        cs = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
        assert cs, "no counter events in export"
        names = {e["name"] for e in cs}
        assert "occupancy_pct/dev0" in names
        assert "pipeline_queue_depth" in names
        # all counters under the dedicated devprof pseudo-process
        devpid = {e["pid"] for e in cs}
        assert len(devpid) == 1
        procs = [e for e in trace["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"]
        assert any(e["args"]["name"] == "devprof"
                   and e["pid"] in devpid for e in procs)
        assert trace["metadata"]["counters"] == len(cs)
        for e in cs:
            assert e["args"]["value"] is not None
            assert e["ts"] >= 0.0       # counter ts joined t0 min

    def test_trace_session_export_includes_counters(self, seam_recorder):
        from cometbft_tpu.simnet.tracing import TraceSession

        class Slot:
            timeline = None

        class FakeNode:
            name = "dv0"
            consensus_state = Slot()
            consensus_reactor = None
            blocksync_reactor = None
            flight_recorder = None

        sess = TraceSession().install([FakeNode()])
        try:
            # install() found the fixture's seam recorder and reused it
            assert sess.devprof_recorder is seam_recorder
            seam_recorder.counter("pipeline_queue_depth", 2)
            trace = sess.export()
        finally:
            sess.uninstall()
        assert devprof.recorder() is seam_recorder   # not clobbered
        assert any(e.get("ph") == "C"
                   for e in trace["traceEvents"])

    def test_trace_session_installs_own_recorder_when_none(self):
        from cometbft_tpu.simnet.tracing import TraceSession

        class FakeNode:
            name = "dv1"
            consensus_state = None
            consensus_reactor = None
            blocksync_reactor = None
            flight_recorder = None

        prev = devprof.recorder()
        devprof.set_recorder(None)
        try:
            sess = TraceSession().install([FakeNode()])
            try:
                assert devprof.recorder() is sess.devprof_recorder
                assert sess.devprof_recorder is not None
            finally:
                sess.uninstall()
            assert devprof.recorder() is None        # restored
        finally:
            devprof.set_recorder(prev)
