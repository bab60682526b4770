"""Mesh-sharded verify dispatch (crypto/mesh.py + the VerifyPipeline
devices=... mode) on the 8-virtual-device CPU mesh from conftest:
sharded accept parity, reject localization, cached-A on a placed
device, window round-robin ordering, and per-device drain fault
isolation.

RLC-bearing tests stick to 2 devices: each extra device placement is
an extra XLA compile of the whole-batch RLC program on the CPU tier,
and 2 devices already exercise the placement/commitment machinery the
8-device run would.
"""

import threading
import time

import jax
import numpy as np
import pytest

from cometbft_tpu.crypto import batch as cb
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import mesh
from cometbft_tpu.crypto import dispatch as vd
from cometbft_tpu.crypto.ed25519 import PubKey
from cometbft_tpu.ops import sharding
from tests.test_dispatch import make_items, serial_verdicts


@pytest.fixture(scope="module")
def sigs16():
    """One deterministic 16-signature fixture (index 7 corrupted)
    shared by every RLC-bearing test in the module: 16 sigs over 2
    devices = the width-8 fused / width-16 cached-A RLC programs the
    multichip dryrun (__graft_entry__) keeps in the persistent
    compile cache, so tier 1 never pays a fresh RLC compile shape."""
    items = make_items(16, seed=42, bad=(7,))
    pks = [i[0] for i in items]
    msgs = [i[1] for i in items]
    sigs = [i[2] for i in items]
    parsed = ed.parse_and_hash(pks, msgs, sigs)
    return items, pks, parsed


class TestSplitSpans:
    def test_covers_contiguously(self):
        for n in (1, 2, 7, 8, 9, 255, 256, 1000):
            for ndev in (1, 2, 3, 8):
                spans = mesh.split_spans(n, ndev)
                assert spans[0][0] == 0 and spans[-1][1] == n
                for (a, b), (c, d) in zip(spans, spans[1:]):
                    assert b == c
                assert all(b > a for a, b in spans)
                assert len(spans) == min(ndev, n)
                sizes = [b - a for a, b in spans]
                assert max(sizes) - min(sizes) <= 1


class TestMeshDeviceList:
    def test_opt_in_by_default(self, monkeypatch):
        monkeypatch.delenv("COMETBFT_TPU_MESH_DEVICES", raising=False)
        assert sharding.mesh_device_list(None) is None

    def test_env_zero_means_all(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_MESH_DEVICES", "0")
        devs = sharding.mesh_device_list(None)
        assert devs is not None and len(devs) == 8

    def test_explicit_k_clamps(self, monkeypatch):
        monkeypatch.delenv("COMETBFT_TPU_MESH_DEVICES", raising=False)
        assert len(sharding.mesh_device_list(3)) == 3
        assert len(sharding.mesh_device_list(64)) == 8
        assert sharding.mesh_device_list(1) is None


class TestAutoBucket:
    def test_divisible_by_mesh(self):
        for n in (3, 16, 100, 1000):
            b = sharding.auto_bucket(n)
            assert b >= n and b % sharding.device_count() == 0

    def test_power_of_two_buckets_unchanged(self):
        from cometbft_tpu.ops import ed25519 as dev

        assert sharding.auto_bucket(100) == dev.bucket_size(100)


class TestShardedParity:
    def test_accept_and_reject_localize(self, sigs16):
        """verify_batch_mesh (batch axis sharded over all 8 devices,
        one verdict-bitmap gather) matches the serial host oracle,
        including the localized reject."""
        items, pks, parsed = sigs16
        want = serial_verdicts(items)
        got = mesh.verify_batch_mesh(pks, parsed)
        assert [bool(v) for v in got] == want
        assert not got[7] and sum(got) == 15

    @pytest.mark.slow
    def test_split_rlc_across_two_devices(self, sigs16):
        """One window split across 2 chips: per-chunk verdicts carry
        the reject structure (index 7 lands in chunk 0 of [0,8)).

        Slow tier: two RLC programs per split x two fixtures is
        minutes of XLA-CPU execution even on a warm compile cache;
        tier-1 keeps the sharded-verdict parity + placed-device
        cached-A tests."""
        _, pks, parsed = sigs16
        devices = jax.devices()[:2]
        out = mesh.split_rlc_verify(pks, parsed, devices)
        assert out == [False, True]
        good = make_items(16, seed=42)
        gpks = [i[0] for i in good]
        gparsed = ed.parse_and_hash(gpks, [i[1] for i in good],
                                    [i[2] for i in good])
        assert mesh.split_rlc_verify(gpks, gparsed, devices) \
            == [True, True]

    def test_cached_a_on_placed_device(self):
        """The A-table cache is keyed per device: a cached-A dispatch
        committed to device 1 must verify (a device-0 table entry
        would poison the placed program otherwise).  16 signatures =
        the width-16 cached-A program the multichip dryrun keeps in
        the persistent compile cache; the second-call cache-hit path
        is exercised by the dryrun's phase 3, so tier 1 pays ONE RLC
        execution and asserts the device-keyed entry directly."""
        good = make_items(16, seed=42)
        gpks = [i[0] for i in good]
        gparsed = ed.parse_and_hash(gpks, [i[1] for i in good],
                                    [i[2] for i in good])
        dev1 = jax.devices()[1]
        packed = ed.pack_rlc(gpks, [b""] * 16, [b""] * 16,
                             parsed=gparsed)
        assert ed.rlc_verify(packed, use_cache=True, device=dev1)
        key = (np.asarray(packed[0]).tobytes(), dev1)
        assert key in ed._A_TABLE_CACHE._entries

    def test_maybe_split_stays_off(self, sigs16, monkeypatch):
        """The opt-in gate, tier 1 (no device dispatch): without the
        env knob — or below min_split — maybe_split_verify declines
        and the caller keeps the single-device path."""
        _, pks, parsed = sigs16
        monkeypatch.delenv("COMETBFT_TPU_MESH_DEVICES", raising=False)
        assert mesh.maybe_split_verify(pks, parsed, min_split=4) is None
        monkeypatch.setenv("COMETBFT_TPU_MESH_DEVICES", "2")
        assert mesh.maybe_split_verify(pks, parsed,
                                       min_split=1 << 30) is None

    @pytest.mark.slow
    def test_maybe_split_dispatches_when_opted_in(self, sigs16,
                                                  monkeypatch):
        """Slow tier (first-touch of the fused RLC programs is ~2 min
        per process on XLA-CPU): with the env knob on and min_split
        crossed, the split verdict reflects the batch."""
        _, pks, parsed = sigs16
        monkeypatch.setenv("COMETBFT_TPU_MESH_DEVICES", "2")
        assert mesh.maybe_split_verify(pks, parsed,
                                       min_split=4) is False
        good = make_items(16, seed=42)
        gpks = [i[0] for i in good]
        gparsed = ed.parse_and_hash(gpks, [i[1] for i in good],
                                    [i[2] for i in good])
        assert mesh.maybe_split_verify(gpks, gparsed,
                                       min_split=4) is True

    @pytest.mark.slow
    def test_device_verify_mesh_hook_parity(self, sigs16, monkeypatch):
        """crypto/batch._device_verify with the mesh knob on: the
        split-RLC reject still localizes per signature, verdicts equal
        the serial oracle."""
        items, pks, parsed = sigs16
        monkeypatch.setenv("COMETBFT_TPU_MESH_DEVICES", "2")
        monkeypatch.setattr(mesh, "MIN_SPLIT", 4)
        ok, verdicts = cb._device_verify(pks, parsed)
        assert not ok
        assert [bool(v) for v in verdicts] == serial_verdicts(items)


class TestPipelineRoundRobin:
    def test_rotation_and_submission_order(self):
        """Windows rotate over the device list; verdicts still resolve
        in submission order even when device 0's dispatch is slow and
        later devices finish first."""
        order = []
        lock = threading.Lock()
        seen_devices = []

        def slow_dev0(win):
            with lock:
                seen_devices.append(win.device_index)
            if win.device_index == 0:
                time.sleep(0.2)
            return True, [True] * len(win.items)

        devices = jax.devices()[:4]
        with vd.VerifyPipeline(depth=8, dispatch_fn=slow_dev0,
                               devices=devices) as pipe:
            handles = []
            for w in range(8):
                h = pipe.submit(make_items(3, seed=w), ctx=w,
                                device_threshold=1)
                h.add_done_callback(
                    lambda hh: (lock.__enter__(),
                                order.append(hh.ctx),
                                lock.__exit__(None, None, None)))
                handles.append(h)
            for h in handles:
                assert h.result(timeout=60)[0] is True
                assert h.path == "device"
        assert order == list(range(8))
        assert sorted(seen_devices) == sorted([0, 1, 2, 3] * 2)
        assert pipe.device_windows == 8

    def test_single_device_forced_by_empty_tuple(self, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_MESH_DEVICES", "0")
        pipe = vd.VerifyPipeline(depth=2, devices=())
        assert pipe.devices is None
        pipe2 = vd.VerifyPipeline(depth=2)
        assert pipe2.devices is not None and len(pipe2.devices) == 8

    def test_verdict_parity_mesh_mode(self):
        """Same fixture through the mesh pipeline (stub judging what
        staging kept, as in test_dispatch) equals the serial oracle —
        staging bugs in mesh mode break parity here."""
        items = make_items(24, seed=7, bad=(3, 20))
        want = serial_verdicts(items)

        def judge_from_staging(win):
            out = [cb.safe_verify(PubKey(pk), m, s)
                   for pk, m, s in zip(win.pks, win.msgs, win.sigs)]
            return all(out), out

        with vd.VerifyPipeline(depth=4, dispatch_fn=judge_from_staging,
                               devices=jax.devices()[:2]) as pipe:
            h = pipe.submit(list(items), device_threshold=1)
            ok, got = h.result(timeout=60)
        assert got == want and not ok


class TestPerDeviceDrain:
    def test_fault_isolated_to_one_device(self):
        """A device failure on device 1 drains ONLY device 1's windows
        to the host; devices 0/2/3 keep dispatching.  Verdicts stay
        correct everywhere and device 1 recovers once its queue
        empties."""
        from cometbft_tpu.libs import flightrec
        from cometbft_tpu.libs import metrics as libmetrics
        from cometbft_tpu.libs.metrics import DeviceMetrics, Registry

        boom = {"armed": True}

        def flaky_dev1(win):
            if win.device_index == 1 and boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("injected device-1 failure")
            return (all(serial_verdicts(win.items)),
                    serial_verdicts(win.items))

        fixtures = [make_items(6, seed=w,
                               bad=((1,) if w == 5 else ()))
                    for w in range(8)]
        reg = Registry("cometbft_tpu")
        dm = DeviceMetrics(reg)
        libmetrics.set_device_metrics(dm)
        rec = flightrec.FlightRecorder()
        flightrec.set_recorder(rec)
        try:
            with vd.VerifyPipeline(depth=8, dispatch_fn=flaky_dev1,
                                   devices=jax.devices()[:4]) as pipe:
                handles = [pipe.submit(list(f), device_threshold=1)
                           for f in fixtures]
                results = [h.result(timeout=60) for h in handles]
                paths = [h.path for h in handles]
                pipe.drain(timeout=30)
                # device 1's queue emptied: it must dispatch again
                again = pipe.submit(make_items(2, seed=90),
                                    device_threshold=1)
                again2 = pipe.submit(make_items(2, seed=91),
                                     device_threshold=1)
                assert again.result(timeout=60)[0] is True
                assert again2.result(timeout=60)[0] is True
                assert "device" in (again.path, again2.path)
        finally:
            flightrec.set_recorder(None)
            libmetrics.set_device_metrics(None)
        for f, (ok, verdicts) in zip(fixtures, results):
            assert verdicts == serial_verdicts(f)
        assert results[5][0] is False       # the corrupted window
        assert all(ok for i, (ok, _) in enumerate(results) if i != 5)
        # window 1 faulted -> drain; windows NOT on device 1 dispatched
        assert paths[1] == "drain"
        for i in (0, 2, 3, 4, 6, 7):
            assert paths[i] == "device", (i, paths)
        assert pipe.faults == 1
        drain_ev = next(e for e in rec.events()
                        if e["kind"] == flightrec.EV_PIPELINE_DRAIN)
        assert drain_ev["device"] == 1
        text = reg.expose()
        assert 'pipeline_device_drains{device="1"} 1' in text
        assert 'mesh_dispatches{device="0"}' in text
        assert "pipeline_device_inflight_windows" in text

    def test_no_lost_or_forged_verdicts_under_repeat_faults(self):
        """Every window submitted while device 2 keeps failing still
        resolves exactly once with oracle verdicts (drain on 2, device
        elsewhere): the never-lose-never-forge acceptance bar."""
        def always_fail_dev2(win):
            if win.device_index == 2:
                raise RuntimeError("device 2 is gone")
            return (all(serial_verdicts(win.items)),
                    serial_verdicts(win.items))

        fixtures = [make_items(4, seed=w, bad=((0,) if w % 3 == 0
                                               else ()))
                    for w in range(9)]
        with vd.VerifyPipeline(depth=6, dispatch_fn=always_fail_dev2,
                               devices=jax.devices()[:3]) as pipe:
            handles = [pipe.submit(list(f), device_threshold=1)
                       for f in fixtures]
            results = [h.result(timeout=60) for h in handles]
        for f, (ok, verdicts) in zip(fixtures, results):
            want = serial_verdicts(f)
            assert verdicts == want
            assert ok == all(want)
        assert pipe.resolved == 9
        assert pipe.faults >= 1


class TestReactorWiring:
    def test_blocksync_pipeline_gets_devices_and_depth(self,
                                                      monkeypatch):
        from cometbft_tpu.blocksync import reactor as bs

        monkeypatch.delenv("COMETBFT_TPU_MESH_DEVICES", raising=False)
        r = bs.BlocksyncReactor.__new__(bs.BlocksyncReactor)
        r.pipeline_depth = 2
        r.mesh_devices = 4
        r._pipeline = None
        pipe = r._get_pipeline()
        try:
            assert pipe.devices is not None and len(pipe.devices) == 4
            assert pipe.depth == 8          # max(2, 2 * 4)
        finally:
            pipe.stop()
        r2 = bs.BlocksyncReactor.__new__(bs.BlocksyncReactor)
        r2.pipeline_depth = 2
        r2.mesh_devices = 0
        r2._pipeline = None
        pipe2 = r2._get_pipeline()
        try:
            assert pipe2.devices is None and pipe2.depth == 2
        finally:
            pipe2.stop()


class TestSecpMeshSplit:
    """crypto/mesh.split_secp_verify — the unified-MSM analog of the
    RLC split.  Tier-1 covers the gating and the routing/concat
    contract (no lost or forged verdicts across chunk boundaries) with
    a stubbed per-chunk dispatch; the real placed-device dispatch runs
    slow-tier so tier 1 never pays per-device kernel compiles."""

    @staticmethod
    def _secp_items(n, bad=()):
        from cometbft_tpu.crypto import secp256k1 as sk

        privs = [sk.PrivKey.generate(bytes([k + 1]) * 4)
                 for k in range(3)]
        pks, msgs, sigs = [], [], []
        for i in range(n):
            p = privs[i % 3]
            m = b"mesh-secp-" + i.to_bytes(4, "little")
            s = bytes(64) if i in bad else p.sign(m)
            pks.append(p.pub_key().bytes())
            msgs.append(m)
            sigs.append(s)
        return pks, msgs, sigs

    def test_maybe_split_gates_off(self, monkeypatch):
        pks, msgs, sigs = self._secp_items(4)
        monkeypatch.delenv("COMETBFT_TPU_MESH_DEVICES", raising=False)
        # under MIN_SPLIT: no split regardless of mesh state
        assert mesh.maybe_split_secp_verify(pks, msgs, sigs) is None
        # above the threshold but mesh opt-in absent: still no split
        assert mesh.maybe_split_secp_verify(pks, msgs, sigs,
                                            min_split=2) is None

    def test_split_routing_no_lost_or_forged_verdicts(self,
                                                      monkeypatch):
        """Every chunk dispatches to its own device BEFORE any
        readback, per-device dispatch counters advance, and the
        concatenated verdicts equal the host oracle in submission
        order — including rejects on both sides of a chunk
        boundary."""
        from cometbft_tpu.crypto import secp256k1 as sk
        from cometbft_tpu.libs import metrics as libmetrics
        from cometbft_tpu.libs.metrics import DeviceMetrics, Registry

        pks, msgs, sigs = self._secp_items(9, bad=(1, 4, 8))
        calls = []

        def fake_async(pk_c, m_c, s_c, batch_size=None, device=None):
            calls.append((len(pk_c), device))
            verdict = np.array(
                [sk.PubKey(pk).verify_signature(m, s)
                 for pk, m, s in zip(pk_c, m_c, s_c)])
            return verdict, np.ones(len(pk_c), bool), len(pk_c)

        monkeypatch.setattr(sk, "verify_msm_async", fake_async)
        monkeypatch.setenv("COMETBFT_TPU_MESH_DEVICES", "2")
        reg = Registry("t")
        dm = DeviceMetrics(reg)
        libmetrics.set_device_metrics(dm)
        try:
            got = mesh.maybe_split_secp_verify(pks, msgs, sigs,
                                               min_split=2)
        finally:
            libmetrics.set_device_metrics(None)
        want = [sk.PubKey(pk).verify_signature(m, s)
                for pk, m, s in zip(pks, msgs, sigs)]
        assert got == want
        assert [got[i] for i in (1, 4, 8)] == [False] * 3
        assert sum(bool(v) for v in got) == 6
        # one dispatch per device, spans cover all 9 sigs, and the
        # two chunks went to DISTINCT placed devices
        assert len(calls) == 2 and sum(c[0] for c in calls) == 9
        assert calls[0][1] is not calls[1][1]
        assert dm.mesh_dispatches._values.get(("0",)) == 1
        assert dm.mesh_dispatches._values.get(("1",)) == 1

    @pytest.mark.slow
    def test_split_real_device_parity(self, monkeypatch):
        """The unstubbed split: per-chunk pack + QTableCache (keyed
        per device) + placed MSM dispatch, verdict parity with the
        host oracle.  Slow tier: each placed device pays its own
        kernel + table-build compile on the CPU tier."""
        from cometbft_tpu.crypto import secp256k1 as sk

        pks, msgs, sigs = self._secp_items(8, bad=(2, 5))
        monkeypatch.setenv("COMETBFT_TPU_MESH_DEVICES", "2")
        old, sk._Q_CACHE = sk._Q_CACHE, sk.QTableCache()
        try:
            got = mesh.maybe_split_secp_verify(pks, msgs, sigs,
                                               min_split=2)
            # one table build per placed device, same key set
            assert sk.q_table_cache().misses == 2
        finally:
            sk._Q_CACHE = old
        want = [sk.PubKey(pk).verify_signature(m, s)
                for pk, m, s in zip(pks, msgs, sigs)]
        assert got == want


class TestShardedBucketMSM:
    @pytest.mark.slow
    def test_bucket_shard_parity_with_straus_scan(self):
        """ops/msm_shard.sharded_bucket_msm (per-device generic bucket
        engine + accumulator all_gather + tree fold) equals the
        single-device Straus scan on the same table/digit tensors over
        the full 8-device CPU mesh — the bucket arm shards without
        changing the group element."""
        import jax.numpy as jnp

        from cometbft_tpu.ops import ed25519 as dev
        from cometbft_tpu.ops import fe, msm_shard

        n_dev = sharding.device_count()
        w = 4 * n_dev
        items = make_items(w, seed=9)
        enc = np.stack([np.frombuffer(pk, dtype="<u4")
                        for pk, _, _ in items], axis=1)
        tab, ok = dev._msm_tables(jnp.asarray(enc))
        assert bool(np.asarray(ok))
        rng = np.random.default_rng(7)
        nwin = 4
        mags = jnp.asarray(rng.integers(0, 17, (nwin, w),
                                        dtype=np.int32))
        negs = jnp.asarray(rng.integers(0, 2, (nwin, w)) != 0)
        want = dev._msm_scan_xla(tab, mags, negs)
        got = msm_shard.sharded_bucket_msm(tab, mags, negs,
                                           mesh=sharding._mesh())
        x_eq = np.asarray(fe.freeze(fe.mul(got[0], want[2]))) \
            == np.asarray(fe.freeze(fe.mul(want[0], got[2])))
        y_eq = np.asarray(fe.freeze(fe.mul(got[1], want[2]))) \
            == np.asarray(fe.freeze(fe.mul(want[1], got[2])))
        assert x_eq.all() and y_eq.all()
