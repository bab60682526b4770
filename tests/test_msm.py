"""Unified batched MSM engine (ops/msm.py) and the secp256k1 MSM
verify path it powers (ops/secp256k1 msm kernels + crypto/secp256k1
pack/cache/orchestration + crypto/batch routing).

Pinning layers:

1. host recodes — the closed-form Joye-Tunstall odd recode
   reconstructs its scalar exactly (both shipping window plans plus a
   narrow one, including the edge scalars 1, 3, 2n-1), and the
   generic biased recode round-trips digits;
2. curve-generic goldens — bucket_msm vs ed25519_ref / the secp host
   bigint oracle at multiple window widths, on both curves (the
   "multiple widths" matrix stays narrow: XLA-CPU compile cost scales
   with the unrolled window count, and the engine is width-uniform by
   construction);
3. the secp MSM kernel vs the host verify oracle across accept and
   every reject class, with per-signature localization;
4. the crypto/batch seam — engine on (cold tables), engine on (hot
   QTableCache), engine off (Straus ladder) raise BYTE-IDENTICAL
   `wrong signature` errors on the same bad commit, mirroring
   tests/test_device_hash.py's hot/cold/disabled discipline.

Every device test below shares one kernel shape (batch 16, key pad 4)
so the whole file pays for a single compile of each program.
"""

import random

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import secp256k1 as sk
from cometbft_tpu.ops import msm

P25519 = (1 << 255) - 19


def _signed_digits(e, width, ndig):
    """Sequential-carry signed-window reference recode, MSB-first."""
    ds, carry = [], 0
    for i in range(ndig):
        d = ((e >> (width * i)) & ((1 << width) - 1)) + carry
        carry = 0
        if d >= (1 << (width - 1)):
            d -= 1 << width
            carry = 1
        ds.append(d)
    assert carry == 0, "scalar too wide for ndig"
    mags = np.array([abs(d) for d in reversed(ds)], np.int32)
    negs = np.array([d < 0 for d in reversed(ds)], bool)
    return mags, negs


class TestRecodeJT:
    # the shipping G plan (8, 32), the shipping Q plan (5, 52), and a
    # narrow plan for the general form
    @pytest.mark.parametrize("width,ndig", [(8, 32), (5, 52), (2, 130)])
    def test_exact_reconstruction(self, width, ndig):
        """k = sum d_i 2^(iw) + 2^(tw) for every odd k in range, with
        every digit odd — including the edge scalars 1, 3, 2n-1."""
        n = sk.N
        rng = random.Random(12)
        top = 1 << (ndig * width + 1)
        ks = [1, 3, min(2 * n - 1, top - 1)]
        ks += [rng.randrange(0, top) | 1 for _ in range(40)]
        rows, negs = msm.recode_jt(ks, width, ndig)
        assert rows.shape == (ndig, len(ks))
        assert int(rows.max()) < (1 << (width - 1))
        for i, k in enumerate(ks):
            acc = 1 << (ndig * width)       # correction point
            for j in range(ndig):
                d = 2 * int(rows[j, i]) + 1
                if negs[j, i]:
                    d = -d
                assert d % 2 == 1 or (-d) % 2 == 1
                acc += d << (j * width)
            assert acc == k, (width, ndig, i)

    def test_rejects_even_and_oversized(self):
        with pytest.raises(AssertionError):
            msm.recode_jt([2], 5, 52)
        with pytest.raises(AssertionError):
            msm.recode_jt([(1 << 41) | 1], 5, 8)

    def test_digit_oracle_matches(self):
        k = 0xDEADBEEF | 1
        rows, negs = msm.recode_jt([k], 4, 9)
        got = msm.jt_digit_value(rows[:, 0], negs[:, 0], 4)
        assert got == k - (1 << 36)


class TestBiasedRecode:
    @pytest.mark.parametrize("width,ndig", [(2, 10), (5, 7), (8, 5)])
    def test_round_trip_vs_reference(self, width, ndig):
        """The generic biased digit extraction equals the
        sequential-carry reference for any width (the w=5 instance is
        additionally pinned bit-identical to the shipping host recode
        by tests/test_device_hash.py through _recode_w5_device)."""
        import jax.numpy as jnp

        rng = random.Random(5)
        es = [rng.randrange(0, 1 << (width * ndig - 2))
              for _ in range(9)]
        bias = msm.bias_int(width, ndig)
        nlimbs = (width * ndig + 1 + 15) // 16 + 1
        xb = np.zeros((len(es), nlimbs), np.uint32)
        for i, e in enumerate(es):
            v = e + bias
            for li in range(nlimbs):
                xb[i, li] = (v >> (16 * li)) & 0xFFFF
        mags, negs = msm.recode_biased_digits(
            jnp.asarray(xb), width, ndig)
        mags, negs = np.asarray(mags), np.asarray(negs)
        for i, e in enumerate(es):
            m, g = _signed_digits(e, width, ndig)
            assert (mags[:, i] == m).all() and (negs[:, i] == g).all()


class TestBucketMSMGoldens:
    """bucket_msm vs independent scalar-mult references, both curves,
    multiple window widths.  The engine runs EAGER here
    (jax.disable_jit): the generic spec's complete-addition scan body
    hits a pathological XLA-CPU compile (one width-4 secp program
    measured 528 s to compile), and eager mode pins the identical
    numerics op-by-op without it.  Even eager, each arm costs 10-30 s
    of per-op dispatch, so the whole matrix lives in the slow tier;
    tier-1 keeps the engine honest through the host recode units above
    and the secp MSM kernel tests below (incomplete-add odd-digit
    form, warm persistent-cache shape) vs the host verify oracle."""

    NDIG = 4
    LANES = 8

    def _digits(self, eis, width, ndig):
        mags = np.zeros((ndig, len(eis)), np.int32)
        negs = np.zeros((ndig, len(eis)), bool)
        for i, e in enumerate(eis):
            mags[:, i], negs[:, i] = _signed_digits(e, width, ndig)
        return mags, negs

    def _run_ed25519(self, width, ndig=None):
        import jax

        from cometbft_tpu.ops import ed25519 as ed

        ndig = ndig or self.NDIG
        spec = msm.ed25519_spec()
        rng = random.Random(2)
        ais = [rng.randrange(1, spec.order) for _ in range(self.LANES)]
        eis = [rng.randrange(0, 1 << (width * ndig - 2))
               for _ in range(self.LANES)]
        encs = [ref.point_compress(ref.point_mul(a, ref.B))
                for a in ais]
        enc_words = np.stack(
            [np.frombuffer(e, np.uint32) for e in encs], axis=1)
        pts, ok = ed.decompress(np.asarray(enc_words))
        assert bool(np.asarray(ok).all())
        mags, negs = self._digits(eis, width, ndig)
        with jax.disable_jit():
            out = msm.bucket_msm(spec, (pts, None), mags, negs, width)
        x, y = spec.to_affine_int(out)
        px, py, pz, _ = ref.point_mul(
            sum(e * a for e, a in zip(eis, ais)) % spec.order, ref.B)
        zi = pow(pz, P25519 - 2, P25519)
        assert (x, y) == (px * zi % P25519, py * zi % P25519)

    def _run_secp256k1(self, width, lanes=4):
        import jax

        from cometbft_tpu.ops import fe_secp as fs

        spec = msm.secp256k1_spec()
        rng = random.Random(3)
        ais = [rng.randrange(1, sk.N) for _ in range(lanes)]
        eis = [rng.randrange(0, 1 << (width * self.NDIG - 2))
               for _ in range(lanes)]
        pts = np.zeros((3, fs.NLIMBS, lanes), np.int32)
        one = fs.int_to_limbs(1)
        for i, a in enumerate(ais):
            x, y = sk._jaffine(sk._jmul(a, sk._G))
            pts[0, :, i] = fs.int_to_limbs(x)
            pts[1, :, i] = fs.int_to_limbs(y)
            pts[2, :, i] = one
        inf = np.zeros(lanes, bool)
        mags, negs = self._digits(eis, width, self.NDIG)
        with jax.disable_jit():
            out = msm.bucket_msm(spec, (pts, inf), mags, negs, width)
        x, y = spec.to_affine_int(out)
        ex, ey = sk._jaffine(sk._jmul(
            sum(e * a for e, a in zip(eis, ais)) % sk.N, sk._G))
        assert (x, y) == (ex, ey)

    @pytest.mark.slow
    def test_ed25519_vs_ref_w2(self):
        self._run_ed25519(2, ndig=3)

    @pytest.mark.slow
    def test_ed25519_vs_ref_w4(self):
        self._run_ed25519(4)

    @pytest.mark.slow
    def test_secp256k1_vs_host_bigint_w2(self):
        self._run_secp256k1(2)

    @pytest.mark.slow
    def test_secp256k1_vs_host_bigint_w4(self):
        self._run_secp256k1(4, lanes=8)


class TestSecpMsmKernel:
    """pack_msm_batch + QTableCache + verify_batch_msm_device vs the
    host verify oracle.  One (16, key-pad-4) shape for the file."""

    def _fixture(self, n=10, n_keys=3):
        privs = [sk.PrivKey.generate(bytes([i + 1]) * 4)
                 for i in range(n_keys)]
        pks, msgs, sigs = [], [], []
        for i in range(n):
            p = privs[i % n_keys]
            m = b"msm-sig-%d" % i
            pks.append(p.pub_key().bytes())
            msgs.append(m)
            sigs.append(p.sign(m))
        return pks, msgs, sigs

    def test_accept_reject_classes_and_localization(self):
        pks, msgs, sigs = self._fixture()
        want = []
        # every reject class: tampered sig, wrong message, wrong key,
        # high-S, structurally invalid — verdicts must localize
        sigs[1] = sigs[1][:8] + bytes([sigs[1][8] ^ 1]) + sigs[1][9:]
        msgs[2] = b"wrong message"
        pks[3] = pks[1]  # index 3 signs with privs[0]; pks[1] differs
        s = int.from_bytes(sigs[4][32:], "big")
        sigs[4] = sigs[4][:32] + (sk.N - s).to_bytes(32, "big")
        sigs[5] = bytes(64)
        for pk, m, s_ in zip(pks, msgs, sigs):
            want.append(sk.PubKey(pk).verify_signature(m, s_))
        assert want[0] and not any(want[1:6]) and all(want[6:])
        got = sk.verify_msm_batch(pks, msgs, sigs)
        assert got == want

    def test_q_table_cache_hits_and_metrics(self):
        from cometbft_tpu.libs import metrics as libmetrics

        pks, msgs, sigs = self._fixture(n=6)
        cache = sk.QTableCache()
        old, sk._Q_CACHE = sk._Q_CACHE, cache
        old_dm = libmetrics.device_metrics()
        try:
            reg = libmetrics.Registry()
            dm = libmetrics.DeviceMetrics(reg)
            libmetrics.set_device_metrics(dm)
            try:
                assert all(sk.verify_msm_batch(pks, msgs, sigs))
                assert all(sk.verify_msm_batch(pks, msgs, sigs))
            finally:
                libmetrics.set_device_metrics(old_dm)
            assert cache.misses == 1 and cache.hits == 1
            assert cache.bytes_resident > 0
            assert dm.q_table_cache_hits._values.get((), 0) == 1
            assert dm.q_table_cache_misses._values.get((), 0) == 1
            assert dm.q_table_cache_bytes._values.get((), 0) == \
                cache.bytes_resident
        finally:
            sk._Q_CACHE = old

    def test_q_table_cache_lru_evicts_by_bytes(self):
        pks, msgs, sigs = self._fixture(n=4, n_keys=2)
        pks2, msgs2, sigs2 = self._fixture(n=4, n_keys=3)
        sizing = sk.QTableCache()
        old, sk._Q_CACHE = sk._Q_CACHE, sizing
        try:
            assert all(sk.verify_msm_batch(pks, msgs, sigs))
            nbytes = sizing.bytes_resident      # one resident entry
            assert nbytes > 0
            cache = sk.QTableCache(max_bytes=nbytes)  # room for one
            sk._Q_CACHE = cache
            assert all(sk.verify_msm_batch(pks, msgs, sigs))
            assert all(sk.verify_msm_batch(pks2, msgs2, sigs2))
            assert cache.evictions == 1
            # the first key set was evicted: a third verify re-misses
            assert all(sk.verify_msm_batch(pks, msgs, sigs))
            assert cache.misses == 3 and cache.hits == 0
        finally:
            sk._Q_CACHE = old

    def test_batch_verifier_routes_msm_and_env_off_routes_ladder(
            self, monkeypatch):
        from cometbft_tpu.crypto import batch as cb

        pks, msgs, sigs = self._fixture(n=5)
        sigs[3] = bytes(64)

        def run():
            bv = cb.create_batch_verifier("secp256k1", provider="tpu")
            for pk, m, s in zip(pks, msgs, sigs):
                bv.add(sk.PubKey(pk), m, s)
            return bv.verify()

        monkeypatch.delenv("COMETBFT_TPU_SECP_MSM", raising=False)
        assert sk.msm_enabled()
        ok_msm, v_msm = run()
        monkeypatch.setenv("COMETBFT_TPU_SECP_MSM", "0")
        assert not sk.msm_enabled()
        ok_ladder, v_ladder = run()
        assert (ok_msm, v_msm) == (ok_ladder, v_ladder)
        assert v_msm == [True, True, True, False, True]


class TestWrongSignatureErrorParity:
    """Engine on (cold tables) / engine on (hot tables) / engine off
    (ladder) must raise BYTE-IDENTICAL `wrong signature` errors on the
    same bad secp-validator commit — the test_device_hash.py
    hot/cold/disabled mirror for the MSM engine."""

    CHAIN_ID = "msm-parity-chain"

    def _commit_fixture(self, bad=()):
        from cometbft_tpu.types import canonical
        from cometbft_tpu.types.block import (
            BlockID, Commit, CommitSig, PartSetHeader,
            BLOCK_ID_FLAG_COMMIT)
        from cometbft_tpu.types.timestamp import Timestamp
        from cometbft_tpu.types.validator_set import (
            Validator, ValidatorSet)

        privs = [sk.PrivKey.generate(bytes([i + 1]) * 32)
                 for i in range(4)]
        vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
        by_addr = {p.pub_key().address(): p for p in privs}
        bid = BlockID(b"\xab" * 32, PartSetHeader(1, b"\xcd" * 32))
        commit = Commit(height=5, round=0, block_id=bid, signatures=[])
        for i, val in enumerate(vs.validators):
            ts = Timestamp(1000 + i, 0)
            sb = canonical.vote_sign_bytes(
                self.CHAIN_ID, 2, 5, 0, bid, ts)
            sig = bytes(64) if i in bad \
                else by_addr[val.address].sign(sb)
            commit.signatures.append(
                CommitSig(BLOCK_ID_FLAG_COMMIT, val.address, ts, sig))
        return vs, bid, commit

    def test_byte_identical_cold_hot_ladder(self, monkeypatch):
        from cometbft_tpu.crypto import sigcache
        from cometbft_tpu.types import validation

        monkeypatch.setenv("COMETBFT_TPU_PROVIDER", "tpu")
        vs, bid, commit = self._commit_fixture(bad=(2,))

        def run_arm() -> str:
            sigcache.reset()
            with pytest.raises(validation.ErrInvalidSignature) as ei:
                validation.verify_commit(
                    self.CHAIN_ID, vs, bid, 5, commit)
            return str(ei.value)

        monkeypatch.delenv("COMETBFT_TPU_SECP_MSM", raising=False)
        old, sk._Q_CACHE = sk._Q_CACHE, sk.QTableCache()
        try:
            e_cold = run_arm()
            e_hot = run_arm()              # tables stay resident
            assert sk.q_table_cache().hits >= 1
        finally:
            sk._Q_CACHE = old
        monkeypatch.setenv("COMETBFT_TPU_SECP_MSM", "0")
        e_ladder = run_arm()
        assert e_cold == e_hot == e_ladder
        assert "wrong signature (#2)" in e_cold


@pytest.mark.slow
def test_simnet_ab_bit_identical_app_hash_engine_toggle(monkeypatch):
    """Same-seed simnet blocksync over a SECP256K1 validator set with
    the MSM engine ON then OFF (ladder): both arms must reach the
    target height and commit bit-identical app hashes — the engine is
    a performance path, never a consensus-visible one.  Mirrors
    tests/test_device_hash.py's device-hash A/B discipline."""
    import time

    from cometbft_tpu.blocksync import reactor as breactor
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.simnet import (
        SimNetwork, SimNode, clone_chain, grow_chain, make_sim_genesis)
    from cometbft_tpu.types import validation

    blocks = 5
    monkeypatch.setattr(breactor, "VERIFY_WINDOW", 2)
    monkeypatch.setattr(validation.DeferredSigBatch,
                        "DEVICE_THRESHOLD", 1)
    # force the batch path through the Tpu verifier so the engine
    # toggle is actually on the verify path (auto would route these
    # tiny windows to the host loop and A/B nothing)
    monkeypatch.setenv("COMETBFT_TPU_PROVIDER", "tpu")

    def run_arm(seed=77):
        net = SimNetwork(seed=seed)
        net.set_default_link(latency=0.001)
        genesis, privs = make_sim_genesis(4, seed=seed, key_module=sk)
        src = SimNode("src", genesis, net, seed=seed)
        grow_chain(src, privs, blocks + 1)
        src2 = SimNode("src2", genesis, net, seed=seed)
        clone_chain(src, src2)
        syncer = SimNode("syncer", genesis, net, block_sync=True,
                         seed=seed)
        nodes = (src, src2, syncer)
        for n_ in nodes:
            n_.start()
        try:
            syncer.dial(src)
            syncer.dial(src2)
            assert syncer.wait_for_height(blocks, timeout=600), \
                f"stalled at {syncer.height()}"
            time.sleep(0.2)
            want = src.block_store.load_block(
                blocks + 1).header.app_hash
            got = syncer.app_hash()
            assert got == want, "arm diverged from the source chain"
            return (syncer.height(), got.hex())
        finally:
            for n_ in nodes:
                n_.stop()

    sigcache.set_enabled(False)
    try:
        monkeypatch.delenv("COMETBFT_TPU_SECP_MSM", raising=False)
        msm_arm = run_arm()
        monkeypatch.setenv("COMETBFT_TPU_SECP_MSM", "0")
        ladder_arm = run_arm()
    finally:
        sigcache.set_enabled(True)
    assert msm_arm == ladder_arm
    assert msm_arm[0] == blocks
