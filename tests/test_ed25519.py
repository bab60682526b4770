"""Ed25519: device kernel vs pure-Python reference vs the cryptography lib.

Covers RFC 8032 test vector 1, random sign/verify round-trips, tampered
signatures, structural rejects (s >= L), and ZIP-215 acceptance of
non-canonical encodings.
"""

import random

import numpy as np
import jax
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import batch as cb
from cometbft_tpu.ops import ed25519 as dev
from cometbft_tpu.ops import scalar25519 as sc
from cometbft_tpu.ops import limbs as lb

rng = random.Random(99)

# RFC 8032 §7.1 TEST 1
RFC_SEED = bytes.fromhex(
    "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60")
RFC_PUB = bytes.fromhex(
    "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
RFC_SIG = bytes.fromhex(
    "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
    "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b")


def test_rfc8032_vector1():
    assert ref.pubkey_from_seed(RFC_SEED) == RFC_PUB
    assert ref.sign(RFC_SEED, b"") == RFC_SIG
    assert ref.verify(RFC_PUB, b"", RFC_SIG)
    assert not ref.verify(RFC_PUB, b"x", RFC_SIG)


def test_against_cryptography_lib():
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey)
    for _ in range(4):
        sk = Ed25519PrivateKey.generate()
        seed = sk.private_bytes_raw()
        msg = rng.randbytes(rng.randrange(0, 200))
        lib_sig = sk.sign(msg)
        assert ref.pubkey_from_seed(seed) == sk.public_key().public_bytes_raw()
        assert ref.sign(seed, msg) == lib_sig
        assert ref.verify(sk.public_key().public_bytes_raw(), msg, lib_sig)


def _batch(n, msg_len=100):
    pks, msgs, sigs = [], [], []
    for _ in range(n):
        priv = ed.PrivKey.generate(rng.randbytes(32))
        m = rng.randbytes(msg_len)
        pks.append(priv.pub_key().bytes())
        msgs.append(m)
        sigs.append(priv.sign(m))
    return pks, msgs, sigs


def test_device_kernel_verdicts():
    pks, msgs, sigs = _batch(6)
    # corrupt: flip a byte in sig 1, wrong msg for 3, s >= L for 4
    sigs[1] = sigs[1][:10] + bytes([sigs[1][10] ^ 0xFF]) + sigs[1][11:]
    msgs[3] = msgs[3] + b"!"
    bad_s = sigs[4][:32] + (ref.L + 5).to_bytes(32, "little")
    sigs[4] = bad_s
    expected = [True, False, True, False, False, True]

    bv = cb.TpuEd25519BatchVerifier()
    for pk, m, s in zip(pks, msgs, sigs):
        bv.add(pk, m, s)
    ok, verdicts = bv.verify()
    assert verdicts == expected
    assert not ok

    cpu = cb.CpuEd25519BatchVerifier()
    for pk, m, s in zip(pks, msgs, sigs):
        cpu.add(pk, m, s)
    assert cpu.verify()[1] == expected


def test_device_kernel_all_good():
    pks, msgs, sigs = _batch(5, msg_len=180)
    bv = cb.create_batch_verifier("ed25519", provider="tpu")
    for pk, m, s in zip(pks, msgs, sigs):
        bv.add(pk, m, s)
    ok, verdicts = bv.verify()
    assert ok and all(verdicts)


def test_zip215_noncanonical_y():
    """A pubkey with y >= p must be accepted by ZIP-215 decompression."""
    # y = p + 3 encodes non-canonically; find a valid curve y
    y_can = 3
    pt = ref.point_decompress(y_can.to_bytes(32, "little"))
    if pt is None:
        pytest.skip("y=3 not on curve")  # pragma: no cover
    noncanon = (ref.P + y_can).to_bytes(32, "little")
    assert ref.point_decompress(noncanon) is not None
    assert ref.point_decompress(noncanon, zip215=False) is None
    # device decompression agrees
    words = np.frombuffer(noncanon, dtype=np.uint32)[:, None]
    _, ok = jax.jit(dev.decompress)(words)
    assert bool(np.asarray(ok)[0])


def test_barrett_reduce():
    f = jax.jit(sc.barrett_reduce_wide)
    vals = [0, 1, sc.L - 1, sc.L, sc.L + 1, 2 * sc.L, (1 << 512) - 1,
            (sc.L << 259) + 12345]
    vals += [rng.randrange(0, 1 << 512) for _ in range(8)]
    x = np.stack([lb.int_to_limbs(v, 32) for v in vals])
    out = np.asarray(f(x))
    for row, v in zip(out, vals):
        assert lb.limbs_to_int(row) == v % sc.L


def test_point_ops_match_reference():
    """Device add/double vs Python ints on random points."""
    from cometbft_tpu.ops import fe
    pts = []
    for _ in range(3):
        k = rng.randrange(1, ref.L)
        pts.append(ref.point_mul(k, ref.B))

    def to_dev(p):
        return np.stack([fe.int_to_limbs(c % ref.P) for c in p])[..., None]

    add = jax.jit(dev.point_add)
    dbl = jax.jit(dev.point_double)
    for p in pts:
        for q in pts:
            got = np.asarray(add(to_dev(p), to_dev(q)))[..., 0]
            want = ref.point_add(p, q)
            gx, gy, gz, gt = [fe.limbs_to_int(row) for row in got]
            assert (gx * want[2] - want[0] * gz) % ref.P == 0
            assert (gy * want[2] - want[1] * gz) % ref.P == 0
        got = np.asarray(dbl(to_dev(p)))[..., 0]
        want = ref.point_double(p)
        gx, gy, gz, gt = [fe.limbs_to_int(row) for row in got]
        assert (gx * want[2] - want[0] * gz) % ref.P == 0
        assert (gy * want[2] - want[1] * gz) % ref.P == 0
        # T consistency: T*Z == X*Y
        assert (gt * gz - gx * gy) % ref.P == 0


def test_single_verify_fast_path_consistent_with_zip215():
    """PubKey.verify_signature (OpenSSL fast path + ZIP-215 fallback)
    must agree with the from-scratch ZIP-215 oracle, including the
    cofactored-only case OpenSSL rejects."""
    import hashlib

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import ed25519_ref as ref

    seed, pub = ref.keygen(b"\x11" * 32)
    pk = ed.PubKey(pub)

    sig = ref.sign(seed, b"fast-path")
    assert pk.verify_signature(b"fast-path", sig)
    assert not pk.verify_signature(b"other", sig)
    assert not pk.verify_signature(b"fast-path", sig[:-1] + b"\x01")

    # Craft a signature whose R carries an 8-torsion component: the
    # cofactored ZIP-215 equation holds, the cofactorless one fails, so
    # the OpenSSL fast path must fall back (not reject) for parity with
    # the batch kernel's semantics.
    t8 = ref.point_decompress(bytes.fromhex(
        "26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc05"))
    assert t8 is not None
    h = hashlib.sha512(seed).digest()
    a = ref._clamp(h)
    prefix = h[32:]
    msg = b"torsion"
    r = int.from_bytes(hashlib.sha512(prefix + msg).digest(),
                       "little") % ref.L
    r_pt = ref.point_mul(r, ref.B)
    r_enc = ref.point_compress(ref.point_add(r_pt, t8))
    k = int.from_bytes(hashlib.sha512(r_enc + pub + msg).digest(),
                       "little") % ref.L
    s = (r + k * a) % ref.L
    tsig = r_enc + s.to_bytes(32, "little")
    assert ref.verify(pub, msg, tsig), "oracle: cofactored must accept"
    assert pk.verify_signature(msg, tsig), \
        "fast path must fall back to ZIP-215, not reject"


def test_rlc_batch_equation():
    """RLC whole-batch verify: accepts honest batches, rejects tampered,
    and the verifier falls back to per-signature verdicts on failure."""
    import numpy as np
    from cometbft_tpu.ops import ed25519 as devk

    pks, msgs, sigs = _batch(10)
    packed = ed.pack_rlc(pks, msgs, sigs)
    assert bool(np.asarray(devk.rlc_verify_device(*packed)))

    bad = bytearray(sigs[3]); bad[5] ^= 0x40; sigs[3] = bytes(bad)
    packed = ed.pack_rlc(pks, msgs, sigs)
    assert not bool(np.asarray(devk.rlc_verify_device(*packed)))

    bv = cb.TpuEd25519BatchVerifier()
    for pk, m, s in zip(pks, msgs, sigs):
        bv.add(pk, m, s)
    ok, verdicts = bv.verify()
    assert not ok
    assert verdicts == [True] * 3 + [False] + [True] * 6

    # structural reject (s >= L) never reaches the RLC path
    sigs[7] = sigs[7][:32] + (ref.L + 1).to_bytes(32, "little")
    assert ed.pack_rlc(pks, msgs, sigs) is None


def test_rlc_a_table_cache():
    """The device A-table cache: cached dispatches agree with the
    uncached kernel, repeated validator sets hit the cache, and a
    tampered signature still fails through the cached path."""
    cache = ed._A_TABLE_CACHE
    h0, m0 = cache.hits, cache.misses

    privs = [ed.PrivKey.generate(bytes([0x40 + i]) * 32)
             for i in range(6)]
    pks = [p.pub_key().bytes() for p in privs]

    # same 6 signers, three different "commits" (messages) — one table
    # build then hits, same verdicts as the uncached kernel
    for round_ in range(3):
        ms = [b"commit %d vote %d" % (round_, i) for i in range(6)]
        ss = [privs[i].sign(ms[i]) for i in range(6)]
        packed = ed.pack_rlc(pks, ms, ss)
        assert ed.rlc_verify(packed, use_cache=True)
        assert ed.rlc_verify(packed, use_cache=False)
    assert cache.misses == m0 + 1, "same valset must build tables once"
    assert cache.hits >= h0 + 2

    # tampered sig rejected through the cached path (cache hit)
    ms = [b"commit 9 vote %d" % i for i in range(6)]
    ss = [privs[i].sign(ms[i]) for i in range(6)]
    bad = bytearray(ss[2]); bad[4] ^= 1; ss[2] = bytes(bad)
    packed = ed.pack_rlc(pks, ms, ss)
    assert not ed.rlc_verify(packed, use_cache=True)
    assert cache.misses == m0 + 1

    # a DIFFERENT valset (reversed order) is a different cache entry
    order = list(reversed(range(6)))
    packed = ed.pack_rlc([pks[i] for i in order],
                         [ms[i] for i in order],
                         [privs[i].sign(ms[i]) for i in order])
    assert ed.rlc_verify(packed, use_cache=True)
    assert cache.misses == m0 + 2


def _valset_words(tag, n=6):
    privs = [ed.PrivKey.generate(bytes([tag]) * 31 + bytes([i + 1]))
             for i in range(n)]
    pks = [p.pub_key().bytes() for p in privs]
    ms = [b"byte bound %d" % i for i in range(n)]
    ss = [privs[i].sign(ms[i]) for i in range(n)]
    return np.asarray(ed.pack_rlc(pks, ms, ss)[0])


def test_a_table_cache_byte_bound():
    """The LRU is bounded by BYTES, not entries: admitting past the
    budget evicts oldest-first, the accounting tracks exactly, and a
    single table larger than the whole budget is served un-admitted
    (reference bounds the analogous expanded-pubkey cache the same
    way, crypto/ed25519/ed25519.go:64-70)."""
    words = [_valset_words(0x50 + t) for t in range(3)]
    per_entry = 17 * 4 * 20 * words[0].shape[-1] * 4

    cache = ed.ATableCache(capacity=100, max_bytes=2 * per_entry)
    cache.get(words[0])
    cache.get(words[1])
    assert cache.bytes_resident == 2 * per_entry
    assert cache.evictions == 0
    cache.get(words[2])                    # over budget: evict oldest
    assert cache.bytes_resident == 2 * per_entry
    assert cache.evictions == 1
    h = cache.hits
    cache.get(words[0])                    # evicted -> rebuild
    assert cache.hits == h and cache.misses == 4

    # two threads missing on the SAME key must count its bytes once
    # (the build runs outside the lock; the insert re-checks)
    import threading

    cache2 = ed.ATableCache(capacity=8, max_bytes=10 * per_entry)
    from cometbft_tpu.ops import ed25519 as devk
    barrier = threading.Barrier(2, timeout=20)
    orig_build = devk.build_a_tables_device

    def synced_build(a_words):
        barrier.wait()                  # both threads inside the miss
        return orig_build(a_words)

    devk.build_a_tables_device = synced_build
    try:
        ts = [threading.Thread(target=cache2.get, args=(words[0],))
              for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        devk.build_a_tables_device = orig_build
    assert cache2.misses == 2
    assert cache2.bytes_resident == per_entry

    # oversize single table: served by get(), never admitted — and the
    # default policy refuses to route it through the cached kernel at
    # all (rebuilding per sighting would be slower than staying fused)
    tiny = ed.ATableCache(capacity=100, max_bytes=per_entry - 1)
    tiny.MIN_K = 4
    assert tiny.get_if_worthwhile(words[0]) is None
    assert tiny.get_if_worthwhile(words[0]) is None   # every sighting
    tab, ok = tiny.get(words[0])
    assert tab.shape[-1] == words[0].shape[-1]
    assert tiny.bytes_resident == 0 and len(tiny._entries) == 0
    # and verification through an un-admitted entry still works
    from cometbft_tpu.ops import ed25519 as devk
    privs = [ed.PrivKey.generate(bytes([0x50]) * 31 + bytes([i + 1]))
             for i in range(6)]
    pks = [p.pub_key().bytes() for p in privs]
    ms = [b"byte bound %d" % i for i in range(6)]
    ss = [privs[i].sign(ms[i]) for i in range(6)]
    packed = ed.pack_rlc(pks, ms, ss)
    out = devk.rlc_verify_device_cached_a(
        tab, ok, packed[1], packed[2], packed[3], packed[4], packed[5])
    assert bool(np.asarray(out))


# -- the packer on the two lanes every RLC batch takes -------------------------

@pytest.fixture(params=["native", "python"])
def packer(request, monkeypatch):
    """The library where it builds, or the loader forced to fail."""
    from cometbft_tpu.crypto import rlcpack

    if request.param == "python":
        monkeypatch.setattr(rlcpack, "_lib", None)
        monkeypatch.setattr(rlcpack, "_failed", True)
    elif not rlcpack.build():
        pytest.skip("no g++ to build librlcpack.so")
    return request.param


def _through_the_seam(items):
    bv = cb.create_batch_verifier("ed25519", provider="tpu")
    for pk, m, s in items:
        bv.add(pk, m, s)
    return bv.verify()


def _through_a_window(items):
    from cometbft_tpu.crypto import dispatch as vd

    with vd.VerifyPipeline(depth=2) as pipe:
        handle = pipe.submit(list(items), subsystem="light",
                             device_threshold=1)
        out = handle.result(timeout=1200)
    assert handle.path == "device"
    return out


@pytest.mark.parametrize("altered", [None, 3])
@pytest.mark.parametrize("lane", [_through_the_seam, _through_a_window])
def test_both_lanes_pack_once_and_parse_only_at_a_reject(
        packer, lane, altered, monkeypatch):
    """The seam and a pipeline window on the CPU backend, the real
    kernels at the 8 x 8 shape: a sound batch is accepted in one
    equation with nothing parsed ahead of it; with one signature
    altered the equation rejects, parse_and_hash runs then, and the
    per-signature kernel names the altered one."""
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.libs import metrics as libmetrics

    privs = [ed.PrivKey.generate(bytes([0x61, i + 1]) * 16)
             for i in range(5)]
    items = [(p.pub_key(), b"lane vote %d" % i, p.sign(b"lane vote %d" % i))
             for i, p in enumerate(privs)]
    if altered is not None:
        pk, m, s = items[altered]
        items[altered] = (pk, m, s[:9] + bytes([s[9] ^ 0x04]) + s[10:])
    parses = []
    real = ed.parse_and_hash
    monkeypatch.setattr(
        ed, "parse_and_hash",
        lambda *a: parses.append(len(a[0])) or real(*a))
    dm = libmetrics.DeviceMetrics(libmetrics.Registry())
    prev = libmetrics.device_metrics()
    libmetrics.set_device_metrics(dm)
    sigcache.set_enabled(False)     # a hit would resolve before the lane
    try:
        ok, verdicts = lane(items)
    finally:
        sigcache.set_enabled(None)
        libmetrics.set_device_metrics(prev)

    def read(metric):
        with metric._mtx:
            return {k[0] if k else "": v for k, v in metric._values.items()}

    assert read(dm.host_pack_signatures) == {packer: 5.0}
    if altered is None:
        assert ok and verdicts == [True] * 5
        assert read(dm.signatures_verified) == {"rlc": 5.0}
        assert read(dm.rlc_fallbacks) == {}
    else:
        assert not ok
        assert verdicts == [i != altered for i in range(5)]
        assert read(dm.signatures_verified) == {"persig": 5.0}
        assert read(dm.rlc_fallbacks) == {"": 1.0}
    # the library hashes for itself: nothing is parsed but at the
    # reject (the Python packer parses once more, to pack)
    rejects = [5] if altered is not None else []
    assert parses == ([5] if packer == "python" else []) + rejects
