"""The native RLC packer (native/rlcpack behind crypto/rlcpack.py)
against the Python packer it replaces on the hot path.

Packing needs no sound signature, only keys of 32 bytes, signatures of
64 and an s below L, so the batches here are random bytes: (a) the six
arrays equal for one random block over batch sizes, key repeats and
every SHA-512 padding edge; (b) structural rejects; (c) the library's
own hash and reduction against known answers; (d) packing from several
threads at once; (e) the fall back and its counter; (f) the stale check.
"""

import hashlib
import os
import random
import shutil
import threading

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import rlcpack
from cometbft_tpu.libs import metrics as libmetrics

L = ed.L
# 64 + len: one block to 111, two to 239 (47/48 and 175/176 are the
# edges where the padding spills into another block)
EDGE_LENGTHS = (0, 47, 48, 63, 64, 111, 120, 175, 176, 191, 192)



@pytest.fixture(scope="module")
def library():
    """Build on demand, as the native-codec tests do; without a
    toolchain the native cases skip and the Python ones still run."""
    if not rlcpack.build():
        pytest.skip("no g++ to build librlcpack.so")
    assert rlcpack.enabled()


needs_library = pytest.mark.usefixtures("library")


def make_batch(n, nkeys, seed, mlen="mixed"):
    """n random structurally sound entries over exactly nkeys distinct
    keys, repeats interleaved in no order."""
    rng = random.Random(seed)
    pool = [rng.randbytes(32) for _ in range(nkeys)]
    idx = list(range(nkeys)) + [rng.randrange(nkeys)
                                for _ in range(n - nkeys)]
    rng.shuffle(idx)
    pks = [pool[i] for i in idx]
    sigs = [rng.randbytes(32) + rng.randrange(L).to_bytes(32, "little")
            for _ in range(n)]
    if mlen == "mixed":
        msgs = [rng.randbytes(rng.choice(EDGE_LENGTHS + (1, 130, 300)))
                for _ in range(n)]
    else:
        msgs = [rng.randbytes(mlen) for _ in range(n)]
    return pks, msgs, sigs, rng.randbytes(16 * n)


def python_pack(pks, msgs, sigs, zblock):
    return ed._pack_rlc_python(pks, ed.parse_and_hash(pks, msgs, sigs),
                               zblock)


def assert_same(got, want):
    assert len(got) == len(want) == 6
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.flags["C_CONTIGUOUS"]
        assert np.array_equal(g, w)


# -- (a) bit for bit ---------------------------------------------------------

SIZES = [(n, k) for n in (2, 5, 58, 117, 3744)
         for k in sorted({1, 5, 175, n}) if k <= n]


@needs_library
@pytest.mark.parametrize("n,nkeys", SIZES)
def test_native_equals_python_over_sizes_and_key_repeats(n, nkeys):
    pks, msgs, sigs, z = make_batch(n, nkeys, seed=1000 * n + nkeys)
    assert len(set(pks)) == nkeys
    got = rlcpack.pack(pks, msgs, sigs, z)
    assert_same(got, python_pack(pks, msgs, sigs, z))
    # slot 0 is -B, then the keys in the order they first appear
    order = list(dict.fromkeys(pks))
    a_words = np.ascontiguousarray(got[0].T)
    assert a_words[0].tobytes() == ed._neg_b_encoding()
    assert [a_words[j].tobytes() for j in range(1, nkeys + 1)] == order


@needs_library
@pytest.mark.parametrize("mlen", EDGE_LENGTHS + ("mixed",))
def test_native_equals_python_at_every_padding_edge(mlen):
    pks, msgs, sigs, z = make_batch(58, 5, seed=77, mlen=mlen)
    assert_same(rlcpack.pack(pks, msgs, sigs, z),
                python_pack(pks, msgs, sigs, z))


@needs_library
def test_pack_rlc_draws_one_block_and_hands_it_to_the_library(monkeypatch):
    """pack_rlc's own draw: the arrays it returns are the oracle's for
    the block `secrets` gave it, and the top bit of every z is set."""
    import secrets

    pks, msgs, sigs, z = make_batch(9, 4, seed=5)
    z = bytes(b & 0x7F for b in z)              # no top bit of its own
    monkeypatch.setattr(secrets, "token_bytes",
                        lambda k: z if k == len(z) else os.urandom(k))
    got, packer = ed.pack_rlc_named(pks, msgs, sigs)
    assert packer == "native"
    assert_same(got, python_pack(pks, msgs, sigs, z))
    # digit 25 of a 128-bit z with its top bit set is 4..7
    assert got[4][0, :9].min() >= 4 and not got[5][0, :9].any()


# -- (b) structural rejects ----------------------------------------------------

def _spoil(kind, pks, sigs, at):
    if kind == "short_key":
        pks[at] = pks[at][:31]
    elif kind == "short_sig":
        sigs[at] = sigs[at][:63]
    elif kind == "s_is_L":
        sigs[at] = sigs[at][:32] + L.to_bytes(32, "little")
    else:
        sigs[at] = sigs[at][:32] + b"\xff" * 32


@pytest.mark.parametrize("at", (0, 20, 40))
@pytest.mark.parametrize("kind", ("short_key", "short_sig", "s_is_L",
                                  "s_is_all_ones"))
def test_a_structural_reject_anywhere_is_none_from_both(kind, at):
    pks, msgs, sigs, z = make_batch(41, 7, seed=3)
    _spoil(kind, pks, sigs, at)
    assert python_pack(pks, msgs, sigs, z) is None
    assert ed.pack_rlc(pks, msgs, sigs) is None
    if rlcpack.enabled():
        assert rlcpack.pack(pks, msgs, sigs, z) is None


@needs_library
def test_s_one_below_L_is_packed():
    pks, msgs, sigs, z = make_batch(3, 3, seed=4)
    sigs[1] = sigs[1][:32] + (L - 1).to_bytes(32, "little")
    assert_same(rlcpack.pack(pks, msgs, sigs, z),
                python_pack(pks, msgs, sigs, z))


# -- (c) the library's hash and reduction -------------------------------------------

@needs_library
@pytest.mark.parametrize("msg,digest", [
    (b"", "cf83e1357eefb8bdf1542850d66d8007d620e4050b5715dc83f4a921d36ce9ce"
          "47d0d13c5d85f2b0ff8318d2877eec2f63b931bd47417a81a538327af927da3e"),
    (b"abc", "ddaf35a193617abacc417349ae20413112e6fa4e89a97ea20a9eeee64b55d39a"
             "2192992a274fc1a836ba3c23a3feebbd454d4423643ce80e2a9ac94fa54ca49f"),
    (b"abcdefghbcdefghicdefghijdefghijkefghijklfghijklmghijklmn"
     b"hijklmnoijklmnopjklmnopqklmnopqrlmnopqrsmnopqrstnopqrstu",
     "8e959b75dae313da8cf4f72814fc143f8f7779c6eb9f7fa17299aeadb6889018"
     "501d289e4900f7e4331b99dec4b5433ac7d329eeb6dd26545e96e55b874be909"),
])
def test_sha512_known_answers(msg, digest):
    assert rlcpack.sha512(msg).hex() == digest


@needs_library
def test_sha512_equals_hashlib_over_every_length_to_three_blocks():
    rng = random.Random(11)
    for n in range(0, 400):
        msg = rng.randbytes(n)
        assert rlcpack.sha512(msg) == hashlib.sha512(msg).digest()


@needs_library
def test_reduction_mod_L_at_its_edges():
    rng = random.Random(12)
    edges = [0, 1, L - 1, L, L + 1, 2 * L - 1, 2 * L, 1 << 252, 1 << 256,
             (1 << 256) - 1, 1 << 320, (1 << 512) - 1, ((1 << 512) // L) * L,
             ((1 << 512) // L) * L - 1]
    for x in edges + [rng.getrandbits(512) for _ in range(2000)] \
            + [rng.randrange(1 << 260) * L + rng.choice((0, 1, L - 1))
               for _ in range(500)]:
        x %= 1 << 512
        got = rlcpack.sc_reduce(x.to_bytes(64, "little"))
        assert int.from_bytes(got, "little") == x % L


# -- (d) several threads at once ---------------------------------------------------

@needs_library
def test_threads_packing_at_once_each_equal_the_oracle():
    batches = [make_batch(n, k, seed=90 + i) for i, (n, k) in
               enumerate([(3744, 175), (2000, 2000), (58, 58), (901, 3)])]
    want = [python_pack(*b) for b in batches]
    errors = []

    def work(i):
        try:
            for _ in range(6):
                assert_same(rlcpack.pack(*batches[i]), want[i])
        except BaseException as e:          # noqa: BLE001 - reported below
            errors.append((i, e))

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(len(batches))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert errors == []


# -- (e) the fall back, and the counter that shows it ---------------------------------

def _counted(dm):
    m = dm.host_pack_signatures
    with m._mtx:
        return {k[0]: v for k, v in m._values.items()}


@pytest.fixture
def device_metrics():
    dm = libmetrics.DeviceMetrics(libmetrics.Registry(namespace="cometbft"))
    prev = libmetrics.device_metrics()
    libmetrics.set_device_metrics(dm)
    yield dm
    libmetrics.set_device_metrics(prev)


def test_without_the_library_python_packs_and_is_counted(
        monkeypatch, device_metrics):
    monkeypatch.setattr(rlcpack, "_lib", None)
    monkeypatch.setattr(rlcpack, "_failed", True)
    assert not rlcpack.enabled()
    pks, msgs, sigs, z = make_batch(12, 5, seed=8)
    assert rlcpack.pack(pks, msgs, sigs, z) is rlcpack.UNAVAILABLE
    import secrets

    monkeypatch.setattr(secrets, "token_bytes", lambda k: z)
    got, packer = ed.pack_rlc_named(pks, msgs, sigs)
    assert packer == "python"
    assert_same(got, python_pack(pks, msgs, sigs, z))
    assert _counted(device_metrics) == {"python": 12.0}


@needs_library
def test_the_counter_names_the_packer(device_metrics):
    pks, msgs, sigs, _ = make_batch(7, 7, seed=9)
    assert ed.pack_rlc(pks, msgs, sigs) is not None
    # a caller that brings its own hashes is packed in Python
    parsed = ed.parse_and_hash(pks, msgs, sigs)
    assert ed.pack_rlc(pks, [], [], parsed=parsed) is not None
    # a reject packs nothing
    assert ed.pack_rlc(pks, msgs, [sigs[0][:5]] + sigs[1:]) is None
    assert _counted(device_metrics) == {"native": 7.0, "python": 7.0}
    assert ('cometbft_device_host_pack_signatures_total{packer="native"} 7'
            in device_metrics.host_pack_signatures.collect())


# -- (f) a source newer than the library rebuilds it -----------------------------------

@needs_library
def test_a_source_newer_than_the_library_rebuilds_it(monkeypatch, tmp_path):
    src = os.path.dirname(rlcpack._LIB_PATH)
    for name in ("rlcpack.cc", "Makefile"):
        shutil.copy(os.path.join(src, name), tmp_path / name)
    lib_path = str(tmp_path / "librlcpack.so")
    monkeypatch.setattr(rlcpack, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(rlcpack, "_LIB_PATH", lib_path)
    monkeypatch.setattr(rlcpack, "_lib", None)
    monkeypatch.setattr(rlcpack, "_failed", False)
    assert rlcpack.enabled()                    # no library yet: built
    os.utime(lib_path, (1_000_000_000, 1_000_000_000))
    assert rlcpack._stale()                     # the copy's source is newer
    monkeypatch.setattr(rlcpack, "_lib", None)
    assert rlcpack.enabled()
    assert os.path.getmtime(lib_path) > 1_000_000_000
    assert not rlcpack._stale()
    pks, msgs, sigs, z = make_batch(5, 2, seed=10)
    assert_same(rlcpack.pack(pks, msgs, sigs, z),
                python_pack(pks, msgs, sigs, z))
