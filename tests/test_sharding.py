"""Multi-chip sharded verification (ops/sharding.py) on the 8-device
virtual CPU mesh from conftest — the production path behind
crypto/batch's per-signature verdict fallback."""

import numpy as np
import pytest

import jax

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.ops import ed25519 as dev
from cometbft_tpu.ops import sharding


def _sigs(n):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey)
    from cryptography.hazmat.primitives.serialization import (
        Encoding, PublicFormat)

    pks, msgs, sigs = [], [], []
    for i in range(n):
        seed = bytes([i % 251 + 1, i // 251 + 1]) + bytes(30)
        k = Ed25519PrivateKey.from_private_bytes(seed)
        m = i.to_bytes(4, "little") * 6
        pks.append(k.public_key().public_bytes(
            Encoding.Raw, PublicFormat.Raw))
        msgs.append(m)
        sigs.append(k.sign(m))
    return pks, msgs, sigs


def test_mesh_has_8_devices():
    assert sharding.device_count() == 8


def test_sharded_matches_single_device():
    pks, msgs, sigs = _sigs(14)
    sigs[5] = sigs[5][:8] + bytes([sigs[5][8] ^ 1]) + sigs[5][9:]
    a, r, s, h, valid = ed.pack_batch(pks, msgs, sigs, 16)
    single = np.asarray(dev.verify_batch_device(a, r, s, h)) & valid
    shard = np.asarray(sharding.verify_batch_sharded(a, r, s, h)) & valid
    assert (single == shard).all()
    assert not shard[5] and shard[:5].all() and shard[6:14].all()


def test_batch_verifier_uses_sharded_path():
    """The crypto/batch fallback (per-signature verdict localization)
    rides the sharded kernel on a multi-device mesh."""
    from cometbft_tpu.crypto import batch as cb
    from cometbft_tpu.crypto.ed25519 import PubKey

    pks, msgs, sigs = _sigs(10)
    sigs[2] = sigs[2][:9] + bytes([sigs[2][9] ^ 0x80]) + sigs[2][10:]
    bv = cb.TpuEd25519BatchVerifier()
    for pk, m, s in zip(pks, msgs, sigs):
        bv.add(PubKey(pk), m, s)
    ok, verdicts = bv.verify()
    assert not ok
    assert verdicts[2] is False or verdicts[2] == False  # noqa: E712
    assert sum(bool(v) for v in verdicts) == 9


@pytest.mark.slow
def test_sharded_pallas_msm_interpret():
    """ops/msm_shard.sharded_msm on the 8-device CPU mesh, interpret
    mode: the SHIPPING window-major kernel runs per device on its lane
    shard; the all_gather + group-addition fold must equal the single-
    device XLA scan (the driver's dryrun phase 4, as a local
    regression test).  Slow tier: ~9-10 min wall on one core
    (shard_map multiplies the interpret compile)."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import msm_shard
    from cometbft_tpu.ops import fe

    n_dev = sharding.device_count()
    w = 4 * n_dev
    pks, msgs, sigs_ = _sigs(w)
    enc = np.stack([np.frombuffer(pk, dtype="<u4") for pk in pks],
                   axis=1)
    tab, ok = dev._msm_tables(jnp.asarray(enc))
    assert bool(np.asarray(ok))
    rng = np.random.default_rng(3)
    nwin = 4
    mags = jnp.asarray(rng.integers(0, 17, (nwin, w), dtype=np.int32))
    negs = jnp.asarray(rng.integers(0, 2, (nwin, w)) != 0)
    want = dev._msm_scan_xla(tab, mags, negs)
    got = msm_shard.sharded_msm(tab, mags, negs,
                                mesh=sharding._mesh(),
                                interpret=True, blk=4)
    x_eq = np.asarray(fe.freeze(fe.mul(got[0], want[2]))) \
        == np.asarray(fe.freeze(fe.mul(want[0], got[2])))
    y_eq = np.asarray(fe.freeze(fe.mul(got[1], want[2]))) \
        == np.asarray(fe.freeze(fe.mul(want[1], got[2])))
    assert x_eq.all() and y_eq.all()
