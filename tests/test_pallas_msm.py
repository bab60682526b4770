"""The four shipping Pallas kernels (ops/pallas_msm.py,
ops/pallas_decompress.py) vs the XLA reference path, and the one
predicate that chooses between them (ops/ed25519._pallas_blk).

Two tiers, both CPU-safe:

1. KERNEL tests run the real kernels in interpret mode at small widths
   (blk<=16, few windows).  The kernels' correctness argument —
   predicated select cascade, pairwise tree, one global accumulator,
   grid/index-map slicing — is width-independent, and interpret-mode
   COMPILE time scales with lanes x windows: 512-lane/26-window
   programs cost 18 min + 16 GB RSS, enough to OOM-segfault a
   full-suite run.  Small shapes keep the whole file in single-digit
   minutes and < 4 GB.

2. DISPATCH tests prove the product path (rlc_verify_kernel) routes
   through the kernels wherever _pallas_blk gives a side a block —
   _pallas_capable patched true, pallas_msm.BLK shrunk — and through
   the XLA path on a side it gives none: each kernel entry is replaced
   at trace time with a spy that records the call and returns the
   XLA path's value under its own name (_msm_scan_xla,
   _decompress_xla, _table17), so the end-to-end verdicts (accept +
   tampered-reject) are checked without paying a giant interpret
   compile.  Full-width semantic equality on real Mosaic is the
   chip's job (chip_smoke.py, the benchmark's `correct`).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import ed25519 as dev
from cometbft_tpu.ops import fe
from cometbft_tpu.ops import pallas_msm as pm

W = 16          # kernel-test batch width


def _points(n, distinct=8):
    """(4, 20, n) extended points: multiples of B, tiled."""
    cols = []
    for i in range(distinct):
        x, y, z, t = ref.point_mul(7919 * (i + 1) + 3, ref.B)
        zi = pow(z, fe.P - 2, fe.P)
        x, y = x * zi % fe.P, y * zi % fe.P
        cols.append((x, y, 1, x * y % fe.P))
    arrs = []
    for coord in range(4):
        a = np.stack([fe.int_to_limbs(cols[i % distinct][coord])
                      for i in range(n)], axis=1)
        arrs.append(jnp.asarray(a))
    return jnp.stack(arrs, axis=0)


def _pt_eq(a, b):
    """Projective equality of two (4,20,1) points."""
    x1z2 = fe.freeze(fe.mul(a[0], b[2]))
    x2z1 = fe.freeze(fe.mul(b[0], a[2]))
    y1z2 = fe.freeze(fe.mul(a[1], b[2]))
    y2z1 = fe.freeze(fe.mul(b[1], a[2]))
    return bool(jnp.all(x1z2 == x2z1)) and bool(jnp.all(y1z2 == y2z1))


# -- tier 1: the kernels themselves, interpret mode ------------------------

def _msm_case(w, nwin, seed):
    rng = np.random.default_rng(seed)
    tab = dev._table17(_points(w))
    mags = jnp.asarray(rng.integers(0, 17, (nwin, w), dtype=np.int32))
    negs = jnp.asarray(rng.integers(0, 2, (nwin, w)) != 0)
    return tab, mags, negs


@pytest.fixture(scope="module")
def scan_case():
    """One table, one digit tensor and the XLA scan's point over them
    for the cases below: the reference compiles once."""
    tab, mags, negs = _msm_case(W, 3, seed=13)
    return tab, mags, negs, dev._msm_scan_xla(tab, mags, negs)


@pytest.mark.parametrize("blk, zero", [
    (W, False),     # blk == W: one block, init and close coincide
    (8, False),     # two blocks: the wacc accumulation across i
    (W, True),      # pads: the first case's compile, reused
], ids=["one_block", "two_blocks", "zero_digits"])
def test_msm_window_major_matches_scan(scan_case, blk, zero):
    """The window-major kernel (blocks inner, ONE global accumulator,
    doublings once per window) equals the XLA shared-doubling scan over
    the same digits; all-zero digits select the identity row in every
    window and sum to the identity (the pad-slot case)."""
    tab, mags, negs, want = scan_case
    if zero:
        mags, negs = jnp.zeros_like(mags), jnp.zeros_like(negs)
    got = pm.msm_window_major(tab, mags, negs, interpret=True, blk=blk)
    assert got.shape[-1] == pm._out_lanes(blk)
    total = dev._tree_reduce(jnp.asarray(got), 1)
    if zero:
        assert bool(dev.point_is_identity(total)[0])
    else:
        assert _pt_eq(want, total)


def _xla_epilogue(pa, pr):
    """The XLA reference of the fold kernel: reduce, combine, cofactor
    8, identity."""
    total = dev.point_add(dev._tree_reduce(pa, 1), dev._tree_reduce(pr, 1))
    for _ in range(3):
        total = dev.point_double(total, with_t=False)
    return dev.point_is_identity(total)[0]


def _xla_epilogue_verdict(pa, pr):
    return bool(_xla_epilogue(pa, pr))


@pytest.mark.slow
def test_fold_verify_matches_xla():
    """Fused fold/verify epilogue vs the XLA reference at tile 8 (the
    halving/butterfly argument is width-independent; real Mosaic at
    tile 128 is covered by tests/test_tpu_compile.py): the identity
    case (R side = negated A side) must accept, the non-identity case
    must reject."""
    pa = _points(16, distinct=8)     # 2*tile: exercises the halving
    pr_neg = dev.point_neg(pa)
    # accept: sum(A) + sum(-A) = identity
    assert _xla_epilogue_verdict(pa, pr_neg) is True
    got = bool(pm.fold_verify(pa, pr_neg, interpret=True, tile=8))
    assert got is True
    # reject: sum(A) + sum(A) = 2*sum != identity (B-multiples, no
    # torsion) — same shapes as the accept case, so the interpret
    # compile is reused (the shape-keyed jit cache)
    assert _xla_epilogue_verdict(pa, pa) is False
    got = bool(pm.fold_verify(pa, pa, interpret=True, tile=8))
    assert got is False


@pytest.mark.slow
def test_fold_verify_chunk_sum_width():
    """A 3*tile-lane partial tensor takes the chunk-sum branch of
    _tree_to_tile (m odd after halving).  tile 4 keeps the interpret
    compile small; the branch logic is tile-independent."""
    pa = _points(12, distinct=4)
    pr = dev.point_neg(pa)
    assert bool(pm.fold_verify(pa, pr, interpret=True, tile=4)) is True


def test_msm_scan_dispatches_window_major(monkeypatch):
    """Where _pallas_blk gives the width a block, one MSM side
    (_msm_side) is msm_window_major at that block."""
    calls = _spy_kernels(monkeypatch, blk=8)
    tab, mags, negs = _msm_case(W, 3, seed=4)
    want = dev._msm_scan_xla(tab, mags, negs)
    got = dev._tree_reduce(dev._msm_side(tab, mags, negs), 1)
    assert calls["msm"] == [((17, 4, 20, W), (3, W), 8)]
    assert _pt_eq(want, got)


@pytest.mark.slow
def test_pallas_decompress_matches_xla():
    """Fused decompress vs ops/ed25519.decompress on valid encodings,
    torsion/low-order points, and invalid (non-square) encodings."""
    from cometbft_tpu.ops import pallas_decompress as pd

    encs = []
    for i in range(W - 3):
        pt = ref.point_mul(6151 * i + 11, ref.B)
        encs.append(ref.point_compress(pt))
    # identity, an 8-torsion point, and a junk non-point encoding
    encs.append(ref.point_compress((0, 1, 1, 0)))
    encs.append(bytes.fromhex(
        "26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc05"))
    encs.append(b"\x13" * 31 + b"\x80")     # x==0 with sign bit: reject
    words = jnp.asarray(np.stack(
        [np.frombuffer(e, dtype=np.uint32) for e in encs], axis=1))

    want_pt, want_ok = dev.decompress(words)
    got_pt, got_ok = pd.decompress(words, interpret=True, blk=W)
    assert np.array_equal(np.asarray(want_ok), np.asarray(got_ok))
    ok = np.asarray(want_ok)
    for i in range(W):
        if ok[i]:
            assert _pt_eq(jnp.asarray(np.asarray(want_pt)[..., i:i + 1]),
                          jnp.asarray(np.asarray(got_pt)[..., i:i + 1])), i


# -- tier 2: product-path dispatch -----------------------------------------

def _sign_batch(n):
    """n (pubkey, msg, sig) triples via the cryptography oracle."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey)
    from cryptography.hazmat.primitives.serialization import (
        Encoding, PublicFormat)

    pks, msgs, sigs = [], [], []
    for i in range(n):
        seed = bytes([i % 250 + 1]) * 32
        k = Ed25519PrivateKey.from_private_bytes(seed)
        m = i.to_bytes(4, "little") * 8
        pks.append(k.public_key().public_bytes(
            Encoding.Raw, PublicFormat.Raw))
        msgs.append(m)
        sigs.append(k.sign(m))
    return pks, msgs, sigs


def _spy_kernels(monkeypatch, blk):
    """The chip's choice of kernel at block `blk`, with every kernel
    entry a spy that records its call and returns the XLA path's value.
    Returns the call log, one list a kernel."""
    import cometbft_tpu.ops.pallas_decompress as pdmod

    calls = {"decompress": [], "tables": [], "msm": [], "fold": []}

    def dec_spy(enc_words, interpret=False, blk=None):
        calls["decompress"].append(enc_words.shape)
        return dev._decompress_xla(enc_words)

    def tab_spy(pt, interpret=False, blk=None):
        calls["tables"].append(pt.shape)
        return dev._table17(dev.point_neg(pt))

    def msm_spy(tab, mags, negs, interpret=False, blk=None):
        calls["msm"].append((tab.shape, mags.shape, blk))
        return dev._msm_scan_xla(tab, mags, negs)    # (4, 20, 1) partial

    def fold_spy(pa, pr, interpret=False):
        calls["fold"].append((pa.shape, pr.shape))
        return _xla_epilogue(pa, pr)

    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)
    monkeypatch.setattr(pm, "BLK", blk)
    monkeypatch.setattr(pdmod, "BLK", blk)
    monkeypatch.setattr(pdmod, "decompress", dec_spy)
    monkeypatch.setattr(pm, "table17_neg", tab_spy)
    monkeypatch.setattr(pm, "msm_window_major", msm_spy)
    monkeypatch.setattr(pm, "fold_verify", fold_spy)
    return calls


def _rlc_run(blk, tamper_idx):
    """Pack an 8-sig batch at the widths the CPU packs (A 16, R 8) and
    run rlc_verify_kernel jitted on it, clean and tampered, with the
    chip's choice of kernel at block `blk` spied: (clean verdict,
    tampered verdict, call log).  The pjit executable cache is keyed on
    the underlying function + shapes, so an executable traced by a
    PREVIOUS run (same shapes, another block) would silently win —
    clear it."""
    from cometbft_tpu.crypto import ed25519 as ed

    pks, msgs, sigs = _sign_batch(8)
    good_args = ed.pack_rlc(pks, msgs, sigs)
    i = tamper_idx
    sigs[i] = sigs[i][:20] + bytes([sigs[i][20] ^ 1]) + sigs[i][21:]
    bad_args = ed.pack_rlc(pks, msgs, sigs)
    with pytest.MonkeyPatch.context() as mp:
        calls = _spy_kernels(mp, blk)
        jax.clear_caches()
        fn = jax.jit(dev.rlc_verify_kernel)
        good = bool(np.asarray(fn(*good_args)))
        bad = bool(np.asarray(fn(*bad_args)))
    jax.clear_caches()
    return good, bad, calls


@pytest.fixture(scope="module")
def all_pallas_run():
    """One trace and compile for the two tests that read it: block 8
    divides both widths."""
    return _rlc_run(blk=8, tamper_idx=3)


def test_rlc_dispatches_fold_verify(all_pallas_run):
    """Both sides with a block: the RLC verdict routes through
    fold_verify with both sides' accumulators, and accept/tampered-
    reject hold around the seam."""
    good, bad, calls = all_pallas_run
    assert good and not bad
    # one accumulator a side, as the msm spy handed it over
    assert calls["fold"] == [((4, 20, 1), (4, 20, 1))]
    assert len(calls["msm"]) == 2


def test_rlc_dispatches_pallas_kernels(all_pallas_run):
    """At widths a block divides, BOTH MSM sides route through
    msm_window_major, both decompressions and both table builds through
    their kernels, and the verdict plumbing (accept + tampered reject)
    holds around the kernel seams."""
    good, bad, calls = all_pallas_run
    assert good and not bad
    # A side (52 windows, width 16) and R side (26 windows, width 8)
    assert calls["msm"] == [((17, 4, 20, 16), (52, 16), 8),
                            ((17, 4, 20, 8), (26, 8), 8)]
    assert calls["decompress"] == [(8, 16), (8, 8)]
    assert calls["tables"] == [(4, 20, 16), (4, 20, 8)]


def test_rlc_mixed_program_takes_xla_epilogue():
    """One side with a block, one without (block 16: the A side's 16
    lanes have it, the R side's 8 do not): the blocked side alone runs
    the kernels, the other the XLA code, and the verdict comes from the
    shared epilogue's XLA branch — fold_verify is never entered — for
    a good batch and for a tampered one."""
    good, bad, calls = _rlc_run(blk=16, tamper_idx=5)
    assert good and not bad
    assert calls["msm"] == [((17, 4, 20, 16), (52, 16), 16)]
    assert calls["decompress"] == [(8, 16)]
    assert calls["tables"] == [(4, 20, 16)]
    assert calls["fold"] == []


@pytest.mark.parametrize("k, n", [(64, 64), (192, 6144), (6144, 192)])
def test_rlc_kernel_plan_blockless_side(monkeypatch, k, n):
    """rlc_kernel_plan stage by stage on programs with a side no block
    divides: that side reads xla in every stage, the other pallas, and
    the fold xla."""
    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)
    plan = dev.rlc_kernel_plan(k, n)
    for side, w in (("a", k), ("r", n)):
        want = "pallas" if w % 128 == 0 else "xla"
        assert plan[side]["width"] == w
        assert (plan[side]["blk"] is None) == (want == "xla")
        assert [plan[side][st] for st in ("decompress", "tables", "msm")] \
            == [want] * 3, (side, plan)
    assert plan["fold"] == "xla"
    assert dev.rlc_kernel_name(k, n) == "xla"


@pytest.mark.slow
def test_pallas_table17_neg_matches_xla():
    """Fused table-build kernel vs _table17(point_neg(p)): every row
    k*(-P) for k=0..16, both blocks of a 2-block grid.  One jitted
    whole-table frozen comparison — the per-lane _pt_eq loop this
    replaces paid 68 eager tiny-shape compiles (the file's slowest
    test by 3x).  Both paths produce Z=1 extended points, so frozen
    coordinate equality is exact."""
    w = 16
    pts = _points(w)
    want = dev._table17(dev.point_neg(pts))
    got = pm.table17_neg(pts, interpret=True, blk=8)
    assert got.shape == want.shape
    tab_eq = jax.jit(lambda a, b: jnp.all(
        fe.freeze(a.transpose(2, 0, 1, 3))
        == fe.freeze(b.transpose(2, 0, 1, 3))))
    assert bool(np.asarray(tab_eq(jnp.asarray(got), want)))


def test_msm_tables_dispatches_pallas_table(monkeypatch):
    """Where _pallas_blk gives the width a block, _msm_tables is the
    decompress kernel and table17_neg."""
    calls = _spy_kernels(monkeypatch, blk=8)
    pks, _, _ = _sign_batch(8)
    words = np.stack([np.frombuffer(pk, dtype="<u4") for pk in pks],
                     axis=1)                        # (8, 8) LE words
    tab, ok = dev._msm_tables(jnp.asarray(words))
    assert calls["tables"] == [(4, 20, 8)]
    assert calls["decompress"] == [(8, 8)]
    assert bool(ok)


# -- blocks and widths ------------------------------------------------------

def test_blk_for_non_pow2_override(monkeypatch):
    """A non-pow2 BLK override (e.g. 384) must still find the pow2
    candidates below it instead of silently losing the Pallas path
    (r4 advisor: 384->192->96 skipped the 128 floor entirely)."""
    monkeypatch.setattr(pm, "BLK", 384)
    assert pm.blk_for(4096) == 256
    assert pm.blk_for(128) == 128
    # >= 128 blocks are pow2-only: the in-kernel tree halves exactly
    # onto the 128-lane scratch, which 384 -> 192 -> 96 would miss
    assert pm.blk_for(768) == 256
    monkeypatch.setattr(pm, "BLK", 512)
    assert pm.blk_for(4096) == 512
    monkeypatch.setattr(pm, "BLK", 96)   # sub-128 test blocks: any size
    assert pm.blk_for(64) == 64
    assert pm.blk_for(192) == 96
    monkeypatch.setattr(pm, "BLK", -5)
    assert pm.blk_for(4096) is None


def test_prefold_odd_tile_width(monkeypatch):
    """_prefold on widths that are ODD multiples of 128 above the fold
    bound must chunk-sum instead of asserting (r4 advisor: W=65*512
    window-loop partials -> 8320 lanes, first halving 4160 % 128 != 0).
    Shrunk analog: bound=8 'lanes' with tile alignment 128 replaced by
    the real 128 via a 3*128-wide tensor and a monkeypatched bound."""
    monkeypatch.setattr(pm, "MAX_FOLD_LANES", 256)
    pts = _points(3 * 128, distinct=6)          # odd multiple of 128
    want = dev._tree_reduce(pts, 1)
    got = dev._prefold(pts)
    assert got.shape[-1] == 256
    assert _pt_eq(want, dev._tree_reduce(got, 1))
