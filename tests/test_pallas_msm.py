"""Fused Pallas MSM kernels (ops/pallas_msm.py, ops/pallas_decompress.py)
vs the XLA reference path.

Two tiers, both CPU-safe:

1. KERNEL tests run the real kernels in interpret mode at small widths
   (blk<=16, few windows).  The kernels' correctness argument —
   predicated select cascade, pairwise tree, per-block linear
   accumulators, grid/index-map slicing — is width-independent, and
   interpret-mode COMPILE time scales with lanes x windows: the
   round-3 file ran 512-lane/26-window programs and cost 18 min +
   16 GB RSS, enough to OOM-segfault a full-suite run.  Small shapes
   keep the whole file in single-digit minutes and < 4 GB.

2. DISPATCH tests prove the product path (rlc_verify_kernel) actually
   routes through the kernels when the flags are on: the kernel entry
   is replaced at trace time with a spy that records the call and
   returns the XLA-branch value, so the end-to-end verdicts (accept +
   tampered-reject) are checked without paying a giant interpret
   compile.  Full-width semantic equality on real Mosaic is
   chip_smoke.py's job (reference verdicts on the chip).
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

@pytest.mark.parametrize("seed", [0, 1])
def test_select_tree_matches_xla(seed):
    rng = np.random.default_rng(seed)
    tab = dev._table17(_points(W))
    mag = jnp.asarray(rng.integers(0, 17, (W,), dtype=np.int32))
    neg = jnp.asarray(rng.integers(0, 2, (W,)) != 0)

    sel = dev._cond_neg_point(dev._select17(tab, mag), neg)
    want = dev._tree_reduce(sel, 1)
    got_part = pm.select_tree(tab, mag, neg, interpret=True, blk=W)
    got = dev._tree_reduce(jnp.asarray(got_part), 1)
    assert _pt_eq(want, got)


def test_select_tree_multiblock():
    """Two 8-lane programs over a 16-wide batch: the grid/index-map
    slicing, not just the in-block math."""
    rng = np.random.default_rng(7)
    tab = dev._table17(_points(W))
    mag = jnp.asarray(rng.integers(0, 17, (W,), dtype=np.int32))
    neg = jnp.asarray(rng.integers(0, 2, (W,)) != 0)

    sel = dev._cond_neg_point(dev._select17(tab, mag), neg)
    want = dev._tree_reduce(sel, 1)
    got_part = pm.select_tree(tab, mag, neg, interpret=True, blk=8)
    assert got_part.shape[-1] == 2 * pm._out_lanes(8)
    got = dev._tree_reduce(jnp.asarray(got_part), 1)
    assert _pt_eq(want, got)


def test_select_tree_identity_pads():
    """Zero digits select the identity row; an all-zero block must
    reduce to the identity (the pad-slot case)."""
    tab = dev._table17(_points(W))
    mag = jnp.zeros((W,), jnp.int32)
    neg = jnp.zeros((W,), bool)
    got_part = pm.select_tree(tab, mag, neg, interpret=True, blk=W)
    total = dev._tree_reduce(jnp.asarray(got_part), 1)
    assert bool(dev.point_is_identity(total)[0])


def test_msm_window_loop_matches_scan():
    """The whole-window-loop kernel (per-block accumulators + fused
    doublings) equals the XLA shared-doubling scan over the same
    digits — the linearity argument in _window_loop_kernel, checked."""
    w, nwin = 8, 4                # j==0 init + 3 accumulate/double steps
    rng = np.random.default_rng(3)
    tab = dev._table17(_points(w))
    mags = jnp.asarray(rng.integers(0, 17, (nwin, w), dtype=np.int32))
    negs = jnp.asarray(rng.integers(0, 2, (nwin, w)) != 0)

    want = dev._msm_scan(tab, mags, negs)          # XLA reference
    partials = pm.msm_window_loop(tab, mags, negs, interpret=True, blk=w)
    got = dev._tree_reduce(jnp.asarray(partials), 1)
    assert _pt_eq(want, got)


def test_msm_window_loop_multiblock():
    """Per-block accumulators across TWO blocks: each block runs its
    own doubling chain; the block sums must still equal the global
    accumulator (the linearity argument's cross-block half)."""
    nwin = 3
    rng = np.random.default_rng(11)
    tab = dev._table17(_points(W))
    mags = jnp.asarray(rng.integers(0, 17, (nwin, W), dtype=np.int32))
    negs = jnp.asarray(rng.integers(0, 2, (nwin, W)) != 0)

    want = dev._msm_scan(tab, mags, negs)
    partials = pm.msm_window_loop(tab, mags, negs, interpret=True, blk=8)
    assert partials.shape[-1] == 2 * pm._out_lanes(8)
    got = dev._tree_reduce(jnp.asarray(partials), 1)
    assert _pt_eq(want, got)


def _xla_epilogue_verdict(pa, pr):
    """The XLA reference of the fold kernel: reduce, combine, cofactor
    8, identity."""
    total = dev.point_add(dev._tree_reduce(pa, 1), dev._tree_reduce(pr, 1))
    for _ in range(3):
        total = dev.point_double(total, with_t=False)
    return bool(dev.point_is_identity(total)[0])


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


def test_rlc_dispatches_fold_verify(monkeypatch):
    """With USE_PALLAS_FOLD on, the RLC verdict routes through
    fold_verify with both sides' partial tensors, and accept/tampered-
    reject hold around the seam."""
    import cometbft_tpu.ops.pallas_msm as pmod

    fold_calls, msm_calls = [], []

    def msm_spy(tab, mags, negs, interpret=False, blk=None):
        msm_calls.append(tab.shape)
        monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", False)
        try:
            return dev._msm_scan(tab, mags, negs)    # (4, 20, 1) partial
        finally:
            monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", True)

    def fold_spy(pa, pr, interpret=False):
        fold_calls.append((pa.shape, pr.shape))
        ta = dev._tree_reduce(pa, 1)
        tr = dev._tree_reduce(pr, 1)
        total = dev.point_add(ta, tr)
        for _ in range(3):
            total = dev.point_double(total, with_t=False)
        return dev.point_is_identity(total)[0]

    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)
    monkeypatch.setattr(pmod, "msm_window_loop", msm_spy)
    monkeypatch.setattr(pmod, "fold_verify", fold_spy)
    monkeypatch.setattr(pmod, "BLK", 8)
    monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", True)
    monkeypatch.setattr(dev, "USE_PALLAS_MSM_MAJOR", False)
    monkeypatch.setattr(dev, "USE_PALLAS_FOLD", True)
    monkeypatch.setattr(dev, "USE_PALLAS_TABLE", False)
    monkeypatch.setattr(dev, "USE_PALLAS_DECOMPRESS", False)

    good, bad = _rlc_verdicts(tamper_idx=3)
    assert good and not bad
    assert fold_calls                     # epilogue went through the seam
    assert len(msm_calls) >= 2            # both MSM sides produced partials


@pytest.mark.slow
def test_msm_window_major_matches_scan():
    """The window-major kernel (blocks inner, ONE global accumulator,
    doublings once per window) equals the XLA shared-doubling scan —
    single block (init/close coincide) and multiblock (the wacc
    scratch accumulation across i)."""
    nwin = 4
    rng = np.random.default_rng(13)
    tab = dev._table17(_points(W))
    mags = jnp.asarray(rng.integers(0, 17, (nwin, W), dtype=np.int32))
    negs = jnp.asarray(rng.integers(0, 2, (nwin, W)) != 0)
    want = dev._msm_scan(tab, mags, negs)

    got1 = pm.msm_window_major(tab, mags, negs, interpret=True, blk=W)
    assert got1.shape[-1] == pm._out_lanes(W)
    assert _pt_eq(want, dev._tree_reduce(jnp.asarray(got1), 1))

    got2 = pm.msm_window_major(tab, mags, negs, interpret=True, blk=8)
    assert got2.shape[-1] == pm._out_lanes(8)
    assert _pt_eq(want, dev._tree_reduce(jnp.asarray(got2), 1))


def test_msm_scan_dispatches_window_major(monkeypatch):
    """USE_PALLAS_MSM_MAJOR routes _msm_scan through msm_window_major
    and takes precedence over the window-loop kernel."""
    import cometbft_tpu.ops.pallas_msm as pmod

    calls = []

    def spy(tab, mags, negs, interpret=False, blk=None):
        calls.append((tab.shape, blk))
        monkeypatch.setattr(dev, "USE_PALLAS_MSM_MAJOR", False)
        monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", False)
        try:
            return dev._msm_scan(tab, mags, negs)
        finally:
            monkeypatch.setattr(dev, "USE_PALLAS_MSM_MAJOR", True)
            monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", True)

    nwin = 3
    rng = np.random.default_rng(4)
    tab = dev._table17(_points(W))
    mags = jnp.asarray(rng.integers(0, 17, (nwin, W), dtype=np.int32))
    negs = jnp.asarray(rng.integers(0, 2, (nwin, W)) != 0)
    want = dev._msm_scan(tab, mags, negs)

    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)
    monkeypatch.setattr(pmod, "msm_window_major", spy)
    monkeypatch.setattr(pmod, "BLK", 8)
    monkeypatch.setattr(dev, "USE_PALLAS_MSM_MAJOR", True)
    monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", True)
    got = dev._msm_scan(tab, mags, negs)
    assert calls == [((17, 4, 20, W), 8)]
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


def _rlc_verdicts(tamper_idx):
    """Pack an 8-sig batch, run rlc_verify_kernel jitted, return
    (clean verdict, tampered verdict).  The pjit executable cache is
    keyed on the underlying function + shapes, so an executable traced
    by a PREVIOUS dispatch test (same 8-sig shapes, different
    monkeypatched spies/flags) would silently win — clear it."""
    from cometbft_tpu.crypto import ed25519 as ed

    jax.clear_caches()
    pks, msgs, sigs = _sign_batch(8)
    fn = jax.jit(dev.rlc_verify_kernel)
    good = bool(np.asarray(fn(*ed.pack_rlc(pks, msgs, sigs))))
    i = tamper_idx
    sigs[i] = sigs[i][:20] + bytes([sigs[i][20] ^ 1]) + sigs[i][21:]
    bad = bool(np.asarray(fn(*ed.pack_rlc(pks, msgs, sigs))))
    return good, bad


def test_rlc_dispatches_pallas_kernels(monkeypatch):
    """With USE_PALLAS_MSM_LOOP and USE_PALLAS_DECOMPRESS on and widths
    divisible by BLK, BOTH MSM sides route through msm_window_loop and
    both decompressions through the fused kernel, and the verdict
    plumbing (accept + tampered reject) holds around the kernel seams.
    One jitted program covers both flags: a separate test per flag
    costs an extra ~3 min RLC compile for no additional coverage."""
    import cometbft_tpu.ops.pallas_decompress as pdmod
    import cometbft_tpu.ops.pallas_msm as pmod

    msm_calls, dec_calls = [], []

    def msm_spy(tab, mags, negs, interpret=False, blk=None):
        msm_calls.append((tab.shape, mags.shape))
        # XLA-branch value, computed by flipping the flag for the
        # duration of this trace-time call
        monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", False)
        try:
            return dev._msm_scan(tab, mags, negs)    # (4, 20, 1)
        finally:
            monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", True)

    def dec_spy(enc_words, interpret=False, blk=None):
        dec_calls.append(enc_words.shape)
        monkeypatch.setattr(dev, "USE_PALLAS_DECOMPRESS", False)
        try:
            return dev.decompress(enc_words)
        finally:
            monkeypatch.setattr(dev, "USE_PALLAS_DECOMPRESS", True)

    tab_calls = []

    def tab_spy(pt, interpret=False, blk=None):
        tab_calls.append(pt.shape)
        return dev._table17(dev.point_neg(pt))

    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)
    monkeypatch.setattr(pmod, "msm_window_loop", msm_spy)
    monkeypatch.setattr(pmod, "table17_neg", tab_spy)
    monkeypatch.setattr(pmod, "BLK", 8)
    monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", True)
    # window-major and the fold epilogue (defaults ON since r4b)
    # supersede the scan path this test exercises; the fold has its
    # own dispatch test below
    monkeypatch.setattr(dev, "USE_PALLAS_MSM_MAJOR", False)
    monkeypatch.setattr(dev, "USE_PALLAS_FOLD", False)
    monkeypatch.setattr(dev, "USE_PALLAS_TABLE", True)
    monkeypatch.setattr(pdmod, "decompress", dec_spy)
    monkeypatch.setattr(pdmod, "BLK", 8)
    monkeypatch.setattr(dev, "USE_PALLAS_DECOMPRESS", True)

    good, bad = _rlc_verdicts(tamper_idx=5)
    assert good and not bad
    # A side (52 windows, width 16) and R side (26 windows, width 8)
    assert ((17, 4, 20, 16), (52, 16)) in msm_calls
    assert ((17, 4, 20, 8), (26, 8)) in msm_calls
    assert (8, 16) in dec_calls and (8, 8) in dec_calls
    assert (4, 20, 16) in tab_calls and (4, 20, 8) in tab_calls


def test_msm_scan_dispatches_select_tree(monkeypatch):
    """USE_PALLAS_TREE routes every window's contribution through
    select_tree with the partial-count contract intact.  Driven at the
    _msm_scan seam (eager, no fresh RLC compile) — the RLC plumbing
    above is flag-independent."""
    import cometbft_tpu.ops.pallas_msm as pmod

    calls = []

    def spy(tab, mag, neg, interpret=False, blk=None):
        calls.append(tab.shape)
        npart = (tab.shape[-1] // 8) * pmod._out_lanes(8)
        contrib = dev._cond_neg_point(dev._select17(tab, mag), neg)
        return dev._tree_reduce(contrib, npart)

    nwin = 3
    rng = np.random.default_rng(2)
    tab = dev._table17(_points(W))
    mags = jnp.asarray(rng.integers(0, 17, (nwin, W), dtype=np.int32))
    negs = jnp.asarray(rng.integers(0, 2, (nwin, W)) != 0)
    want = dev._msm_scan(tab, mags, negs)

    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)
    monkeypatch.setattr(pmod, "select_tree", spy)
    monkeypatch.setattr(pmod, "BLK", 8)
    monkeypatch.setattr(dev, "USE_PALLAS_TREE", True)
    monkeypatch.setattr(dev, "USE_PALLAS_MSM_LOOP", False)
    monkeypatch.setattr(dev, "USE_PALLAS_MSM_MAJOR", False)
    got = dev._msm_scan(tab, mags, negs)
    # the window body is TRACED once inside lax.scan and reused for
    # every window; one recorded call proves the routing
    assert calls == [(17, 4, 20, W)]
    assert _pt_eq(want, got)


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
    """USE_PALLAS_TABLE routes _msm_tables through table17_neg."""
    import cometbft_tpu.ops.pallas_msm as pmod

    calls = []

    def spy(pt, interpret=False, blk=None):
        calls.append(pt.shape)
        return dev._table17(dev.point_neg(pt))

    monkeypatch.setattr(dev, "_pallas_capable", lambda: True)
    monkeypatch.setattr(pmod, "table17_neg", spy)
    monkeypatch.setattr(pmod, "BLK", 8)
    monkeypatch.setattr(dev, "USE_PALLAS_TABLE", True)
    monkeypatch.setattr(dev, "USE_PALLAS_DECOMPRESS", False)

    pks, _, _ = _sign_batch(8)
    words = np.stack([np.frombuffer(pk, dtype="<u4") for pk in pks],
                     axis=1)                        # (8, 8) LE words
    tab, ok = dev._msm_tables(jnp.asarray(words))
    assert calls and calls[0] == (4, 20, 8)
    assert bool(ok)


# -- r4 advisor regressions ------------------------------------------------

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


def test_group_for_divisor_degradation():
    """Requested window groups degrade to the largest divisor of the
    side's window count (52-window A sides vs 26-window R sides)."""
    assert pm.group_for(6, 4) == 3
    assert pm.group_for(52, 8) == 4
    assert pm.group_for(52, 16) == 13
    assert pm.group_for(26, 16) == 13
    assert pm.group_for(26, 4) == 2
    assert pm.group_for(7, 4) == 1      # prime: grouped == ungrouped


@pytest.mark.slow
def test_msm_window_major_grouped_matches_scan():
    """The grouped window-major kernel (G windows per table fetch, per-
    window VMEM scratch accumulators, fori_loop group-close doubling
    chain) equals the XLA shared-doubling scan.  Slow tier: each
    interpret compile is ~3.5 min on one core.  Combos cover multiblock wacc
    accumulation (blk 8), divisor degradation (4 -> 3), the jg != 0
    later-group close, single-block grids, and group == nwin."""
    nwin = 6
    rng = np.random.default_rng(29)
    tab = dev._table17(_points(W))
    mags = jnp.asarray(rng.integers(0, 17, (nwin, W), dtype=np.int32))
    negs = jnp.asarray(rng.integers(0, 2, (nwin, W)) != 0)
    want = dev._msm_scan(tab, mags, negs)
    for blk, grp in ((8, 4), (W, 2), (8, 6)):
        got = pm.msm_window_major(tab, mags, negs, interpret=True,
                                  blk=blk, group=grp)
        assert got.shape[-1] == pm._out_lanes(blk), (blk, grp)
        assert _pt_eq(want, dev._tree_reduce(jnp.asarray(got), 1)), \
            (blk, grp)
