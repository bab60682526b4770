"""Pallas TPU kernels of the RLC verify path's MSM: the 17-row table
build (table17_neg), the window-major Straus MSM (msm_window_major) and
the fold/verify epilogue (fold_verify).  ops/pallas_decompress.py holds
the fourth shipping kernel and borrows the field ops below.

Why kernels: under XLA every point_add level at shrinking widths
dispatches ~20 separate (20, W) elementwise fusions whose fixed costs
dominate below ~2048 lanes.  Each kernel here keeps its whole pipeline
— 17-way predicated select from the window table, signed-digit
negation, the log-depth tree of extended-coordinate point additions,
the shared doublings — inside one Pallas program with everything
VMEM-resident.  ops/ed25519._pallas_blk decides where they run.

The field arithmetic mirrors ops/fe.py (same radix-13 signed-limb
bounds proof); shapes inside the kernel are (20, lanes) with the limb
axis on sublanes, so carries are sublane-axis concatenations — no lane
crossings, matching the VPU layout the XLA kernels use.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import fe

# Lanes per program: the VMEM-resident table block is 17*4*20*BLK*4 B,
# 2.8 MB at 512.  A module attribute because tests shrink it
# (monkeypatch.setattr) to run the kernels in interpret mode.
BLK = 512


def blk_for(w: int, cap: int | None = None):
    """Largest block size from min(BLK, cap) halving down to 128 that
    divides width w, or None (caller falls back to the XLA path).
    The 128 floor is Mosaic's lane-tile width; tests that shrink BLK
    below it keep their narrow block as the floor."""
    b = min(BLK, cap) if cap else BLK
    if b <= 0:          # no legal block: the XLA path, not a hang
        return None
    # sub-128 test blocks may be any size (the in-kernel tree never
    # halves them: out_lanes == blk).  At or above 128 the tree must
    # halve exactly onto the 128-lane output, so blocks are pow2-only
    # — a non-pow2 BLK (e.g. 384, whose halving walks 384->192->96
    # past the 128-lane scratch) rounds DOWN to a pow2 candidate
    # instead of being returned verbatim or losing the path
    if b < 128 and w % b == 0:
        return b
    b = 1 << (b.bit_length() - 1)
    floor = min(128, b)
    while b >= floor:
        if w % b == 0:
            return b
        b //= 2
    return None


# Lanes of the MSM accumulator (cap).  The in-kernel pairwise tree
# stops at 128 lanes: every level below 128 needs sub-tile lane
# slicing/relayouts, and narrowing below one (8, 128) VPU tile saves
# nothing — a (20, 8) accumulator pads to the same vregs as (20, 128).
# Stopping at 128 also shrinks the unrolled body from 6 point_add
# levels to 2 at BLK=512.  fold_verify folds the 128 lanes once per
# MSM (not per window).
OUT_PER_BLK = 128


def _out_lanes(blk: int) -> int:
    """Lanes the accumulator occupies for a given block size."""
    return min(blk, OUT_PER_BLK)


# -- field ops on VALUES (not refs); shapes (20, n) ------------------------
# fe's carry/add/sub are elementwise + axis-0 concatenate, which Mosaic
# lowers fine — reuse them so the radix-13 bounds proof lives in ONE
# place; only the product needs a Mosaic-specific (static-slice) rewrite.

_norm_weak = fe.norm_weak
_add = fe.add
_sub = fe.sub


def _prod_tail(cols):
    """Product-column list (39 entries) -> weak-form limbs; the Mosaic
    mirror of fe._prod_tail (same bound proof)."""
    cols = cols + [jnp.zeros_like(cols[0])]
    acc = jnp.stack(cols, axis=0)                    # (40, n)
    hi_ = acc >> fe.RADIX
    lo_ = acc - (hi_ << fe.RADIX)
    acc = lo_ + jnp.concatenate(
        [jnp.zeros_like(hi_[:1]), hi_[:-1]], axis=0)
    out = acc[:fe.NLIMBS] + jnp.int32(fe.WRAP) * acc[fe.NLIMBS:]
    return _norm_weak(out)


def _mul(a, b):
    """Column-sum schoolbook product (no dynamic-update-slices: Mosaic
    wants static slicing)."""
    nl = fe.NLIMBS
    cols = []
    for k in range(2 * nl - 1):
        lo = max(0, k - nl + 1)
        hi = min(nl - 1, k)
        t = a[lo] * b[k - lo]
        for i in range(lo + 1, hi + 1):
            t = t + a[i] * b[k - i]
        cols.append(t)
    return _prod_tail(cols)


def _sq(a):
    """Dedicated squaring, Mosaic form of fe.sqr: cross terms once
    against doubled limbs plus the diagonal — 210 multiplies vs _mul's
    400 on identical column values (fe.sqr has the bounds argument)."""
    nl = fe.NLIMBS
    a2 = a + a
    cols = []
    for k in range(2 * nl - 1):
        t = None
        i = max(0, k - nl + 1)
        while i < k - i:
            term = a2[i] * a[k - i]
            t = term if t is None else t + term
            i += 1
        if k % 2 == 0:
            d = a[k // 2] * a[k // 2]
            t = d if t is None else t + d
        cols.append(t)
    return _prod_tail(cols)


def _mul_word(a, w: int):
    return _norm_weak(a * jnp.int32(w))


def _carry(x):
    hi = x >> fe.RADIX
    lo = x - (hi << fe.RADIX)
    wrapped = jnp.concatenate(
        [hi[-1:] * jnp.int32(fe.WRAP), hi[:-1]], axis=0)
    return lo + wrapped


def _seq_canonical(x):
    """fe._seq_canonical_pass without .at[] (static stacking only)."""
    c = jnp.zeros(x.shape[1:], dtype=jnp.int32)
    outs = []
    for i in range(fe.NLIMBS):
        v = x[i] + c
        lo = v & jnp.int32(fe.MASK)
        outs.append(lo)
        c = (v - lo) >> fe.RADIX
    top = outs[-1] >> jnp.int32(8)
    outs[-1] = outs[-1] & jnp.int32(0xFF)
    outs[0] = outs[0] + top * jnp.int32(19) + c * jnp.int32(fe.WRAP)
    return jnp.stack(outs, axis=0)


def _freeze(x, pad_8p, p_canon):
    """Canonical digits in [0, p) (fe.freeze with passed constants)."""
    x = _norm_weak(x) + pad_8p
    for _ in range(3):
        x = _seq_canonical(x)
    gt = jnp.zeros(x.shape[1:], dtype=bool)
    eq_ = jnp.ones(x.shape[1:], dtype=bool)
    for i in range(fe.NLIMBS - 1, -1, -1):
        gt = gt | (eq_ & (x[i] > p_canon[i]))
        eq_ = eq_ & (x[i] == p_canon[i])
    take = (gt | eq_)[None]
    diff = x - p_canon
    c = jnp.zeros(diff.shape[1:], dtype=jnp.int32)
    outs = []
    for i in range(fe.NLIMBS):
        v = diff[i] + c
        lo = v & jnp.int32(fe.MASK)
        outs.append(lo)
        c = (v - lo) >> fe.RADIX
    sub = jnp.stack(outs, axis=0)
    return jnp.where(take, sub, x)


def _eq(a, b, pad_8p, p_canon):
    return jnp.all(_freeze(a, pad_8p, p_canon)
                   == _freeze(b, pad_8p, p_canon), axis=0)


# -- point ops; points are (4, 20, n) --------------------------------------

def _to_cached(p, d2):
    return jnp.stack([
        _add(p[1], p[0]),
        _sub(p[1], p[0]),
        _mul(p[3], jnp.broadcast_to(d2, p[3].shape)),
        _mul_word(p[2], 2)], axis=0)


def _add_cached(p, q):
    a = _mul(_sub(p[1], p[0]), q[1])
    b = _mul(_add(p[1], p[0]), q[0])
    c = _mul(p[3], q[2])
    d = _mul(p[2], q[3])
    e = _sub(b, a)
    f = _sub(d, c)
    g = _add(d, c)
    h = _add(b, a)
    return jnp.stack([_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h)],
                     axis=0)


def _point_add(p, q, d2):
    return _add_cached(p, _to_cached(q, d2))


def _point_double(p, with_t: bool):
    """dbl-2008-hwcd for a=-1 on values (ops/ed25519.point_double)."""
    x, y, z = p[0], p[1], p[2]
    a = _sq(x)
    b = _sq(y)
    c = _mul_word(_sq(z), 2)
    h = _add(a, b)
    xy = _add(x, y)
    e = _sub(h, _sq(xy))
    g = _sub(a, b)
    f = _add(c, g)
    t = _mul(e, h) if with_t else jnp.zeros_like(x)
    return jnp.stack([_mul(e, f), _mul(g, h), _mul(f, g), t], axis=0)


# -- fused 17-row table build ----------------------------------------------

def _table17_neg_kernel(pt_ref, d2_ref, out_ref):
    """(4, 20, BLK) extended P -> (17, 4, 20, BLK) rows k*(-P),
    k=0..16 (the MSM consumes negated tables: ops/ed25519._msm_tables).
    Fuses the negation, the cached-form conversion, and the 15
    sequential cached adds that otherwise run as an XLA scan of ~20
    dispatched fusions per step."""
    p = pt_ref[...]
    d2 = d2_ref[:, :]
    p = jnp.stack([fe.neg(p[0]), p[1], p[2], fe.neg(p[3])], axis=0)
    one = (jax.lax.broadcasted_iota(jnp.int32, p.shape[1:], 0)
           == 0).astype(jnp.int32)
    zero = jnp.zeros_like(one)
    ident = jnp.stack([zero, one, one, zero], axis=0)
    rows = [ident, p]
    pc = _to_cached(p, d2)
    cur = p
    for _ in range(15):
        cur = _add_cached(cur, pc)
        rows.append(cur)
    out_ref[...] = jnp.stack(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret", "blk"))
def _table17_neg_jit(pt, interpret, blk):
    w = pt.shape[-1]
    assert w % blk == 0, (w, blk)
    nblk = w // blk
    out = pl.pallas_call(
        _table17_neg_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (17, 4, fe.NLIMBS, w), jnp.int32),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((4, fe.NLIMBS, blk), lambda i: (0, 0, i)),
            pl.BlockSpec((fe.NLIMBS, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((17, 4, fe.NLIMBS, blk),
                               lambda i: (0, 0, 0, i)),
        interpret=interpret,
    )(pt, jnp.asarray(fe.D2_LIMBS).reshape(fe.NLIMBS, 1))
    return out


def table17_neg(pt, interpret=False, blk=None):
    """(4,20,W) extended points -> (17,4,20,W) negated window tables,
    one fused Pallas program per blk lanes."""
    return _table17_neg_jit(pt, interpret, blk or BLK)


# -- window-major whole-MSM kernel -----------------------------------------
#
# Grid (nwin, nblk), block fastest: per window, the blocks' select+tree
# contributions accumulate into a VMEM scratch, and the 5 shared
# doublings run ONCE per window on the single global accumulator (the
# output block, whose constant index map keeps it VMEM-resident across
# the whole grid) — not once per block.  The table block changes every
# step and is re-streamed from HBM each window (~5440 B/lane/window),
# but the per-step fetch (2.8 MB at blk 512, ~3.4 us at v5e HBM
# bandwidth) hides under the ~30 us of per-step compute in the pipeline.

def _block_contrib(tab_ref, mag, neg, d2, out_w):
    """The kernel's prologue: 17-row predicated select from the VMEM
    table block, signed-digit negation (X/T arithmetic negation of the
    redundant signed limbs), and the tile-aligned pairwise halving of
    the block down to out_w lanes."""
    sel = tab_ref[0]                     # (4, 20, BLK)
    for k in range(1, 17):
        cond = (mag == jnp.int32(k))[None, None]
        sel = jnp.where(cond, tab_ref[k], sel)
    flip = (neg != 0)[None]
    x = jnp.where(flip, -sel[0], sel[0])
    t = jnp.where(flip, -sel[3], sel[3])
    pts = jnp.stack([x, sel[1], sel[2], t], axis=0)
    w = pts.shape[-1]
    while w > out_w:
        half = w // 2
        pts = _point_add(pts[..., :half], pts[..., half:w], d2)
        w = half
    return pts


def _window_major_kernel(tab_ref, mag_ref, neg_ref, d2_ref, out_ref,
                         wacc_ref, *, nblk):
    j = pl.program_id(0)
    i = pl.program_id(1)
    d2 = d2_ref[:, :]
    pts = _block_contrib(tab_ref, mag_ref[0, 0, :], neg_ref[0, 0, :],
                         d2, wacc_ref.shape[-1])

    @pl.when(i == 0)
    def _win_first():
        wacc_ref[...] = pts

    @pl.when(i != 0)
    def _win_accum():
        wacc_ref[...] = _point_add(wacc_ref[...], pts, d2)

    @pl.when(i == nblk - 1)
    def _win_close():
        @pl.when(j == 0)
        def _first_window():
            out_ref[0] = wacc_ref[...]

        @pl.when(j != 0)
        def _later_window():
            acc = out_ref[0]
            acc = _point_double(acc, with_t=False)
            acc = _point_double(acc, with_t=False)
            acc = _point_double(acc, with_t=False)
            acc = _point_double(acc, with_t=False)
            acc = _point_double(acc, with_t=True)
            out_ref[0] = _point_add(acc, wacc_ref[...], d2)


@functools.partial(jax.jit, static_argnames=("interpret", "blk"))
def _msm_window_major_jit(tab, mags, negs, interpret, blk):
    from jax.experimental.pallas import tpu as pltpu

    w = tab.shape[-1]
    assert w % blk == 0, (w, blk)
    nblk = w // blk
    nwin = mags.shape[0]
    out_l = _out_lanes(blk)
    kernel = functools.partial(_window_major_kernel, nblk=nblk)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, 4, fe.NLIMBS, out_l),
                                       jnp.int32),
        grid=(nwin, nblk),            # last dim fastest: blocks inner
        in_specs=[
            pl.BlockSpec((17, 4, fe.NLIMBS, blk),
                         lambda j, i: (0, 0, 0, i)),
            # digits ride a (nwin, 1, W) layout so the BLOCK's last two
            # dims are (1, blk) against ARRAY dims (1, W) — Mosaic
            # requires the last two block dims divisible by (8, 128) or
            # equal to the array's
            pl.BlockSpec((1, 1, blk), lambda j, i: (j, 0, i)),
            pl.BlockSpec((1, 1, blk), lambda j, i: (j, 0, i)),
            pl.BlockSpec((fe.NLIMBS, 1), lambda j, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 4, fe.NLIMBS, out_l),
                               lambda j, i: (0, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((4, fe.NLIMBS, out_l), jnp.int32)],
        interpret=interpret,
    )(tab, mags.reshape(nwin, 1, w),
      negs.astype(jnp.int32).reshape(nwin, 1, w),
      jnp.asarray(fe.D2_LIMBS).reshape(fe.NLIMBS, 1))
    return out[0]


def msm_window_major(tab, mags, negs, interpret=False, blk=None):
    """(17,4,20,W) table + (nwin,W) MSB-first signed digits ->
    (4,20,out_lanes) accumulator holding the FULL MSM (its lane-sum):
    the exact Straus recurrence with one global accumulator.

    blk (lanes per program) defaults to module BLK; the correctness
    argument is width-independent, so tests run narrow blocks."""
    return _msm_window_major_jit(tab, mags, negs, interpret, blk or BLK)


# -- fused fold/verify epilogue --------------------------------------------
#
# After the window-major kernel, each MSM side is a (4, 20, m*128)
# partial tensor whose lane-sum is the MSM result (m = 1 on one chip,
# the mesh size under ops/msm_shard).  The XLA epilogue (_tree_reduce
# to 1 lane, combine, 3 cofactor doublings, identity check) runs ~12
# point_add levels at shrinking widths — exactly the
# fixed-cost-dominated regime the kernels exist to avoid.  This kernel
# runs the whole epilogue in ONE program: tile-aligned
# halving/chunk-sum to 128 lanes, a 7-step butterfly roll-fold (every
# op full-width — Mosaic takes no sub-128-lane slicing), cofactor,
# frozen identity.

# Partials wider than this are pre-folded by the caller in XLA (those
# levels are wide enough to be efficient there) to bound kernel VMEM:
# two (4, 20, 8192) inputs = 5.2 MB.
MAX_FOLD_LANES = 8192


def _tree_to_tile(pts, d2, tile):
    """(4, 20, m*tile) -> (4, 20, tile) using tile-aligned ops only:
    halve while the half stays a multiple of tile (m even), then
    chunk-sum the m in {3, 5} leftover tile-wide chunks."""
    w = pts.shape[-1]
    while w > tile and (w // 2) % tile == 0:
        half = w // 2
        pts = _point_add(pts[..., :half], pts[..., half:w], d2)
        w = half
    if w > tile:
        acc = pts[..., :tile]
        for k in range(1, w // tile):
            acc = _point_add(acc, pts[..., k * tile:(k + 1) * tile], d2)
        pts = acc
    return pts


def _make_fold_kernel(interpret: bool, tile: int):
    if interpret:
        def _roll(x, shift):
            return jnp.roll(x, shift, axis=-1)
    else:
        from jax.experimental.pallas import tpu as pltpu

        def _roll(x, shift):
            return pltpu.roll(x, shift, axis=x.ndim - 1)

    def kernel(a_ref, r_ref, consts_ref, out_ref):
        """a (4,20,Pa), r (4,20,Pr) partials; consts (3,20,1) =
        [d2, pad_8p, p_canon]; out (1,tile) int32 verdict broadcast."""
        consts = consts_ref[...]
        d2, pad_8p, p_canon = consts[0], consts[1], consts[2]
        a = _tree_to_tile(a_ref[...], d2, tile)
        r = _tree_to_tile(r_ref[...], d2, tile)
        tot = _point_add(a, r, d2)
        # butterfly: after folds at shifts tile/2..1 every lane holds
        # the full tile-wide sum (wraparound rotate, all ops full-tile)
        shift = tile // 2
        while shift >= 1:
            rolled = _roll(tot, shift)
            tot = _point_add(tot, rolled, d2)
            shift //= 2
        for _ in range(3):               # cofactor 8
            tot = _point_double(tot, with_t=False)
        x_zero = jnp.all(_freeze(tot[0], pad_8p, p_canon) == 0, axis=0)
        yz_eq = _eq(tot[1], tot[2], pad_8p, p_canon)
        out_ref[...] = (x_zero & yz_eq).astype(jnp.int32)[None]

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _fold_verify_jit(a_part, r_part, interpret, tile):
    assert tile & (tile - 1) == 0, tile       # butterfly needs pow2
    assert a_part.shape[-1] % tile == 0 and r_part.shape[-1] % tile == 0
    assert a_part.shape[-1] <= MAX_FOLD_LANES, a_part.shape
    assert r_part.shape[-1] <= MAX_FOLD_LANES, r_part.shape
    consts = jnp.stack([
        jnp.asarray(fe.D2_LIMBS), jnp.asarray(fe._PAD_8P),
        jnp.asarray(fe._P_CANON)], axis=0).reshape(3, fe.NLIMBS, 1)
    out = pl.pallas_call(
        _make_fold_kernel(interpret, tile),
        out_shape=jax.ShapeDtypeStruct((1, tile), jnp.int32),
        in_specs=[
            pl.BlockSpec(a_part.shape, lambda: (0, 0, 0)),
            pl.BlockSpec(r_part.shape, lambda: (0, 0, 0)),
            pl.BlockSpec((3, fe.NLIMBS, 1), lambda: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda: (0, 0)),
        interpret=interpret,
    )(a_part, r_part, consts)
    return out[0, 0] != 0


def fold_verify(a_part, r_part, interpret=False, tile=128):
    """Fused RLC epilogue: two per-block partial tensors (lane counts
    multiples of tile, <= MAX_FOLD_LANES) -> bool([8](A+R) == identity).
    Pairs with ops/ed25519.rlc_verify_kernel's cofactor-8 check.

    tile is the Mosaic lane-tile width (128 on hardware); interpret
    tests shrink it — the halving/butterfly argument is width-
    independent."""
    return _fold_verify_jit(a_part, r_part, interpret, tile)
