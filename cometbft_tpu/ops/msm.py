"""Curve-generic batched signed-window MSM engine.

One engine, two curves: the verify hot path on both ed25519 and
secp256k1 is a multi-scalar multiplication, and until this module each
curve carried a bespoke device path (the RLC/w5 Straus stack in
ops/ed25519.py vs the per-signature 4-bit Shamir ladder in
ops/secp256k1.py).  The engine factors the common structure out into
three curve-independent pieces, parameterized by a small
:class:`CurveSpec` (field ops, unified add formulas, limb layout,
group order):

1. **windowed recode** — the bias trick of PR 10's
   ``_recode_w5_device`` generalized to any window width
   (:func:`recode_biased_digits`), plus a fully-parallel *odd*
   signed-digit recode (:func:`recode_jt`, Joye–Tunstall closed form)
   for the shared-table product path where all-odd digits make every
   in-loop addition structurally nonzero;

2. **bucket accumulation** — ``_segment_sum_mod_l``'s segment-sum
   discipline generalized from scalar limbs to curve points: per
   window, each point lands in the bucket of its digit magnitude.  A
   TPU has no efficient data-dependent scatter for 80-limb points
   (the long-standing comment in ops/ed25519.py), so the buckets are
   formed the way the radix scatter forms byte columns: a masked
   bucket-major selection tensor reduced by the same pairwise
   tree-add used everywhere else (:func:`bucket_accumulate`), then
   combined with the classic running-sum fold
   (:func:`bucket_fold`);

3. **shared-table multi-product** — N *independent* products
   ``k_i·P + l_i·Q_{g(i)}`` computed against shared precomputed
   window tables with zero in-loop doublings
   (:func:`multiprod_shared_tables`); this is the shape ECDSA batch
   verification needs (each signature checks an x-coordinate, so no
   sound whole-batch RLC single-point equation exists — recovering
   R from r is y-parity ambiguous) and the base the BLS12-381
   aggregate work can reuse.

Crossover: on this architecture the masked-selection bucket form
costs ~``B·W`` point-lane-ops per window (B = bucket count, 16 at
width 5) against Straus' ~``W`` to ``6W``, so the ed25519 RLC path
keeps Straus (ops/ed25519.py) and the bucket arm serves the goldens
and ops/msm_shard.sharded_bucket_msm; the engine's product
win is the secp256k1 shared-table path (ops/secp256k1.py
``msm_verify_kernel``), which replaces ~4224 field-muls/sig of ladder
with ~1250 and drops the 256 per-window exact-zero freezes.

Soundness note for the all-odd product path: with digits recoded odd
(never zero) and the accumulator blinded by a fresh random point S
(crypto/secp256k1.pack_msm_batch draws the scalar with ``secrets``,
exactly the RLC z_i discipline), every in-loop addition adds a
structurally nonzero table row to ``S + (partial sum)``; an
incomplete-add collision requires the adversary to hit ±S, i.e. a
~2^-247 guess per dispatch — the same soundness class as the RLC
fold.  A collision degrades to the absorbing Z=0 point and the
epilogue rejects Z=0 lanes, so the failure mode is a (negligible)
false *reject*, never a false accept.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# CurveSpec: what the engine needs to know about a curve
# ---------------------------------------------------------------------------
#
# Point state is a (coords_array, inf_plane) pair.  Curves with
# complete formulas (ed25519 extended coordinates) represent the
# identity in-band and carry inf=None; incomplete short-Weierstrass
# curves (secp256k1 Jacobian) carry an explicit boolean infinity
# plane, and their `add` must be the exact complete addition —
# bucket accumulation feeds masked identity entries through it by
# design.

@dataclass(frozen=True)
class CurveSpec:
    name: str
    order: int                       # prime group order
    coords: int                      # point stack height (4 ext / 3 jac)
    nlimbs: int                      # field limb count
    identity: Callable               # batch_shape -> state
    add: Callable                    # state, state -> state  (complete)
    dbl: Callable                    # state -> state
    cond_neg: Callable               # pts, mask -> pts
    select: Callable = None          # mask, state_a, state_b -> state
    # optional host-side helpers for goldens/tests
    to_affine_int: Callable = None   # state (width-1) -> (x, y) ints


def _where_state(mask, a, b):
    """Generic state select: mask broadcasts against the trailing
    batch dims of the coordinate stack."""
    pa, ia = a
    pb, ib = b
    pt = jnp.where(mask[None, None], pa, pb)
    if ia is None and ib is None:
        return pt, None
    return pt, jnp.where(mask, ia, ib)


def ed25519_spec() -> CurveSpec:
    from . import ed25519 as ed
    from . import fe

    def identity(batch_shape):
        return ed.identity_point(batch_shape), None

    def add(a, b):
        return ed.point_add(a[0], b[0]), None

    def dbl(a):
        return ed.point_double(a[0]), None

    def to_affine_int(state):
        pt = np.asarray(state[0])[..., 0]
        z = fe.limbs_to_int(pt[2])
        p = fe.P
        zi = pow(z, p - 2, p)
        return (fe.limbs_to_int(pt[0]) * zi % p,
                fe.limbs_to_int(pt[1]) * zi % p)

    return CurveSpec(
        name="ed25519", order=(1 << 252) + 27742317777372353535851937790883648493,
        coords=4, nlimbs=fe.NLIMBS,
        identity=identity, add=add, dbl=dbl,
        cond_neg=ed._cond_neg_point, select=_where_state,
        to_affine_int=to_affine_int)


def secp256k1_spec() -> CurveSpec:
    from . import fe_secp as fs
    from . import secp256k1 as sp

    def identity(batch_shape):
        one = sp._one_fe(batch_shape)
        return (sp._pt(one, one, sp._zero_fe(batch_shape)),
                jnp.ones(batch_shape, dtype=bool))

    def add(a, b):
        return sp.jadd_complete(a[0], a[1], b[0], b[1])

    def dbl(a):
        # jdbl is complete for a=0 (Z=0 stays Z=0, no 2-torsion)
        return sp.jdbl(a[0]), a[1]

    def cond_neg(pts, neg):
        y = jnp.where(neg[None], -pts[1], pts[1])
        return sp._pt(pts[0], y, pts[2])

    def to_affine_int(state):
        pt = np.asarray(state[0])[..., 0]
        if bool(np.asarray(state[1])[..., 0]):
            return None
        z = fs.limbs_to_int(pt[2]) % sp_p()
        zi = pow(z, sp_p() - 2, sp_p())
        return (fs.limbs_to_int(pt[0]) * zi * zi % sp_p(),
                fs.limbs_to_int(pt[1]) * zi * zi * zi % sp_p())

    return CurveSpec(
        name="secp256k1", order=sp.N_ORDER,
        coords=3, nlimbs=fs.NLIMBS,
        identity=identity, add=add, dbl=dbl,
        cond_neg=cond_neg, select=_where_state,
        to_affine_int=to_affine_int)


def sp_p() -> int:
    from ..crypto import secp256k1 as host
    return host.P


# ---------------------------------------------------------------------------
# windowed recodes
# ---------------------------------------------------------------------------

def bias_int(width: int, ndig: int) -> int:
    """The per-position bias that linearizes signed-window recoding:
    adding ``2^(w-1)`` at every window position pre-pays the
    worst-case borrow, so the signed digits of x are the plain base
    ``2^w`` digits of x + BIAS minus ``2^(w-1)`` — one limb addition
    plus static bit extraction instead of a data-dependent carry
    loop (PR 10's _recode_w5_device trick, any width)."""
    return sum((1 << (width - 1)) << (width * j) for j in range(ndig))


def recode_biased_digits(xb: jnp.ndarray, width: int, ndig: int):
    """(…, L) uint32 16-bit limbs of x + BIAS -> ((ndig, …), (ndig, …))
    signed-window digit magnitudes and signs, MSB-first.  Static bit
    extraction only; the caller performs the bias addition (it owns
    the scalar-limb carry discipline).  width <= 16."""
    mask = jnp.uint32((1 << width) - 1)
    half = 1 << (width - 1)
    nl = xb.shape[-1]
    mags, negs = [], []
    for j in range(ndig - 1, -1, -1):              # MSB first
        p = width * j
        li, sh = p >> 4, p & 15
        hi = xb[..., li + 1] if li + 1 < nl else 0
        word = xb[..., li] | (hi << 16)
        d = ((word >> sh) & mask).astype(jnp.int32) - half
        negs.append(d < 0)
        mags.append(jnp.abs(d))
    return jnp.stack(mags, axis=0), jnp.stack(negs, axis=0)


def recode_jt(ks, width: int, ndig: int):
    """Odd signed-digit recode (Joye–Tunstall), closed form, host side.

    For ODD k the width-w odd signed digits are::

        d_i = 2 * ((k >> (i*w + 1)) mod 2^w) + 1 - 2^w

    — fully parallel bit extraction, every digit odd in
    [-(2^w - 1), 2^w - 1], and for ``0 < k < 2^(ndig*w + 1)``::

        k = sum_i d_i * 2^(i*w)  +  2^(ndig*w)

    The fixed ``2^(ndig*w)`` remainder is a known per-table
    correction point added once by the kernel.  All-odd digits are
    what lets the in-loop adds skip the exact-zero branch machinery:
    no digit ever selects the identity.

    Returns ``(rows, negs)`` with rows ``(ndig, N)`` int32 in
    ``[0, 2^(w-1))`` indexing the odd multiple ``(2*row + 1)·2^(i*w)``
    and negs ``(ndig, N)`` bool, window index i ascending (LSB
    first — the shared-table product has no doubling order to
    respect).
    """
    n = len(ks)
    nbytes = (ndig * width + 7) // 8 + 3
    buf = np.zeros((n, nbytes), np.uint8)
    for i, k in enumerate(ks):
        k = int(k)
        assert k & 1 and 0 < k < (1 << (ndig * width + 1)), \
            "recode_jt needs odd 0 < k < 2^(ndig*w+1)"
        buf[i] = np.frombuffer(k.to_bytes(nbytes, "little"), np.uint8)
    b = buf.astype(np.uint32)
    mask = np.uint32((1 << width) - 1)
    rows = np.empty((ndig, n), np.int32)
    negs = np.empty((ndig, n), bool)
    for i in range(ndig):
        p = i * width + 1
        byi, sh = p >> 3, p & 7
        word = b[:, byi] | (b[:, byi + 1] << 8) | (b[:, byi + 2] << 16)
        d = (2 * ((word >> sh) & mask).astype(np.int64)
             + 1 - (1 << width))
        neg = d < 0
        mag = np.where(neg, -d, d)                 # odd, >= 1
        rows[i] = ((mag - 1) >> 1).astype(np.int32)
        negs[i] = neg
    return rows, negs


def jt_digit_value(rows: np.ndarray, negs: np.ndarray, width: int) -> int:
    """Reconstruct sum_i d_i 2^(i*w) from a recode_jt column — the
    test oracle for the closed form (add 2^(ndig*w) for k)."""
    ndig = rows.shape[0]
    total = 0
    for i in range(ndig):
        d = int(2 * rows[i] + 1)
        if negs[i]:
            d = -d
        total += d << (i * width)
    return total


# ---------------------------------------------------------------------------
# bucket accumulation + running-sum fold (the Pippenger arm)
# ---------------------------------------------------------------------------

def _tree_reduce_state(spec: CurveSpec, state, target: int = 1):
    """Pairwise tree-add over the LAST batch axis of a state, the
    generic form of ops/ed25519._tree_reduce (works for any leading
    batch dims, carries the infinity plane through spec.add)."""
    pts, inf = state
    while pts.shape[-1] > target:
        w = pts.shape[-1]
        half = w // 2
        a = (pts[..., :half], None if inf is None else inf[..., :half])
        b = (pts[..., half:2 * half],
             None if inf is None else inf[..., half:2 * half])
        left_p, left_i = spec.add(a, b)
        if w % 2:
            left_p = jnp.concatenate([left_p, pts[..., 2 * half:]],
                                     axis=-1)
            if inf is not None:
                left_i = jnp.concatenate([left_i, inf[..., 2 * half:]],
                                         axis=-1)
        pts, inf = left_p, left_i
    return pts, inf


def bucket_accumulate(spec: CurveSpec, pts_state, mag, neg, nbuckets: int):
    """One window's bucket accumulation: (coords, nlimbs, W) points
    with (W,) digit magnitudes in [0, nbuckets] -> per-bucket sums
    (coords, nlimbs, nbuckets) for buckets 1..nbuckets.

    The segment-sum discipline of _segment_sum_mod_l lifted to
    points: a lane contributes its (sign-adjusted) point to exactly
    the bucket of its |digit|; magnitude 0 contributes nowhere.  The
    scatter is expressed as a bucket-major masked selection (the
    identity is the masked filler) reduced by the pairwise tree —
    data-independent shapes, which is the whole trick on a TPU.
    """
    pts, inf = pts_state
    signed = spec.cond_neg(pts, neg)
    ident_p, ident_i = spec.identity(pts.shape[2:])
    # (coords, nlimbs, nbuckets, W) bucket-major selection tensor
    sel_mask = (mag[None, :] ==
                (jnp.arange(1, nbuckets + 1, dtype=mag.dtype)[:, None]))
    stack_p = jnp.where(sel_mask[None, None], signed[:, :, None, :],
                        ident_p[:, :, None, :])
    if inf is None:
        stack_i = None
    else:
        stack_i = jnp.where(sel_mask, inf[None, :],
                            ident_i[None, :])
    bp, bi = _tree_reduce_state(spec, (stack_p, stack_i), 1)
    return bp[..., 0], None if bi is None else bi[..., 0]


def bucket_fold(spec: CurveSpec, buckets_state):
    """Running-sum fold: (coords, nlimbs, B) bucket sums ->
    (coords, nlimbs, 1) window sum ``sum_b b * bucket_b`` via the
    classic descending running sum (2(B-1) adds, no multiplies)."""
    bp, bi = buckets_state
    nb = bp.shape[-1]

    def slot(b):
        return (bp[..., b:b + 1], None if bi is None else bi[..., b:b + 1])

    run = slot(nb - 1)
    tot = run
    for b in range(nb - 2, -1, -1):
        run = spec.add(run, slot(b))
        tot = spec.add(tot, run)
    return tot


def bucket_msm(spec: CurveSpec, pts_state, mags, negs, width: int):
    """Full bucket (Pippenger) MSM: ``sum_i e_i P_i`` over
    (coords, nlimbs, W) points with (nwin, W) MSB-first signed-window
    digit magnitudes/signs of the e_i (the same digit layout
    ops/ed25519._msm_side consumes).  Returns a width-1 state.

    Window combination is MSB-first Horner: ``acc = 2^w acc + W_j``,
    so the doublings are shared across all buckets exactly like the
    Straus scan — the arms differ only in how a window's contribution
    is reduced (bucket accumulate+fold vs select cascade+tree).
    """
    nbuckets = 1 << (width - 1)

    def step(acc, xs):
        mag, neg = xs
        for _ in range(width):
            acc = spec.dbl(acc)
        wsum = bucket_fold(
            spec, bucket_accumulate(spec, pts_state, mag, neg, nbuckets))
        return spec.add(acc, wsum), None

    acc = spec.identity((1,))
    acc, _ = jax.lax.scan(step, acc, (mags, negs))
    return acc


# ---------------------------------------------------------------------------
# shared-table multi-product (zero in-loop doublings)
# ---------------------------------------------------------------------------

def multiprod_shared_tables(acc, sides):
    """N independent products against shared precomputed window
    tables — zero in-loop doublings.

    ``acc`` seeds the accumulator (the blinding point S broadcast to
    the lane width).  ``sides`` is a sequence of
    ``(tables, rows, negs, gather, add_entry)``: ``tables`` stacks the
    per-window tables along axis 0 (it rides the scan as an xs, so
    each step sees only its own window's slice), ``rows/negs`` are
    (nwin, N) odd-row indices/signs from :func:`recode_jt`,
    ``gather(tab_j, rows_j)`` widens window j's table to one entry
    per lane, and ``add_entry(acc, entry, neg)`` performs the
    (incomplete, blinding-protected) add.  The caller appends the
    per-side ``2^(ndig*w)`` correction points and subtracts S — see
    ops/secp256k1.msm_verify_kernel, the ECDSA instantiation.

    Kept generic and separate from that kernel so the BLS12-381
    aggregate path (ROADMAP item 2) can instantiate it with pairing
    curve specs without touching the ECDSA wiring.
    """
    for tables, rows, negs, gather, add_entry in sides:
        def step(a, xs, gather=gather, add_entry=add_entry):
            tab_j, row, neg = xs
            return add_entry(a, gather(tab_j, row), neg), None
        acc, _ = jax.lax.scan(step, acc, (tables, rows, negs))
    return acc
