"""GF(2**255 - 19) arithmetic for TPU, v3: limbs-first signed 20 x 13-bit.

Round-2 profiling on the real chip showed the v2 (batch, 20) layout ran
~6x under the VPU's measured ~600 Gops/s: a 20-wide minor dimension
fills 20 of 128 vector lanes, and the skew-reshape antidiagonal sum
forced full relayouts of every (B, 20, 20) partial-product tensor
through HBM.  v3 turns the layout inside out:

- field elements are (NLIMBS, ...batch): the LIMB axis is axis 0
  (sublanes), the batch fills the 128-lane minor dimension.  Every op
  is a shallow graph of (20, B)-shaped elementwise ops — no reshapes,
  no gathers, no lane-crossing anywhere in the hot path.
- the schoolbook product accumulates 20 statically-shifted
  multiply-adds into a (39, B) column tensor (plain sublane slices),
  then carries with whole-vector shifts along axis 0.

Numerics are unchanged from v2 (same bounds proof):
- limbs are SIGNED int32 in radix 2**13 (20 limbs = 260 bits; wrap
  608 = 19 * 2**5 since 2**260 == 19 * 2**5 mod p).
- op outputs have limbs in [-1220, 9800] ("weak" form); mul accepts
  |limb| <= 10300: 20 * 10300**2 = 2.12e9 < 2**31.

Reference analog: the 64-bit limb arithmetic inside curve25519-voi
consumed by /root/reference/crypto/ed25519/ed25519.go.  The layout is
an original TPU design, not a translation.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

NLIMBS = 20
RADIX = 13
BASE = 1 << RADIX            # 8192
MASK = BASE - 1
WRAP = 19 << 5               # 608: 2**260 == 608 (mod p)
P = (1 << 255) - 19

_MAX_IN = 10300              # max |limb| mul accepts
assert NLIMBS * _MAX_IN * _MAX_IN < (1 << 31)


# ---------------------------------------------------------------------------
# host <-> limb conversion
# ---------------------------------------------------------------------------

def int_to_limbs(x: int) -> np.ndarray:
    """Python int -> 20 int32 limbs (radix 2**13, little-endian)."""
    x %= P
    out = np.zeros(NLIMBS, dtype=np.int32)
    for i in range(NLIMBS):
        out[i] = x & MASK
        x >>= RADIX
    assert x == 0
    return out


def limbs_to_int(limbs) -> int:
    """Accepts redundant/signed limbs; value mod p."""
    arr = np.asarray(limbs, dtype=np.int64)
    return sum(int(v) << (RADIX * i) for i, v in enumerate(arr)) % P


# curve constants
D_INT = (-121665 * pow(121666, P - 2, P)) % P
D2_INT = (2 * D_INT) % P
SQRT_M1_INT = pow(2, (P - 1) // 4, P)
D_LIMBS = int_to_limbs(D_INT)
D2_LIMBS = int_to_limbs(D2_INT)
SQRT_M1_LIMBS = int_to_limbs(SQRT_M1_INT)
ONE_LIMBS = int_to_limbs(1)
ZERO_LIMBS = int_to_limbs(0)

# canonical digits of p: [8173, 8191*18, 255]
_P_CANON = np.zeros(NLIMBS, dtype=np.int32)
_t = P
for _i in range(NLIMBS):
    _P_CANON[_i] = _t & MASK
    _t >>= RADIX

# 8p in 20 digits, every digit >= 2047: adding it makes any weak-form
# (limbs >= -1220) element nonnegative.
_PAD_8P = np.zeros(NLIMBS, dtype=np.int32)
_t = 8 * P
for _i in range(NLIMBS - 1):
    _PAD_8P[_i] = _t & MASK
    _t >>= RADIX
_PAD_8P[NLIMBS - 1] = _t
assert sum(int(v) << (RADIX * i) for i, v in enumerate(_PAD_8P)) == 8 * P
assert (_PAD_8P >= 2047).all()


def _bcast(limbs: np.ndarray, ndim: int) -> jnp.ndarray:
    """(20,) host constant -> (20, 1, ...) broadcastable to ndim dims."""
    return jnp.asarray(limbs.reshape((NLIMBS,) + (1,) * (ndim - 1)))


# ---------------------------------------------------------------------------
# carries: data-parallel whole-vector shifts along the limb axis
# ---------------------------------------------------------------------------

def _carry_pass(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry step on 20 limbs.  Arithmetic >> keeps floor
    semantics for signed limbs, so lo is always in [0, 2**13); the top
    limb's carry wraps through 2**260 == 608."""
    hi = x >> RADIX
    lo = x - (hi << RADIX)
    wrapped = jnp.concatenate(
        [hi[-1:] * jnp.int32(WRAP), hi[:-1]], axis=0)
    return lo + wrapped


def norm_weak(x: jnp.ndarray) -> jnp.ndarray:
    """Two passes: |limb| < 2**27 input -> limbs in [-1220, 9800]."""
    return _carry_pass(_carry_pass(x))


# ---------------------------------------------------------------------------
# field ops (all outputs in weak form); arrays are (20, ...batch)
# ---------------------------------------------------------------------------

def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _carry_pass(a + b)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _carry_pass(a - b)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return _carry_pass(-a)


def _prod_tail(acc: jnp.ndarray, batch: tuple) -> jnp.ndarray:
    """(39, B) product columns -> weak-form (20, B): carry pass in
    40-wide column space (no wrap: col 39 catches it), then the
    2**260 == 608 fold, then two carry passes.

    Bound: columns <= 20 * 10300**2 = 2.12e9 < 2**31 on entry.  After
    the column-space carry pass, columns are < 2**13 + 2.12e9/2**13 ~
    267k; folding multiplies the high half by 608: <= 608*267k ~
    1.63e8 < 2**31.  Two more passes land in weak form."""
    acc = jnp.concatenate([acc, jnp.zeros((1,) + batch, jnp.int32)], axis=0)
    hi = acc >> RADIX
    lo = acc - (hi << RADIX)
    acc = lo + jnp.concatenate(
        [jnp.zeros((1,) + batch, jnp.int32), hi[:-1]], axis=0)
    # fold: 2**260 == 608  =>  out_k = col_k + 608 * col_{20+k}
    out = acc[:NLIMBS] + jnp.int32(WRAP) * acc[NLIMBS:]
    return norm_weak(out)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """20 shifted multiply-accumulates -> (39, B) columns -> _prod_tail.
    Inputs: |limb| <= 10300 (column bound proof in _prod_tail)."""
    batch = a.shape[1:]
    acc = jnp.zeros((2 * NLIMBS - 1,) + batch, dtype=jnp.int32)
    for i in range(NLIMBS):
        acc = acc.at[i:i + NLIMBS].add(a[i] * b)
    return _prod_tail(acc, batch)


def sqr(a: jnp.ndarray) -> jnp.ndarray:
    """a**2 with the doubled-cross-terms schoolbook: the i<j products
    appear once against 2*a_i, the diagonal once — 190 + 20 = 210
    multiplies vs mul(a, a)'s 400, on the exact same column VALUES, so
    _prod_tail's bound proof carries over unchanged.  Per-term bound:
    |2a_i * a_j| <= 20600 * 10300 = 2.13e8 < 2**31.

    Dominates the decompression sqrt chains (~253 squarings each,
    docs/PERF.md) and point_double (4S of 4M+4S)."""
    batch = a.shape[1:]
    a2 = a + a
    acc = jnp.zeros((2 * NLIMBS - 1,) + batch, dtype=jnp.int32)
    for i in range(NLIMBS):
        acc = acc.at[2 * i].add(a[i] * a[i])
        if i + 1 < NLIMBS:
            acc = acc.at[2 * i + 1: i + NLIMBS].add(a2[i] * a[i + 1:])
    return _prod_tail(acc, batch)


def mul_word(a: jnp.ndarray, w: int) -> jnp.ndarray:
    """Multiply by a small nonneg constant: w * 10300 < 2**31."""
    return norm_weak(a * jnp.int32(w))


def _sq_n(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return jax.lax.fori_loop(0, n, lambda i, v: sqr(v), x, unroll=4)


def _pow_22501(z: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shared prefix of the p-2 and (p-5)/8 chains: (z**(2**250-1), z**11)."""
    z2 = sqr(z)
    z9 = mul(_sq_n(z2, 2), z)
    z11 = mul(z9, z2)
    z2_5_0 = mul(sqr(z11), z9)
    z2_10_0 = mul(_sq_n(z2_5_0, 5), z2_5_0)
    z2_20_0 = mul(_sq_n(z2_10_0, 10), z2_10_0)
    z2_40_0 = mul(_sq_n(z2_20_0, 20), z2_20_0)
    z2_50_0 = mul(_sq_n(z2_40_0, 10), z2_10_0)
    z2_100_0 = mul(_sq_n(z2_50_0, 50), z2_50_0)
    z2_200_0 = mul(_sq_n(z2_100_0, 100), z2_100_0)
    z2_250_0 = mul(_sq_n(z2_200_0, 50), z2_50_0)
    return z2_250_0, z11


def invert(z: jnp.ndarray) -> jnp.ndarray:
    """z**(p-2); returns 0 for z == 0."""
    z2_250_0, z11 = _pow_22501(z)
    return mul(_sq_n(z2_250_0, 5), z11)


def pow_p58(z: jnp.ndarray) -> jnp.ndarray:
    """z**((p-5)/8)."""
    z2_250_0, _ = _pow_22501(z)
    return mul(_sq_n(z2_250_0, 2), z)


# ---------------------------------------------------------------------------
# canonicalization / predicates (cold path: eq/identity checks)
# ---------------------------------------------------------------------------

def _seq_canonical_pass(x: jnp.ndarray) -> jnp.ndarray:
    """Exact sequential carry over nonneg limbs, then reduce the bits at
    and above 2**255 (limb 19 bits >= 8) through the 19-wrap."""
    c = jnp.zeros(x.shape[1:], dtype=jnp.int32)
    outs = []
    for i in range(NLIMBS):
        v = x[i] + c
        lo = v & jnp.int32(MASK)
        outs.append(lo)
        c = (v - lo) >> RADIX
    x = jnp.stack(outs, axis=0)
    # c is the carry out of limb 19 (units of 2**260 == 608)
    top = x[19] >> jnp.int32(8)         # bits 255.. of the value
    x = x.at[19].set(x[19] & jnp.int32(0xFF))
    add0 = top * jnp.int32(19) + c * jnp.int32(WRAP)
    return x.at[0].add(add0)


def freeze(a: jnp.ndarray) -> jnp.ndarray:
    """Canonical representative in [0, p).  Rare (eq/identity checks),
    so a few exact 20-step ripples are fine."""
    x = norm_weak(a) + _bcast(_PAD_8P, a.ndim)   # all limbs > 0
    for _ in range(3):
        x = _seq_canonical_pass(x)
    # value now < 2**255; subtract p once if needed
    return _cond_sub_p(x)


def _cond_sub_p(x: jnp.ndarray) -> jnp.ndarray:
    """x - p if x >= p else x, for canonical digits (value < 2**255)."""
    p_l = jnp.asarray(_P_CANON)
    gt = jnp.zeros(x.shape[1:], dtype=bool)
    eq_ = jnp.ones(x.shape[1:], dtype=bool)
    for i in range(NLIMBS - 1, -1, -1):
        gt = gt | (eq_ & (x[i] > p_l[i]))
        eq_ = eq_ & (x[i] == p_l[i])
    take = (gt | eq_)[None]
    diff = x - _bcast(_P_CANON, x.ndim)
    c = jnp.zeros(diff.shape[1:], dtype=jnp.int32)
    outs = []
    for i in range(NLIMBS):
        v = diff[i] + c
        lo = v & jnp.int32(MASK)
        outs.append(lo)
        c = (v - lo) >> RADIX
    diff = jnp.stack(outs, axis=0)
    return jnp.where(take, diff, x)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(freeze(a) == 0, axis=0)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return is_zero(sub(a, b))


def parity(a: jnp.ndarray) -> jnp.ndarray:
    return (freeze(a)[0] & jnp.int32(1)).astype(jnp.uint32)


def sqrt_ratio(u: jnp.ndarray, v: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """sqrt(u/v) per RFC 8032 decompression; returns (x, ok)."""
    v3 = mul(sqr(v), v)
    v7 = mul(sqr(v3), v)
    r = mul(mul(u, v3), pow_p58(mul(u, v7)))
    check = mul(v, sqr(r))
    correct = eq(check, u)
    flipped = eq(check, neg(u))
    r_alt = mul(r, _bcast(SQRT_M1_LIMBS, r.ndim))
    x = jnp.where(flipped[None], r_alt, r)
    return x, correct | flipped


# ---------------------------------------------------------------------------
# packing: 8 little-endian uint32 words -> limbs (words on axis 0)
# ---------------------------------------------------------------------------

def words32_to_limbs(words: jnp.ndarray) -> jnp.ndarray:
    """(8, ...) uint32 LE words -> (20, ...) int32 limbs.  Bit 255 (the
    sign bit of point encodings) is EXCLUDED: limb 19 holds bits
    247..254 only."""
    w = jnp.concatenate(
        [words, jnp.zeros_like(words[:1])], axis=0).astype(jnp.uint32)
    limbs = []
    for i in range(NLIMBS):
        bit = RADIX * i
        j, r = bit // 32, bit % 32
        v = w[j] >> jnp.uint32(r)
        if r + RADIX > 32:
            v = v | (w[j + 1] << jnp.uint32(32 - r))
        mask = MASK if i < NLIMBS - 1 else 0xFF   # drop the sign bit
        limbs.append((v & jnp.uint32(mask)).astype(jnp.int32))
    return jnp.stack(limbs, axis=0)
