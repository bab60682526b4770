"""Pallas TPU kernel for fused point decompression.

Decompression is the measured per-signature floor of the RLC path
(docs/PERF.md): two ~270-mul sqrt-exponent chains per point (A and R),
~1.8 us/point at width 4096 under XLA.  The chain is pure elementwise
radix-13 arithmetic — its cost under XLA is dominated by per-op
dispatch/fusion boundaries, which is exactly what a single VMEM-
resident Pallas program removes: one program per BLK-lane slice runs
words->limbs, y^2, the (p-5)/8 power chain (fori_loop of fused
squarings), the sqrt checks, sign fix, and T=X*Y without leaving VMEM.

ops/ed25519.decompress takes it wherever _pallas_blk gives the width a
block.

Reference behavior matched: ZIP-215 decompression
(/root/reference/crypto/ed25519/ed25519.go:181 via curve25519-voi),
oracled against ops/fe.sqrt_ratio + ops/ed25519.decompress in
tests/test_pallas_msm.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import fe
from .pallas_msm import (_carry, _eq, _freeze, _mul, _norm_weak,
                         _seq_canonical, _sq as _sqr)

BLK = 512            # lanes per program


def _sq_n(x, n: int):
    # Mosaic's fori_loop lowering supports only unroll=1 (or full
    # unroll at num_steps=2); the r4 smoke run rejected unroll=4.
    return jax.lax.fori_loop(0, n, lambda i, v: _sqr(v), x, unroll=1)


def _pow_p58(z):
    """z**((p-5)/8) — fe._pow_22501's chain with Mosaic-safe ops."""
    z2 = _sqr(z)
    z9 = _mul(_sq_n(z2, 2), z)
    z11 = _mul(z9, z2)
    z2_5_0 = _mul(_sqr(z11), z9)
    z2_10_0 = _mul(_sq_n(z2_5_0, 5), z2_5_0)
    z2_20_0 = _mul(_sq_n(z2_10_0, 10), z2_10_0)
    z2_40_0 = _mul(_sq_n(z2_20_0, 20), z2_20_0)
    z2_50_0 = _mul(_sq_n(z2_40_0, 10), z2_10_0)
    z2_100_0 = _mul(_sq_n(z2_50_0, 50), z2_50_0)
    z2_200_0 = _mul(_sq_n(z2_100_0, 100), z2_100_0)
    z2_250_0 = _mul(_sq_n(z2_200_0, 50), z2_50_0)
    return _mul(_sq_n(z2_250_0, 2), z)



def _add(a, b):
    return _carry(a + b)


def _sub(a, b):
    return _carry(a - b)


def _neg(a):
    return _carry(-a)





# consts tensor rows (passed as one (5, 20, 1) ref)
_C_D, _C_SQRT_M1, _C_ONE, _C_PAD8P, _C_PCANON = range(5)


def _decompress_kernel(words_ref, consts_ref, pt_ref, ok_ref):
    """words (8, BLK) int32 (bit pattern of the LE uint32 words);
    consts (5, 20, 1); pt out (4, 20, BLK); ok out (1, BLK) int32."""
    words = words_ref[...]
    consts = consts_ref[...]
    d = consts[_C_D]
    sqrt_m1 = consts[_C_SQRT_M1]
    one = consts[_C_ONE]
    pad_8p = consts[_C_PAD8P]
    p_canon = consts[_C_PCANON]

    # sign bit 255, via logical shift on the int32 bit pattern
    w7u = words[7].astype(jnp.uint32)
    sign = (w7u >> jnp.uint32(31)).astype(jnp.int32)

    # words -> limbs (fe.words32_to_limbs, value form): limb i takes 13
    # bits at offset 13*i; the sign bit is excluded from limb 19
    wu = words.astype(jnp.uint32)
    limbs = []
    for i in range(fe.NLIMBS):
        bit = fe.RADIX * i
        j, r = bit // 32, bit % 32
        v = wu[j] >> jnp.uint32(r)
        if r + fe.RADIX > 32 and j + 1 < 8:
            v = v | (wu[j + 1] << jnp.uint32(32 - r))
        mask = fe.MASK if i < fe.NLIMBS - 1 else 0xFF
        limbs.append((v & jnp.uint32(mask)).astype(jnp.int32))
    y = jnp.stack(limbs, axis=0)                       # (20, BLK)

    y2 = _sqr(y)
    u = _sub(y2, one)
    v = _add(_mul(y2, jnp.broadcast_to(d, y2.shape)), one)

    # sqrt(u/v): r = u v^3 (u v^7)^((p-5)/8)
    v3 = _mul(_sqr(v), v)
    v7 = _mul(_sqr(v3), v)
    r = _mul(_mul(u, v3), _pow_p58(_mul(u, v7)))
    check = _mul(v, _sqr(r))
    correct = _eq(check, u, pad_8p, p_canon)
    flipped = _eq(check, _neg(u), pad_8p, p_canon)
    x = jnp.where(flipped[None],
                  _mul(r, jnp.broadcast_to(sqrt_m1, r.shape)), r)
    ok = correct | flipped

    xf = _freeze(x, pad_8p, p_canon)
    x_zero = jnp.all(xf == 0, axis=0)
    ok = ok & ~(x_zero & (sign == 1))
    flip = (xf[0] & jnp.int32(1)) != sign
    x = jnp.where(flip[None], _neg(x), x)
    t = _mul(x, y)
    one_b = jnp.broadcast_to(one, y.shape)
    pt_ref[...] = jnp.stack([x, y, one_b, t], axis=0)
    ok_ref[...] = ok.astype(jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("interpret", "blk"))
def _decompress_jit(enc_words, interpret, blk):
    w = enc_words.shape[-1]
    assert w % blk == 0, (w, blk)
    nblk = w // blk
    consts = jnp.stack([
        jnp.asarray(fe.D_LIMBS), jnp.asarray(fe.SQRT_M1_LIMBS),
        jnp.asarray(fe.ONE_LIMBS), jnp.asarray(fe._PAD_8P),
        jnp.asarray(fe._P_CANON)], axis=0).reshape(5, fe.NLIMBS, 1)
    pt, ok = pl.pallas_call(
        _decompress_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((4, fe.NLIMBS, w), jnp.int32),
            jax.ShapeDtypeStruct((1, w), jnp.int32),
        ),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((8, blk), lambda i: (0, i)),
            pl.BlockSpec((5, fe.NLIMBS, 1), lambda i: (0, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((4, fe.NLIMBS, blk), lambda i: (0, 0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
        ),
        interpret=interpret,
    )(enc_words.astype(jnp.uint32).view(jnp.int32), consts)
    return pt, ok[0] != 0


def decompress(enc_words, interpret=False, blk=None):
    """(8, W) uint32 encodings -> ((4, 20, W) extended point, (W,) ok).
    W must be a multiple of blk (default module BLK); the caller
    guards."""
    return _decompress_jit(enc_words, interpret, blk or BLK)
