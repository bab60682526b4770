"""Ed25519 verification as a batched TPU kernel, v3 (limbs-first layout).

Design (TPU-first, profiling-driven — see ops/fe.py for the field
layer and the layout rationale):
- Arrays are limbs-first: field elements (20, B), points (4, 20, B),
  window tables (16, 4, 20, B) — the batch fills the 128-lane minor
  dimension, every op is elementwise, and table selection is a 16-way
  predicated-select cascade (no gathers anywhere).
- Each signature is verified independently; the batch axis is the SPMD
  axis.  One jitted program: decompress A and R, then a shared-doubling
  Straus chain computes s*B - h*A - R with 4-bit windows (64 iterations
  of 4 doublings + 2 cached-form table additions under lax.scan), and
  the cofactored ZIP-215 acceptance [8]*(s*B - h*A - R) == identity.
- h = SHA-512(R||A||M) mod L is computed on the HOST (hashlib is
  C-speed and overlaps with device work); the device receives two
  256-bit scalars per signature.
- Table entries live in "cached" form (Y+X, Y-X, 2d*T, 2Z) so each
  addition is 8 muls; the first three doublings of every window skip
  the unused T output (saves 3 muls/window).
- Per-signature verdicts come out directly — the (ok, []bool) contract
  of the reference BatchVerifier (/root/reference/crypto/crypto.go:47,
  types/validation.go:220-324).

Verification follows ZIP-215 like the reference's voi backend
(/root/reference/crypto/ed25519/ed25519.go:181-240): non-canonical y
accepted, cofactored equation, s < L enforced host-side.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import compile_hook
from . import fe
from . import limbs as lb
from . import scalar25519 as sc
from . import sha2
from ..crypto import ed25519_ref as ref

# ---------------------------------------------------------------------------
# point representation: (4, 20, ...batch), coords on axis 0
# ---------------------------------------------------------------------------

_X, _Y, _Z, _T = 0, 1, 2, 3

import functools as _functools


@_functools.lru_cache(maxsize=1)
def _pallas_capable() -> bool:
    """True when the default backend lowers Pallas/Mosaic for real: a
    TPU.  On cpu/gpu hosts (tests, the virtual-mesh dryrun, CPU-only
    light clients) the XLA path is the product path: interpret-mode
    Pallas would be orders of magnitude slower.  A backend that fails
    to initialise raises here — it must not read as 'no TPU, take the
    XLA path on whatever is there'."""
    return jax.devices()[0].platform == "tpu"


def _pt(x, y, z, t):
    return jnp.stack([x, y, z, t], axis=0)


def identity_point(batch_shape=()):
    one = jnp.broadcast_to(
        jnp.asarray(fe.ONE_LIMBS).reshape((fe.NLIMBS,) + (1,) * len(batch_shape)),
        (fe.NLIMBS,) + batch_shape)
    zero = jnp.zeros((fe.NLIMBS,) + batch_shape, dtype=jnp.int32)
    return _pt(zero, one, one, zero)


def point_double(p, with_t: bool = True):
    """dbl-2008-hwcd for a=-1: 4M+4S (3M+4S without T)."""
    x, y, z = p[_X], p[_Y], p[_Z]
    a = fe.sqr(x)
    b = fe.sqr(y)
    c = fe.mul_word(fe.sqr(z), 2)
    h = fe.add(a, b)
    e = fe.sub(h, fe.sqr(fe.add(x, y)))
    g = fe.sub(a, b)
    f = fe.add(c, g)
    t = fe.mul(e, h) if with_t else jnp.zeros_like(x)
    return _pt(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), t)


def to_cached(p):
    """Extended -> cached (Y+X, Y-X, 2d*T, 2Z): one mul."""
    d2 = fe._bcast(fe.D2_LIMBS, p[_T].ndim)
    return _pt(fe.add(p[_Y], p[_X]),
               fe.sub(p[_Y], p[_X]),
               fe.mul(p[_T], d2),
               fe.mul_word(p[_Z], 2))


def add_cached(p, q):
    """add-2008-hwcd-3 with q pre-cached: 8M, complete for a=-1."""
    a = fe.mul(fe.sub(p[_Y], p[_X]), q[1])
    b = fe.mul(fe.add(p[_Y], p[_X]), q[0])
    c = fe.mul(p[_T], q[2])
    d = fe.mul(p[_Z], q[3])
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return _pt(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_add(p, q):
    """Extended + extended (convenience; hot path uses add_cached)."""
    return add_cached(p, to_cached(q))


def point_neg(p):
    return _pt(fe.neg(p[_X]), p[_Y], p[_Z], fe.neg(p[_T]))


def point_is_identity(p):
    """[X:Y:Z:T] == identity <=> X == 0 and Y == Z (Z != 0 always)."""
    return fe.is_zero(p[_X]) & fe.eq(p[_Y], p[_Z])


# ---------------------------------------------------------------------------
# decompression (ZIP-215: no canonical-y check)
# ---------------------------------------------------------------------------

def decompress(enc_words: jnp.ndarray):
    """(8, ...) uint32 LE words of a 32-byte encoding -> (point, ok):
    the fused kernel (ops/pallas_decompress.py) where _pallas_blk gives
    the width a block, the XLA code everywhere else."""
    if enc_words.ndim == 2:
        from . import pallas_decompress as pd
        blk = _pallas_blk(enc_words.shape[-1], cap=pd.BLK)
        if blk is not None:
            return pd.decompress(enc_words, blk=blk)
    return _decompress_xla(enc_words)


def _decompress_xla(enc_words: jnp.ndarray):
    """decompress on the XLA path: the CPU product path, any batch
    shape, and the reference the kernel is tested against."""
    y = fe.words32_to_limbs(enc_words)
    sign = ((enc_words[7] >> 31) & jnp.uint32(1)).astype(jnp.int32)
    y2 = fe.sqr(y)
    one = fe._bcast(fe.ONE_LIMBS, y.ndim)
    u = fe.sub(y2, one)
    v = fe.add(fe.mul(y2, fe._bcast(fe.D_LIMBS, y.ndim)), one)
    x, ok = fe.sqrt_ratio(u, v)
    xf = fe.freeze(x)
    x_zero = jnp.all(xf == 0, axis=0)
    ok = ok & ~(x_zero & (sign == 1))
    flip = (xf[0] & jnp.int32(1)) != sign
    x = jnp.where(flip[None], fe.neg(x), x)
    t = fe.mul(x, y)
    one_b = jnp.broadcast_to(one, y.shape)
    return _pt(x, y, one_b, t), ok


# ---------------------------------------------------------------------------
# windowed double-scalar multiplication
# ---------------------------------------------------------------------------

WINDOW = 4
NWINDOWS = 64          # 256 bits / 4

# static base-point table k*B (k=0..15) in cached form, (16, 4, 20) const
_BTAB_NP = np.zeros((16, 4, fe.NLIMBS), dtype=np.int32)
for _k, _pt_ref in enumerate(ref.base_window_table(WINDOW)):
    _x, _y, _z, _t = _pt_ref
    _zi = pow(_z, fe.P - 2, fe.P)
    _x, _y = _x * _zi % fe.P, _y * _zi % fe.P
    _BTAB_NP[_k, 0] = fe.int_to_limbs((_y + _x) % fe.P)
    _BTAB_NP[_k, 1] = fe.int_to_limbs((_y - _x) % fe.P)
    _BTAB_NP[_k, 2] = fe.int_to_limbs(fe.D2_INT * _x * _y % fe.P)
    _BTAB_NP[_k, 3] = fe.int_to_limbs(2)


def _nibbles(s: jnp.ndarray) -> jnp.ndarray:
    """(k, ...) uint32 radix-2**16 limbs -> (4k, ...) nibbles, LSB first."""
    nwin = 4 * s.shape[0]
    idx = jnp.arange(nwin) // 4
    shift = (jnp.arange(nwin) % 4) * 4
    shift = shift.reshape((nwin,) + (1,) * (s.ndim - 1))
    return (s[idx] >> shift.astype(jnp.uint32)) & jnp.uint32(0xF)


def _table_rows(p):
    """Window-table rows k*P, k=0..15, extended coords, as ONE stacked
    (16, 4, 20, ...) tensor.  The 14 cumulative adds run under lax.scan
    (sequential anyway) — unrolling them tripled the kernel's HLO size
    and dominated compile time."""
    p_cached = to_cached(p)

    def body(prev, _):
        nxt = add_cached(prev, p_cached)
        return nxt, nxt

    _, rows = jax.lax.scan(body, p, None, length=14)   # 2P..15P
    return jnp.concatenate(
        [identity_point(p.shape[2:])[None], p[None], rows], axis=0)


def _cached_table(p):
    """Per-signature cached window table: (16, 4, 20, ...), one extra
    mul per row for the cached-form conversion (vmapped over rows)."""
    return jax.vmap(to_cached)(_table_rows(p))


def _select(table, nib):
    """table (16, 4, 20, ...), nib (...,) -> (4, 20, ...) via a 16-way
    predicated-select cascade (no gather: lane-aligned selects only)."""
    sel = table[0]
    cond = nib[None, None]                      # (1, 1, ...)
    for k in range(1, 16):
        sel = jnp.where(cond == jnp.uint32(k), table[k], sel)
    return sel


def _select_base(nib):
    """Fixed-base table select: (...,) nibbles -> (4, 20, ...)."""
    ndim = nib.ndim
    tab = jnp.asarray(_BTAB_NP.reshape((16, 4, fe.NLIMBS) + (1,) * ndim))
    sel = jnp.broadcast_to(tab[0], (4, fe.NLIMBS) + nib.shape)
    cond = nib[None, None]
    for k in range(1, 16):
        sel = jnp.where(cond == jnp.uint32(k), tab[k], sel)
    return sel


def verify_kernel(a_words, r_words, s_limbs, h_limbs):
    """Batched ZIP-215 verify, limbs-first layout.

    a_words, r_words: (8, N) uint32 LE words of pubkey / R encodings.
    s_limbs: (16, N) uint32 radix-2**16 scalar limbs (host ensures s < L).
    h_limbs: (16, N) uint32 radix-2**16 limbs of SHA512(R||A||M) mod L
             (host-computed).
    Returns (N,) bool verdicts.
    """
    # decompress A and R in ONE stacked batch (halves op count vs two)
    stacked = jnp.concatenate([a_words, r_words], axis=-1)   # (8, 2N)
    pts, oks = decompress(stacked)
    n = a_words.shape[-1]
    a_pt, r_pt = pts[..., :n], pts[..., n:]
    ok_a, ok_r = oks[..., :n], oks[..., n:]

    neg_a_tab = _cached_table(point_neg(a_pt))
    s_nib = _nibbles(s_limbs)        # (64, N)
    h_nib = _nibbles(h_limbs)

    def step(acc, xs):
        s_n, h_n = xs
        acc = point_double(acc, with_t=False)
        acc = point_double(acc, with_t=False)
        acc = point_double(acc, with_t=False)
        acc = point_double(acc, with_t=True)
        acc = add_cached(acc, _select_base(s_n))
        acc = add_cached(acc, _select(neg_a_tab, h_n))
        return acc, None

    xs = (s_nib[::-1], h_nib[::-1])
    acc = identity_point(a_words.shape[1:])
    acc, _ = jax.lax.scan(step, acc, xs)

    acc = add_cached(acc, to_cached(point_neg(r_pt)))
    for _ in range(3):               # cofactor 8
        acc = point_double(acc, with_t=False)
    return ok_a & ok_r & point_is_identity(acc)


# ---------------------------------------------------------------------------
# random-linear-combination batch verification (v4: split A/R MSMs)
# ---------------------------------------------------------------------------
#
# One shared equation for the whole batch (the reference's voi backend
# does the same, /root/reference/crypto/ed25519/ed25519.go:208-240):
#
#   [8] * ( sum_i z_i*s_i * B  -  sum_i (z_i*h_i)*A_i  -  sum_i z_i*R_i ) == 0
#
# with z_i random 128-bit scalars.  Host preprocessing (pack_rlc):
# - scalars for REPEATED pubkeys are aggregated mod L (sum_i zh_i*A_i
#   over signatures collapses to sum_k (sum zh_i)*A_k over DISTINCT
#   keys) — a light-client syncing 10k headers against one validator
#   set pays the A-side cost once per validator, not once per sig;
# - the fixed-base term rides in an A slot (A=-B, coeff c=sum z_i*s_i).
#
# The device then runs TWO independent Straus MSMs and adds them:
# - A-MSM: K distinct keys x 256-bit aggregated scalars (64 windows);
#   K is usually << N so its windows are nearly free;
# - R-MSM: N nonces x 128-bit z_i (32 windows) — the per-signature
#   marginal cost is ~1 tree point-add per window for 32 windows,
#   instead of 64, plus decompression and the 15-add window table.
#
# Why Straus-with-tree beats Pippenger here: bucket accumulation needs
# data-dependent scatters (terrible on TPU); the select cascade + dense
# lane-parallel tree reduction keeps every op static-shaped and
# elementwise, which is what the VPU wants.
#
# RLC yields ONE verdict; per-signature localization falls back to
# verify_kernel, mirroring verifyCommitBatch -> verifyCommitSingle
# (/root/reference/types/validation.go:115).

NPART_MAX = 192      # max lane-resident partial accumulators

_SMALL_WIDTHS = (8, 16, 32, 64, 96, 128, 160, 192)
_BASE_WIDTHS = (128, 160, 192)


def _grid_widths():
    """The bucket grid, ascending: the small widths verbatim, then
    base*2^L with base in a 3-element grid — bounds the number of
    compiled shapes while keeping pad waste <= 25% (a plain next-pow2
    pad wastes up to 100%: K=4097 -> 8192)."""
    yield from _SMALL_WIDTHS
    lvl = 1
    while True:
        for base in _BASE_WIDTHS:
            yield base << lvl
        lvl += 1


def pad_width(n: int) -> int:
    """Bucketed batch width for an MSM side: the first grid width that
    holds n and — where the Pallas kernels lower for real
    (_pallas_capable) — that a Pallas block divides
    (pallas_msm.blk_for), so no RLC batch packed on the chip lowers to
    the XLA Straus scan: n <= 128 -> 128, 129..256 -> 256,
    257..320 -> 384 at the default block, the rest of the grid as it
    is.  A 58-signature batch at width 64 kept the chip busy 22 ms on
    the XLA path where 3,744 signatures at 4096 take 4 on the kernels
    (PERF.md); pad slots contribute the identity (pack_rlc).  Off the
    chip the XLA path is the product path and the grid is used as is."""
    grid = (w for w in _grid_widths() if w >= n)
    first = w = next(grid)
    if not _pallas_capable():
        return first
    from . import pallas_msm
    while pallas_msm.blk_for(w) is None:
        if w >= 512:
            # any block that is legal at all gives 512 a block: none
            # is (pallas_msm.BLK <= 0), every width takes the XLA
            # path, so keep the grid's own
            return first
        w = next(grid)
    return w


def _npart(w: int) -> int:
    """Partial-accumulator count: halve the width until <= NPART_MAX."""
    while w > NPART_MAX:
        assert w % 2 == 0
        w //= 2
    return w


def _tree_reduce(pts, target):
    """(4, 20, W) extended points -> (4, 20, target) by pairwise adds.
    Odd widths fold the leftover lane back in (widths are multiples of
    the partial count until the final reduce-to-one)."""
    while pts.shape[-1] > target:
        w = pts.shape[-1]
        half = w // 2
        left = point_add(pts[..., :half], pts[..., half:2 * half])
        if w % 2:
            left = jnp.concatenate([left, pts[..., 2 * half:]], axis=-1)
        pts = left
    return pts


def _table17(p):
    """Rows k*P for k=0..16, extended coords, (17, 4, 20, ...) —
    signed-window tables need magnitude 16."""
    p_cached = to_cached(p)

    def body(prev, _):
        nxt = add_cached(prev, p_cached)
        return nxt, nxt

    _, rows = jax.lax.scan(body, p, None, length=15)   # 2P..16P
    return jnp.concatenate(
        [identity_point(p.shape[2:])[None], p[None], rows], axis=0)


def _select17(table, mag):
    """(17, 4, 20, W) table, (W,) int32 magnitudes -> (4, 20, W)."""
    sel = table[0]
    cond = mag[None, None]
    for k in range(1, 17):
        sel = jnp.where(cond == jnp.int32(k), table[k], sel)
    return sel


def _cond_neg_point(p, neg):
    """Negate extended points where neg: X -> -X, T -> -T (redundant
    signed limbs: plain arithmetic negation, normalized by the next
    add's carry passes)."""
    n = neg[None]
    return _pt(jnp.where(n, -p[_X], p[_X]), p[_Y], p[_Z],
               jnp.where(n, -p[_T], p[_T]))


def _pallas_blk(w: int, cap: int | None = None):
    """Lane block the Pallas kernels take at side width w, or None when
    that side runs the XLA path: off the chip, or at a width no
    power-of-two block >= 128 divides (pallas_msm.blk_for: 64, 192).
    On the chip pad_width returns no such width, so only a caller that
    packs at a width of its own lands here.

    This is the one choice of kernel: decompress, _msm_tables,
    _msm_side and _rlc_verdict ask nothing else, and nothing can be
    set.  Pallas against the XLA scan is the fork the ledger measured
    (PERF_LEDGER.jsonl, PR 26: the 58-signature remainder 22.35 ms ->
    0.595 ms on the chip)."""
    if not _pallas_capable():
        return None
    from . import pallas_msm
    return pallas_msm.blk_for(w, cap=cap)


def _msm_tables(enc_words):
    """Decompress one MSM side and build its negated 17-row window
    tables: (8, W) encodings -> ((17, 4, 20, W) table, all-ok bool).
    A function of its own so a repeated side (the distinct-pubkey A
    side of a validator set verifying many commits) can be built ONCE
    and cached on device — the reference caches expanded pubkeys for
    the same reason (/root/reference/crypto/ed25519/ed25519.go:64)."""
    pt, ok = decompress(enc_words)
    blk = _pallas_blk(pt.shape[-1])
    if blk is not None:
        from . import pallas_msm
        return pallas_msm.table17_neg(pt, blk=blk), jnp.all(ok)
    return _table17(point_neg(pt)), jnp.all(ok)


def _msm_scan_xla(tab, mags, negs):
    """Shared-doubling Straus scan over pre-built window tables, on the
    XLA path: sum_i e_i * (-P_i) with SIGNED 5-bit windows.

    tab: (17, 4, 20, W); mags: (nwin, W) int32 digit magnitudes 0..16,
    MSB-first; negs: (nwin, W) bool signs (host recoding,
    crypto/ed25519._recode_w5: 26 windows for the 128-bit z_i, 52 for
    the 256-bit aggregated zh).  5 doublings/window act on <= NPART_MAX
    lane-resident partials.  Returns a (4, 20, 1) point."""
    npart = _npart(tab.shape[-1])

    def step(acc, xs):
        mag, neg = xs
        acc = point_double(acc, with_t=False)
        acc = point_double(acc, with_t=False)
        acc = point_double(acc, with_t=False)
        acc = point_double(acc, with_t=False)
        acc = point_double(acc, with_t=True)
        contrib = _cond_neg_point(_select17(tab, mag), neg)
        return point_add(acc, _tree_reduce(contrib, npart)), None

    acc = identity_point((npart,))
    acc, _ = jax.lax.scan(step, acc, (mags, negs))
    return _tree_reduce(acc, 1)


def _msm_side(tab, mags, negs):
    """One MSM side as points whose lane-sum is the MSM: the
    window-major kernel's (4, 20, out_lanes) accumulator where
    _pallas_blk gives the width a block, the XLA scan's (4, 20, 1)
    point where it gives none."""
    blk = _pallas_blk(tab.shape[-1])
    if blk is None:
        return _msm_scan_xla(tab, mags, negs)
    from . import pallas_msm
    return pallas_msm.msm_window_major(tab, mags, negs, blk=blk)


def rlc_kernel_plan(k: int, n: int) -> dict:
    """Which kernel each stage of the RLC program at A width k, R width
    n lowers to — the predicate rlc_verify_kernel traces through
    (_pallas_blk), so an operator (and chip_smoke.py) can read 'pallas'
    or 'xla' per MSM side instead of inferring it from a width."""
    from . import pallas_decompress as pd

    def name(pallas):
        return "pallas" if pallas else "xla"

    def side(w):
        blk = _pallas_blk(w)
        return {"width": w, "blk": blk,
                "decompress": name(_pallas_blk(w, cap=pd.BLK) is not None),
                "tables": name(blk is not None),
                "msm": name(blk is not None)}

    a, r = side(k), side(n)
    return {"a": a, "r": r,
            "fold": name(a["blk"] is not None and r["blk"] is not None)}


@_functools.lru_cache(maxsize=None)
def _rlc_kernel_name(k: int, n: int, capable: bool) -> str:
    # capable is only part of the key: the plan asks _pallas_capable
    plan = rlc_kernel_plan(k, n)
    stages = {plan["fold"]} | {plan[side][stage] for side in ("a", "r")
                               for stage in ("decompress", "tables", "msm")}
    return "pallas" if stages == {"pallas"} else "xla"


def rlc_kernel_name(k: int, n: int) -> str:
    """'pallas' where every stage of rlc_kernel_plan(k, n) is a Pallas
    kernel, 'xla' where any stage lowers to the XLA path: what a
    verify.dispatch span says of the program it dispatched.  Memoised
    per (k, n), and per backend capability so a test that patches it
    reads its own answer: a dispatch pays a dictionary lookup."""
    return _rlc_kernel_name(k, n, _pallas_capable())


def _prefold(partials):
    """XLA reduction of a partial tensor down to the fold kernel's VMEM
    bound — only the wide (efficient) levels run here.  The
    window-major kernel hands over one accumulator of at most 128
    lanes, so on the product path nothing is left to do; a wider
    tensor of m*128 lanes is halved while the half stays 128-aligned,
    and an odd m chunk-sums its tail into the MAX_FOLD_LANES-wide
    head."""
    from . import pallas_msm
    bound = pallas_msm.MAX_FOLD_LANES
    while partials.shape[-1] > bound:
        w = partials.shape[-1]
        half = w // 2
        if half % 128 == 0:
            partials = point_add(partials[..., :half], partials[..., half:])
            continue
        acc = partials[..., :bound]
        off = bound
        while off < w:
            n = min(bound, w - off)
            acc = jnp.concatenate(
                [point_add(acc[..., :n], partials[..., off:off + n]),
                 acc[..., n:]], axis=-1)
            off += bound
        partials = acc
    return partials


def _rlc_verdict(ok_a, ok_r, tab_a, tab_r, a_mag, a_neg, r_mag, r_neg):
    """Both MSMs over built tables and the cofactored identity check
    [8](A + R) == 0, as the batch's one bool: the fused fold
    (pallas_msm.fold_verify) where both sides ran the window-major
    kernel, else add, three doublings, identity check on the XLA
    path."""
    pa = _msm_side(tab_a, a_mag, a_neg)         # 52 windows, width K
    pr = _msm_side(tab_r, r_mag, r_neg)         # 26 windows, width N
    if _pallas_blk(tab_a.shape[-1]) is not None \
            and _pallas_blk(tab_r.shape[-1]) is not None:
        from . import pallas_msm
        return ok_a & ok_r & pallas_msm.fold_verify(_prefold(pa),
                                                    _prefold(pr))
    total = point_add(_tree_reduce(pa, 1), _tree_reduce(pr, 1))
    for _ in range(3):               # cofactor 8
        total = point_double(total, with_t=False)
    return ok_a & ok_r & point_is_identity(total)[0]


def rlc_verify_kernel(a_words, r_words, a_mag, a_neg, r_mag, r_neg):
    """Whole-batch RLC verify: one bool verdict.

    a_words: (8, K) uint32 LE words of the DISTINCT pubkey encodings
             (plus the -B fixed-base slot and benign pads);
    r_words: (8, N) R encodings.
    a_mag/a_neg: (52, K) signed-window digits of the aggregated z*h
    mod L; r_mag/r_neg: (26, N) digits of the 128-bit z_i; MSB-first.
    """
    tab_a, ok_a = _msm_tables(a_words)
    tab_r, ok_r = _msm_tables(r_words)
    return _rlc_verdict(ok_a, ok_r, tab_a, tab_r,
                        a_mag, a_neg, r_mag, r_neg)


_rlc_jitted = jax.jit(rlc_verify_kernel)


def rlc_verify_device(a_words, r_words, a_mag, a_neg, r_mag, r_neg):
    with compile_hook.dispatch_scope(
            "ed25519_rlc", (a_words.shape[-1], r_words.shape[-1])):
        return _rlc_jitted(a_words, r_words, a_mag, a_neg, r_mag,
                           r_neg)


def rlc_verify_kernel_cached_a(a_tab, a_ok, r_words,
                               a_mag, a_neg, r_mag, r_neg):
    """RLC verify with a PRE-BUILT A-side table (see _msm_tables):
    skips the A decompression (two ~270-mul sqrt chains per distinct
    key — the measured per-point floor) and the 16 sequential table
    adds, the dominant A-side cost when the same validator set verifies
    a stream of commits (light-client sync, blocksync replay)."""
    r_tab, ok_r = _msm_tables(r_words)
    return _rlc_verdict(a_ok, ok_r, a_tab, r_tab,
                        a_mag, a_neg, r_mag, r_neg)


_a_tables_jitted = jax.jit(_msm_tables)
_rlc_cached_jitted = jax.jit(rlc_verify_kernel_cached_a)


def build_a_tables_device(a_words):
    """One-time device build of an A-side table for the cache."""
    with compile_hook.dispatch_scope("ed25519_a_tables",
                                     a_words.shape[-1:]):
        return _a_tables_jitted(a_words)


def rlc_verify_device_cached_a(a_tab, a_ok, r_words,
                               a_mag, a_neg, r_mag, r_neg):
    with compile_hook.dispatch_scope(
            "ed25519_rlc_cached", (a_tab.shape[-1], r_words.shape[-1])):
        return _rlc_cached_jitted(a_tab, a_ok, r_words,
                                  a_mag, a_neg, r_mag, r_neg)


# jitted entry with bucketed batch sizes to avoid re-compiles
_jitted = jax.jit(verify_kernel)

BATCH_BUCKETS = (16, 64, 256, 1024, 4096, 16384)


def bucket_size(n: int) -> int:
    for b in BATCH_BUCKETS:
        if n <= b:
            return b
    return ((n + BATCH_BUCKETS[-1] - 1) // BATCH_BUCKETS[-1]) * BATCH_BUCKETS[-1]


def verify_batch_device(a_words, r_words, s_limbs, h_limbs):
    with compile_hook.dispatch_scope("ed25519_persig",
                                     a_words.shape[-1:]):
        return _jitted(a_words, r_words, s_limbs, h_limbs)


# ---------------------------------------------------------------------------
# fused hash-to-scalar verify (device-side h = SHA512(R||A||M) mod L)
# ---------------------------------------------------------------------------
#
# The RLC path above still receives h_i REDUCTIONS from the host: every
# signature's SHA-512 runs through hashlib and the per-pubkey z*h
# aggregation plus the signed-window recode run in numpy — the largest
# host stage left on the blocksync critical path.  The fused variant
# moves all of it onto the device:
#
#   h_i   = SHA512(R_i || A_i || M_i) mod L      (sha2 kernel + Barrett)
#   zh_i  = z_i * h_i mod L                      (limb mul + Barrett)
#   agg_k = (base_k + sum_{group(i)=k} zh_i) mod L
#   digits= signed 5-bit recode of agg_k          (bias trick, below)
#
# and feeds the digits straight into rlc_verify_kernel — no digest or
# scalar ever crosses back to the host.  The host ships raw padded
# message blocks, the 128-bit z_i as limbs, a per-signature group id
# mapping each sig to its distinct-pubkey A slot, and per-slot host
# scalars (slot 0 carries c = sum z_i*s_i mod L for the -B fixed-base
# term; every other slot is zero).  Filler signatures carry z = 0 so
# their zh vanishes no matter what their (zeroed) blocks hash to.
#
# Signed-digit recode without a sequential carry sweep: the signed
# 5-bit digits of x are exactly the base-32 digits of x + BIAS minus
# 16, where BIAS = sum_j 16*32**j — adding 16 to every digit position
# pre-pays the worst-case borrow, turning the host's data-dependent
# carry loop into one limb addition plus static bit extraction.

_NDIG_A = 52                       # 256-bit scalars, 5-bit windows
_W5_BIAS_LIMBS = lb.int_to_limbs(
    sum(16 << (5 * j) for j in range(_NDIG_A)), 17)
_SEG_BYTES = 36                    # sum_i zh_i < 2**17 * L < 2**270


def _h_scalars(blocks_hi, blocks_lo, n_blocks):
    """Padded message blocks -> (N, 16) limbs of SHA512(msg) mod L."""
    sh, sl = sha2.sha512_blocks(blocks_hi, blocks_lo, n_blocks)
    return sc.barrett_reduce_wide(sc.digest512_to_wide_limbs(sh, sl))


def _zh_mod_l(z_limbs, h_limbs):
    """(N, 8) z limbs x (N, 16) h limbs -> (N, 16) z*h mod L.

    The 384-bit product is < 2**381 < 2**512, inside Barrett's domain.
    """
    prod = lb.mul(z_limbs, h_limbs)                       # (N, 24)
    zeros = jnp.zeros(prod.shape[:-1] + (sc.WIDE - prod.shape[-1],),
                      dtype=jnp.uint32)
    return sc.barrett_reduce_wide(jnp.concatenate([prod, zeros], axis=-1))


def _segment_sum_mod_l(zh, group_ids, k):
    """Per-A-slot sum of zh rows mod L: (N, 16) x (N,) -> (k, 16).

    The scatter-add runs in radix 2**8: each 16-bit limb splits into
    two byte columns, so a column accumulates at most N * 255 < 2**25
    per lane at the 131071-sig max shape — no uint32 overflow, unlike a
    direct 16-bit-limb scatter which overflows past N = 65536.  A
    static byte-radix carry sweep then renormalizes before Barrett.
    """
    cols = jnp.stack([zh & jnp.uint32(0xFF), zh >> 8],
                     axis=-1).reshape(zh.shape[:-1] + (2 * zh.shape[-1],))
    acc = jnp.zeros((k, cols.shape[-1]), dtype=jnp.uint32)
    acc = acc.at[group_ids].add(cols)
    out = []
    carry = jnp.zeros((k,), dtype=jnp.uint32)
    for j in range(_SEG_BYTES):
        v = carry if j >= acc.shape[-1] else acc[..., j] + carry
        out.append(v & jnp.uint32(0xFF))
        carry = v >> 8
    by = jnp.stack(out, axis=-1)                          # (k, 36) bytes
    limbs = by[..., 0::2] | (by[..., 1::2] << 8)          # (k, 18)
    zeros = jnp.zeros((k, sc.WIDE - limbs.shape[-1]), dtype=jnp.uint32)
    return sc.barrett_reduce_wide(jnp.concatenate([limbs, zeros], axis=-1))


def _add_mod_l(a, b):
    """(…, 16) + (…, 16) mod L for inputs already < L."""
    s, _ = lb.carry_prop(a + b)                           # sum < 2L < 2**254
    return lb.cond_sub(s, jnp.asarray(sc.L_LIMBS))


def _recode_w5_device(scalars):
    """(K, 16) limbs (< L) -> ((52, K), (52, K)) signed-window digit
    magnitudes and signs, MSB-first — bit-identical to the host
    crypto/ed25519._recode_w5 (pinned by tests/test_device_hash.py).
    The bias addition stays here (it owns the scalar-limb carry
    discipline); the digit extraction is the engine's generic
    any-width form."""
    from . import msm as msm_engine

    pad = jnp.zeros(scalars.shape[:-1] + (1,), dtype=jnp.uint32)
    xb, _ = lb.carry_prop(
        jnp.concatenate([scalars, pad], axis=-1) +
        jnp.asarray(_W5_BIAS_LIMBS))                      # (K, 17)
    return msm_engine.recode_biased_digits(xb, 5, _NDIG_A)


def rlc_verify_hash_kernel(a_words, r_words, base_limbs, z_limbs,
                           group_ids, blocks_hi, blocks_lo, n_blocks,
                           r_mag, r_neg):
    """Whole-batch RLC verify with DEVICE-side hash-to-scalar.

    a_words: (8, K) distinct-pubkey encodings (slot 0 = -B, pads = B);
    r_words: (8, N) R encodings.
    base_limbs: (K, 16) host scalar per A slot (slot 0 = c = sum z*s
                mod L, others zero); z_limbs: (N, 8) 128-bit z_i;
    group_ids: (N,) int32 A-slot index per signature (fillers -> 0,
               where z = 0 keeps them inert);
    blocks_hi/lo: (N, B, 16) padded SHA-512 blocks of R||A||M;
    n_blocks: (N,); r_mag/r_neg: (26, N) z_i window digits, MSB-first.
    Returns one bool verdict.
    """
    h = _h_scalars(blocks_hi, blocks_lo, n_blocks)        # (N, 16)
    zh = _zh_mod_l(z_limbs, h)                            # (N, 16)
    seg = _segment_sum_mod_l(zh, group_ids, a_words.shape[-1])
    a_mag, a_neg = _recode_w5_device(_add_mod_l(base_limbs, seg))
    return rlc_verify_kernel(a_words, r_words, a_mag, a_neg, r_mag, r_neg)


def verify_hash_kernel(a_words, r_words, s_limbs, blocks_hi, blocks_lo,
                       n_blocks):
    """Per-signature verify with device-side hashing: the reject
    localization path of the fused mode, so digests stay on device even
    when a batch fails and individual verdicts are needed."""
    h = _h_scalars(blocks_hi, blocks_lo, n_blocks)        # (N, 16)
    return verify_kernel(a_words, r_words, s_limbs,
                         jnp.moveaxis(h, -1, 0))


_rlc_hash_jitted = jax.jit(rlc_verify_hash_kernel)
_hash_jitted = jax.jit(verify_hash_kernel)


def rlc_verify_hash_device(a_words, r_words, base_limbs, z_limbs,
                           group_ids, blocks_hi, blocks_lo, n_blocks,
                           r_mag, r_neg):
    with compile_hook.dispatch_scope("ed25519_rlc_hash",
                                     blocks_hi.shape):
        return _rlc_hash_jitted(a_words, r_words, base_limbs, z_limbs,
                                group_ids, blocks_hi, blocks_lo,
                                n_blocks, r_mag, r_neg)


def verify_batch_hash_device(a_words, r_words, s_limbs, blocks_hi,
                             blocks_lo, n_blocks):
    with compile_hook.dispatch_scope("ed25519_persig_hash",
                                     blocks_hi.shape):
        return _hash_jitted(a_words, r_words, s_limbs, blocks_hi,
                            blocks_lo, n_blocks)
