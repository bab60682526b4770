"""Host-side Ed25519 API: keys, signing, and the TPU batch-verify bridge.

Mirrors the seam of the reference's crypto/ed25519 package
(/root/reference/crypto/ed25519/ed25519.go: PrivKey.Sign :45,
PubKey.VerifySignature :181, BatchVerifier :208) but the batch path packs
signatures into uint32 device arrays and runs one jitted TPU program
(ops/ed25519.verify_kernel) instead of per-signature CPU verification.
"""

from __future__ import annotations

import os

from dataclasses import dataclass

import numpy as np

from functools import lru_cache

from . import ed25519_ref as ref
from ..libs import lockrank
from ..libs.trace import span as trace_span
from .hash import sum_sha256

KEY_TYPE = "ed25519"
PUBKEY_SIZE = 32
PRIVKEY_SIZE = 64          # seed || pubkey, like the reference golang layout
SIGNATURE_SIZE = 64
L = ref.L


@dataclass(frozen=True)
class PubKey:
    data: bytes

    def __post_init__(self):
        if len(self.data) != PUBKEY_SIZE:
            raise ValueError("ed25519 pubkey must be 32 bytes")

    def type(self) -> str:
        return KEY_TYPE

    def bytes(self) -> bytes:
        return self.data

    def address(self) -> bytes:
        """First 20 bytes of SHA-256, the reference's address rule."""
        return sum_sha256(self.data)[:20]

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        """Single verify — the live-consensus per-vote hot path
        (reference types/vote_set.go:219-223 -> ed25519.go:181).

        Fast path: OpenSSL's strict cofactorless RFC-8032 verify.  Its
        accept set is a SUBSET of ZIP-215 (sB = R + hA implies
        [8]sB = [8]R + [8]hA, and it only accepts canonical encodings
        ZIP-215 also accepts), so True is always final; only a rejection
        falls back to the from-scratch ZIP-215 reference check, keeping
        batch/single semantics identical while honest signatures cost
        ~100 us instead of ~4 ms of pure-Python bignum math.
        """
        fast = _openssl_verifier(self.data)
        if fast is not None:
            if fast(msg, sig):
                return True
        return ref.verify(self.data, msg, sig)

    def __bytes__(self):
        return self.data


@lru_cache(maxsize=4096)
def _openssl_verifier(pub: bytes):
    """Parsed-key cache, the analog of the reference's 4096-entry
    expanded-pubkey LRU (ed25519.go:64-70). Returns None if OpenSSL is
    unavailable or the key fails to parse (non-canonical encodings the
    ZIP-215 path must judge)."""
    try:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PublicKey)
    except ImportError:  # pragma: no cover
        return None
    try:
        key = Ed25519PublicKey.from_public_bytes(pub)
    except ValueError:
        return None

    def check(msg: bytes, sig: bytes) -> bool:
        try:
            key.verify(sig, msg)
            return True
        except (InvalidSignature, ValueError):
            return False

    return check


@dataclass(frozen=True)
class PrivKey:
    data: bytes              # seed(32) || pubkey(32)

    def __post_init__(self):
        if len(self.data) != PRIVKEY_SIZE:
            raise ValueError("ed25519 privkey must be 64 bytes")

    @staticmethod
    def generate(seed: bytes | None = None) -> "PrivKey":
        seed, pub = ref.keygen(seed)
        return PrivKey(seed + pub)

    def type(self) -> str:
        return KEY_TYPE

    def bytes(self) -> bytes:
        return self.data

    def pub_key(self) -> PubKey:
        return PubKey(self.data[32:])

    def sign(self, msg: bytes) -> bytes:
        # Prefer the constant-time OpenSSL path (the pure-Python reference
        # signer is variable-time and only safe for tests/tools).
        try:
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PrivateKey)
            return Ed25519PrivateKey.from_private_bytes(
                self.data[:32]).sign(msg)
        except ImportError:  # pragma: no cover
            return ref.sign(self.data[:32], msg)


def parse_signature(sig: bytes) -> tuple[bytes, int] | None:
    """Split sig into (R_enc, s) and range-check s < L (RFC 8032 / ZIP-215)."""
    if len(sig) != SIGNATURE_SIZE:
        return None
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return None
    return sig[:32], s


def parse_and_hash(pubkeys: list[bytes], msgs: list[bytes],
                   sigs: list[bytes]) -> list[tuple[bytes, int, int] | None]:
    """Host-side structural parse + hash, done ONCE per batch: for each
    entry (r_enc, s, h = SHA512(R||A||M) mod L) or None on a structural
    reject.  Both device packings (per-signature and RLC) build from
    this, so a fallback never re-hashes messages."""
    import hashlib

    out = []
    for pk, msg, sig in zip(pubkeys, msgs, sigs):
        parsed = parse_signature(sig) if len(pk) == PUBKEY_SIZE else None
        if parsed is None:
            out.append(None)
            continue
        r_enc, s = parsed
        h = int.from_bytes(
            hashlib.sha512(r_enc + pk + msg).digest(), "little") % L
        out.append((r_enc, s, h))
    return out


def pack_batch(pubkeys: list[bytes], msgs: list[bytes], sigs: list[bytes],
               batch_size: int, parsed=None):
    """Pack a signature batch into device-ready numpy arrays.

    h = SHA512(R||A||M) mod L is computed HERE on the host (hashlib is
    C-speed; it overlaps with device work and keeps the device program
    small — round-2 redesign, see ops/ed25519.py).  Entries failing
    host-side structural checks (bad lengths, s >= L) get a
    pre-determined False verdict via the `valid` mask; their slots are
    filled with benign data so the kernel stays branch-free.

    Arrays are LIMBS-FIRST (v3 kernel layout: batch in the minor/lane
    dimension): returns (a_words (8,B), r_words (8,B), s_limbs (16,B),
    h_limbs (16,B), valid (B,)).
    """
    from ..ops import limbs as lb

    n = len(pubkeys)
    assert batch_size >= n
    if parsed is None:
        parsed = parse_and_hash(pubkeys, msgs, sigs)
    valid = np.zeros(batch_size, dtype=bool)
    a_words = np.zeros((batch_size, 8), dtype=np.uint32)
    r_words = np.zeros((batch_size, 8), dtype=np.uint32)
    s_limbs = np.zeros((batch_size, 16), dtype=np.uint32)
    h_limbs = np.zeros((batch_size, 16), dtype=np.uint32)
    dummy = ref.point_compress(ref.B)
    for i in range(n):
        if parsed[i] is None:
            continue
        r_enc, s, h = parsed[i]
        valid[i] = True
        a_words[i] = np.frombuffer(pubkeys[i], dtype=np.uint32)
        r_words[i] = np.frombuffer(r_enc, dtype=np.uint32)
        s_limbs[i] = lb.int_to_limbs(s, 16)
        h_limbs[i] = lb.int_to_limbs(h, 16)
    # benign filler so decompression of invalid slots still succeeds
    filler = np.frombuffer(dummy, dtype=np.uint32)
    a_words[~valid] = filler
    r_words[~valid] = filler
    return (np.ascontiguousarray(a_words.T),
            np.ascontiguousarray(r_words.T),
            np.ascontiguousarray(s_limbs.T),
            np.ascontiguousarray(h_limbs.T), valid)


NDIG_128 = 26       # signed-5-bit digits covering 128-bit z (+carry)
NDIG_256 = 52       # covering scalars < L (253 bits, +carry)


def _recode_nbytes(ndig: int) -> int:
    """Little-endian byte width of _recode_w5's raw input rows."""
    return (5 * ndig + 7) // 8 + 1


def _recode_w5_scalar(values: list[int], ndig: int, width: int):
    """Pure-Python reference recoding, one value and one digit at a
    time (the pre-vectorization semantics, LSB-up carry sweep): the
    parity oracle `_recode_w5` and the device-side recode are pinned
    against in tests/test_recode.py."""
    mag = np.zeros((width, ndig), np.int32)
    neg = np.zeros((width, ndig), bool)
    for i, v in enumerate(values):
        assert v < 1 << (5 * ndig), \
            "scalar out of range for recoding width"
        digs = [(v >> (5 * j)) & 31 for j in range(ndig)]
        carry = 0
        for j in range(ndig):
            d = digs[j] + carry
            carry = 1 if d > 15 else 0
            digs[j] = d - 32 if d > 15 else d
        assert carry == 0, "scalar out of range for recoding width"
        mag[i] = [abs(d) for d in digs]
        neg[i] = [d < 0 for d in digs]
    return (np.ascontiguousarray(mag.T[::-1]),
            np.ascontiguousarray(neg.T[::-1]))


def _recode_w5(values, ndig: int, width: int):
    """Signed radix-32 recoding: each value becomes ndig digits in
    [-16, 15], emitted MSB-first as separate magnitude (int32) and sign
    (bool) arrays of shape (ndig, width).  Pad columns beyond
    len(values) stay zero (identity contribution).

    Fully vectorized via the bias trick: the signed digits of x are the
    plain base-32 digits of x + BIAS minus 16, where
    BIAS = sum_j 16*32**j — pre-paying the worst-case borrow turns the
    old data-dependent carry sweep into one addition plus static bit
    extraction, and is the exact algorithm the device recode
    (ops/ed25519._recode_w5_device) runs.  `values` is either a
    list[int] or an already-raw (n, _recode_nbytes(ndig)) uint8 array
    of little-endian bytes (the device-hash packer hands z straight
    from its random byte block, never materializing Python ints)."""
    n = len(values)
    mag = np.zeros((width, ndig), np.int32)
    neg = np.zeros((width, ndig), bool)
    if n:
        nbytes = _recode_nbytes(ndig)
        if isinstance(values, np.ndarray):
            assert values.shape == (n, nbytes) and values.dtype == np.uint8
            raw = values.astype(np.uint16)
        else:
            assert max(values) < 1 << (5 * ndig), \
                "scalar out of range for recoding width"
            raw = np.frombuffer(
                b"".join(v.to_bytes(nbytes, "little") for v in values),
                dtype=np.uint8).reshape(n, nbytes).astype(np.uint16)
        bias = np.frombuffer(
            sum(16 << (5 * j) for j in range(ndig)).to_bytes(
                nbytes, "little"), dtype=np.uint8).astype(np.uint16)
        acc = raw + bias                      # per-byte sums < 2**9
        carry = np.zeros(n, np.uint16)
        for k in range(nbytes):
            t = acc[:, k] + carry
            acc[:, k] = t & 0xFF
            carry = t >> 8
        assert not carry.any(), "scalar out of range for recoding width"
        digs = np.empty((n, ndig), np.int16)
        for j in range(ndig):
            off = 5 * j
            k, sh = off >> 3, off & 7
            word = acc[:, k] | (acc[:, k + 1] << 8)
            digs[:, j] = (((word >> sh) & 31).astype(np.int16)) - 16
        mag[:n] = np.abs(digs)
        neg[:n] = digs < 0
    return (np.ascontiguousarray(mag.T[::-1]),
            np.ascontiguousarray(neg.T[::-1]))


def _neg_b_encoding() -> bytes:
    """Compressed -B: flip the x-sign bit of the base point encoding."""
    enc = bytearray(ref.point_compress(ref.B))
    enc[31] ^= 0x80
    return bytes(enc)


_NEG_B_ENC = None


def pack_rlc(pubkeys: list[bytes], msgs: list[bytes], sigs: list[bytes],
             parsed=None):
    """Pack a batch for the device RLC kernel (ops/ed25519.rlc_verify_kernel).

    Host work per signature: h = SHA512(R||A||M) mod L, a random
    128-bit z, zh = z*h mod L.  Two preprocessing steps shrink the
    device program (v4 kernel, split A/R MSMs):

    - REPEATED pubkeys aggregate: zh coefficients for the same 32-byte
      A encoding are summed mod L, so the A-side MSM runs over DISTINCT
      keys only (a 150-validator set verifying 10k commits costs 150 A
      slots, not 1.5M).
    - the fixed-base term c = sum z_i*s_i mod L rides in A slot 0 as
      (-B, c).

    Both batches pad to bucketed widths (ops/ed25519.pad_width); pad
    slots hold the base point with zero scalar and contribute the
    identity.  Scalars are recoded into signed 5-bit window digits.

    One native call does all of it for the batch (crypto/rlcpack.py),
    outside the interpreter lock; _pack_rlc_python is the same
    algorithm in Python, the oracle the library is pinned against and
    what serves a host without a toolchain or a caller that brings
    `parsed` (parse_and_hash's rows: the sr25519 bridge, whose h is no
    SHA-512).  Which one packed is in
    cometbft_device_host_pack_signatures_total{packer}.

    Returns (a_words (8,K), r_words (8,N), a_mag (52,K), a_neg (52,K),
    r_mag (26,N), r_neg (26,N)) limbs-first/MSB-first, or None if any
    entry fails structural checks (caller falls back to the
    per-signature kernel for verdicts).
    """
    return pack_rlc_named(pubkeys, msgs, sigs, parsed=parsed)[0]


def pack_rlc_named(pubkeys: list[bytes], msgs: list[bytes],
                   sigs: list[bytes], parsed=None):
    """pack_rlc's result and the packer that made it ("native" or
    "python"), for the host_pack spans' `packer` field."""
    import secrets

    from ..libs import metrics as libmetrics
    from . import rlcpack

    n = len(pubkeys)
    if n == 0:
        return None, "python"
    # the batch's whole draw, up front: 128 bits a signature from the
    # same source as ever, handed to whichever packer serves
    zblock = secrets.token_bytes(16 * n)
    packer = "native"
    packed = rlcpack.pack(pubkeys, msgs, sigs, zblock) \
        if parsed is None else rlcpack.UNAVAILABLE
    if packed is rlcpack.UNAVAILABLE:
        packer = "python"
        if parsed is None:
            parsed = parse_and_hash(pubkeys, msgs, sigs)
        packed = _pack_rlc_python(pubkeys, parsed, zblock)
    dm = libmetrics.device_metrics()
    if dm is not None and packed is not None:
        dm.host_pack_signatures.labels(packer).add(n)
    return packed, packer


def _pack_rlc_python(pubkeys: list[bytes], parsed, zblock: bytes):
    """pack_rlc in Python from parse_and_hash's rows: z_i is the i-th
    16 bytes of `zblock`, little-endian, top bit set."""
    global _NEG_B_ENC
    if _NEG_B_ENC is None:
        _NEG_B_ENC = _neg_b_encoding()

    n = len(pubkeys)
    agg: dict[bytes, int] = {}
    c = 0
    r_encs = []
    zs = []
    for i in range(n):
        if parsed[i] is None:
            return None
        r_enc, s, h = parsed[i]
        z = int.from_bytes(zblock[16 * i:16 * i + 16],
                           "little") | (1 << 127)
        pk = pubkeys[i]
        agg[pk] = (agg.get(pk, 0) + z * h) % L
        c = (c + z * s) % L
        r_encs.append(r_enc)
        zs.append(z)

    from ..ops import ed25519 as dev

    k = 1 + len(agg)
    kbatch = dev.pad_width(k)
    nbatch = dev.pad_width(n)
    a_words = np.zeros((kbatch, 8), dtype=np.uint32)
    r_words = np.zeros((nbatch, 8), dtype=np.uint32)

    filler = np.frombuffer(ref.point_compress(ref.B), dtype=np.uint32)
    a_words[:] = filler
    r_words[:] = filler
    a_words[0] = np.frombuffer(_NEG_B_ENC, dtype=np.uint32)
    a_scalars = [c] + list(agg.values())
    for j, pk in enumerate(agg.keys(), start=1):
        a_words[j] = np.frombuffer(pk, dtype=np.uint32)
    for i in range(n):
        r_words[i] = np.frombuffer(r_encs[i], dtype=np.uint32)
    a_mag, a_neg = _recode_w5(a_scalars, NDIG_256, kbatch)
    r_mag, r_neg = _recode_w5(zs, NDIG_128, nbatch)
    return (np.ascontiguousarray(a_words.T),
            np.ascontiguousarray(r_words.T),
            a_mag, a_neg, r_mag, r_neg)


# ---------------------------------------------------------------------------
# device-side hash-to-scalar packing (COMETBFT_TPU_DEVICE_HASH)
# ---------------------------------------------------------------------------
#
# The fused kernel (ops/ed25519.rlc_verify_hash_kernel) computes
# h = SHA512(R||A||M) mod L, zh = z*h, the per-pubkey aggregation AND
# the signed-window recode on device; the host's per-signature work
# shrinks to a structural parse plus one columnar message-pad.  No
# digest or scalar ever crosses back to the host.


def device_hash_enabled() -> bool:
    """Env knob for the fused device-hash verify path.  Read per call
    (cheap) so tests and operators can flip it without reloads."""
    return os.environ.get("COMETBFT_TPU_DEVICE_HASH", "0") == "1"


# Static SHA-512 block bucket for R||A||M messages.  Vote sign-bytes
# are ~110-130 bytes; +64 for R||A and +17 padding overhead needs 3
# blocks.  Messages that exceed the bucket raise ValueError from
# sha2._pad, which the dispatch layer turns into a host-hash fallback
# (flightrec EV_DEVICE_HASH_FALLBACK + DeviceMetrics counter).
DEVICE_HASH_MAX_BLOCKS = int(os.environ.get(
    "COMETBFT_TPU_DEVICE_HASH_BLOCKS", "3"))


def parse_batch(pubkeys: list[bytes],
                sigs: list[bytes]) -> list[tuple[bytes, int] | None]:
    """Structural parse ONLY (lengths, s < L) — the host side of the
    device-hash path, where parse_and_hash's hashlib loop never runs."""
    return [parse_signature(sig) if len(pk) == PUBKEY_SIZE else None
            for pk, sig in zip(pubkeys, sigs)]


def pack_rlc_device_hash(pubkeys: list[bytes], msgs: list[bytes],
                         sigs: list[bytes], parsed=None,
                         max_blocks: int | None = None):
    """Pack a batch for the fused hash-to-scalar RLC kernel.

    `parsed` is a parse_batch result ((r_enc, s) | None per entry — no
    h).  Host work per signature: a 128-bit z draw (one vectorized
    block), c += z*s mod L, and the R||A||M byte splice; hashing,
    per-pubkey zh aggregation and the A-side recode all move on-device.

    Returns the kernel's positional argument tuple
    (a_words (8,K), r_words (8,N), base_limbs (K,16), z_limbs (N,8),
    group_ids (N,), blocks_hi/lo (N,B,16), n_blocks (N,),
    r_mag/r_neg (26,N)), or None if any entry fails structural checks.
    Raises ValueError("message exceeds max_blocks") when a message
    outgrows the static block bucket — the caller's fallback trigger.
    """
    import secrets

    from ..ops import ed25519 as dev
    from ..ops import limbs as lb
    from ..ops import sha2

    global _NEG_B_ENC
    if _NEG_B_ENC is None:
        _NEG_B_ENC = _neg_b_encoding()

    n = len(pubkeys)
    if n == 0:
        return None
    if parsed is None:
        parsed = parse_batch(pubkeys, sigs)
    if max_blocks is None:
        max_blocks = DEVICE_HASH_MAX_BLOCKS

    zraw = np.frombuffer(secrets.token_bytes(16 * n),
                         dtype=np.uint8).reshape(n, 16).copy()
    zraw[:, 15] |= 0x80                    # pin the top bit, like pack_rlc

    if any(p is None for p in parsed):
        return None

    # callers that parsed ahead of time (crypto/batch._device_verify_hash,
    # crypto/mesh.split_rlc_verify_hash) pass placeholder sigs; the
    # 64-byte rows rebuild from parsed's (r_enc, s)
    if len(sigs[0]) != 64:
        sigs = [r_enc + s.to_bytes(32, "little") for r_enc, s in parsed]

    # fully vectorized from here: parse_batch guaranteed every sig is
    # 64 bytes and every key 32, so the whole batch flattens into two
    # matrices and the per-signature Python loop disappears.
    nbatch = dev.pad_width(n)
    filler = np.frombuffer(ref.point_compress(ref.B), dtype=np.uint32)
    sig_mat = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
    pk_mat = np.frombuffer(b"".join(pubkeys), dtype=np.uint8).reshape(n, 32)
    r_words = np.empty((nbatch, 8), dtype=np.uint32)
    r_words[:] = filler
    r_words[:n] = np.ascontiguousarray(sig_mat[:, :32]).view(np.uint32)

    # group ids without the per-signature dict walk: unique keys via a
    # byte-comparing sort, remapped to FIRST-APPEARANCE order so slot
    # assignment matches the host-hash packer exactly
    pk_void = pk_mat.view(np.dtype((np.void, 32))).ravel()
    _, first_idx, inv = np.unique(pk_void, return_index=True,
                                  return_inverse=True)
    n_keys = len(first_idx)
    remap = np.empty(n_keys, dtype=np.int32)
    remap[np.argsort(first_idx)] = np.arange(n_keys, dtype=np.int32)
    group_ids = np.zeros(nbatch, dtype=np.int32)
    group_ids[:n] = remap[inv] + 1

    # c = sum(z_i * s_i) mod L as one uint16-limb convolution: column
    # sums are bounded by 8n * 2^32, far under 2^64, so a single
    # big-int fold replaces n per-signature 384-bit modmuls
    s16 = np.ascontiguousarray(sig_mat[:, 32:]).view(np.uint16)
    z16 = zraw.view(np.uint16)
    cols = np.zeros(23, dtype=np.uint64)
    for j in range(8):
        cols[j:j + 16] += (z16[:, j:j + 1].astype(np.uint64)
                           * s16).sum(axis=0)
    c = sum(int(v) << (16 * k) for k, v in enumerate(cols)) % L

    # columnar R||A||M assembly straight into the padded block matrix:
    # the 64-byte prefix is two matrix copies, the message bytes one
    # reshape when lengths are uniform (the vote case), and
    # pad_sha512_matrix finishes 0x80/bit-length in place
    mlens = np.fromiter((len(m) for m in msgs), dtype=np.int64, count=n)
    lens = np.zeros(nbatch, dtype=np.int64)
    lens[:n] = 64 + mlens
    if int(lens.max()) + 1 + 16 > max_blocks * 128:
        raise ValueError("message exceeds max_blocks")
    mat = np.zeros((nbatch, max_blocks * 128), dtype=np.uint8)
    mat[:n, :32] = sig_mat[:, :32]
    mat[:n, 32:64] = pk_mat
    flat = np.frombuffer(b"".join(msgs), dtype=np.uint8)
    m0 = int(mlens[0])
    if np.all(mlens == m0):
        mat[:n, 64:64 + m0] = flat.reshape(n, m0)
    else:
        colix = np.arange(max_blocks * 128, dtype=np.int64)
        mask = (colix[None, :] >= 64) & (colix[None, :] < lens[:n, None])
        mat[:n][mask] = flat
    blocks_hi, blocks_lo, n_blocks = sha2.pad_sha512_matrix(mat, lens)
    n_blocks[n:] = 0                       # fillers: z = 0 keeps them inert

    kbatch = dev.pad_width(1 + n_keys)
    a_words = np.empty((kbatch, 8), dtype=np.uint32)
    a_words[:] = filler
    a_words[0] = np.frombuffer(_NEG_B_ENC, dtype=np.uint32)
    a_words[1:1 + n_keys] = np.ascontiguousarray(
        pk_mat[np.sort(first_idx)]).view(np.uint32)
    base_limbs = np.zeros((kbatch, 16), dtype=np.uint32)
    base_limbs[0] = lb.int_to_limbs(c, 16)

    z_limbs = np.zeros((nbatch, 8), dtype=np.uint32)
    z_limbs[:n] = zraw[:, 0::2].astype(np.uint32) | \
        (zraw[:, 1::2].astype(np.uint32) << 8)
    zbytes = np.zeros((n, _recode_nbytes(NDIG_128)), dtype=np.uint8)
    zbytes[:, :16] = zraw
    r_mag, r_neg = _recode_w5(zbytes, NDIG_128, nbatch)
    return (np.ascontiguousarray(a_words.T),
            np.ascontiguousarray(r_words.T),
            base_limbs, z_limbs, group_ids,
            blocks_hi, blocks_lo, n_blocks, r_mag, r_neg)


def pack_batch_device_hash(pubkeys: list[bytes], msgs: list[bytes],
                           sigs: list[bytes], batch_size: int,
                           parsed=None, max_blocks: int | None = None):
    """Per-signature packing with device-side hashing — the reject
    localization arm of the fused mode (digests stay on device even
    when a batch fails and individual verdicts are needed).

    Returns (a_words (8,B), r_words (8,B), s_limbs (16,B),
    blocks_hi/lo (B,Bk,16), n_blocks (B,), valid (B,)); raises
    ValueError on an oversized message like pack_rlc_device_hash.
    """
    from ..ops import limbs as lb
    from ..ops import sha2

    n = len(pubkeys)
    assert batch_size >= n
    if parsed is None:
        parsed = parse_batch(pubkeys, sigs)
    if max_blocks is None:
        max_blocks = DEVICE_HASH_MAX_BLOCKS
    valid = np.zeros(batch_size, dtype=bool)
    a_words = np.zeros((batch_size, 8), dtype=np.uint32)
    r_words = np.zeros((batch_size, 8), dtype=np.uint32)
    s_limbs = np.zeros((batch_size, 16), dtype=np.uint32)
    hash_msgs = []
    for i in range(n):
        if parsed[i] is None:
            hash_msgs.append(b"")
            continue
        r_enc, s = parsed[i]
        valid[i] = True
        a_words[i] = np.frombuffer(pubkeys[i], dtype=np.uint32)
        r_words[i] = np.frombuffer(r_enc, dtype=np.uint32)
        s_limbs[i] = lb.int_to_limbs(s, 16)
        hash_msgs.append(r_enc + pubkeys[i] + msgs[i])
    blocks_hi, blocks_lo, n_blocks = sha2.pad_sha512(
        hash_msgs + [b""] * (batch_size - n), max_blocks)
    n_blocks[~valid] = 0
    filler = np.frombuffer(ref.point_compress(ref.B), dtype=np.uint32)
    a_words[~valid] = filler
    r_words[~valid] = filler
    return (np.ascontiguousarray(a_words.T),
            np.ascontiguousarray(r_words.T),
            np.ascontiguousarray(s_limbs.T),
            blocks_hi, blocks_lo, n_blocks, valid)


def rlc_verify_hash_async(packed, device=None):
    """Fused-kernel dispatch without the host sync (see
    rlc_verify_async).  The A-table cache is not plumbed through this
    kernel yet: the fused program recodes its A scalars on device, and
    the cacheable part (decompression + table build) is a smaller
    fraction of its runtime than of the host-hash kernel's."""
    from ..ops import ed25519 as dev

    k, n = packed[0].shape[-1], packed[1].shape[-1]
    with trace_span("verify", "dispatch", k=k, n=n, cached=False,
                    kernel=dev.rlc_kernel_name(k, n)):
        if device is not None:
            import jax

            packed = tuple(jax.device_put(np.asarray(x), device)
                           for x in packed)
        return dev.rlc_verify_hash_device(*packed)


def _read_verdict(out) -> bool:
    """Block until the verdict bit of an rlc_verify*_async dispatch is
    on the host: the wait for the device, and little else."""
    with trace_span("verify", "readback"):
        return bool(np.asarray(out))


def rlc_verify_hash(packed, device=None) -> bool:
    return _read_verdict(rlc_verify_hash_async(packed, device=device))


# one cached A-table slot: 17 rows x 4 coords x 20 int32 limbs
BYTES_PER_A_SLOT = 17 * 4 * 20 * 4


class ATableCache:
    """Device cache of decompressed A-side window tables.

    A validator set's distinct pubkeys produce the same packed a_words
    every commit (pack_rlc's aggregation preserves first-seen order,
    which follows the address-sorted validator iteration), so the
    decompression + 17-row table build — the whole per-key cost of the
    A-side MSM — can live in HBM across dispatches.  The reference
    caches expanded pubkeys for the same access pattern
    (/root/reference/crypto/ed25519/ed25519.go:64-70); here the cached
    object is the device-resident table, so a 10k-header light-client
    sync pays the valset decompression once, not 10k times.

    Keyed by the raw a_words bytes; LRU-bounded primarily by a BYTE
    budget: one table is 17*4*20*4 = 5440 bytes per padded A slot, so
    a 10k-validator set pins ~56 MB of HBM — round 3's entry-count cap
    of 8 could silently hold ~0.45 GB.  The budget
    (COMETBFT_TPU_A_CACHE_BYTES, default 128 MiB) is accounted per
    admission and exported via DeviceMetrics; a generous entry cap
    remains as a secondary bound so a flood of tiny valsets cannot
    grow the dict without limit.  Thread-safe.
    """

    def __init__(self, capacity: int = 128, max_bytes: int | None = None):
        import collections

        self._cap = capacity
        self._max_bytes = (max_bytes if max_bytes is not None else
                           int(os.environ.get(
                               "COMETBFT_TPU_A_CACHE_BYTES",
                               str(128 << 20))))
        self._entries = collections.OrderedDict()   # key -> (entry, nbytes)
        self._bytes = 0
        self._seen: collections.OrderedDict = collections.OrderedDict()
        self._lock = lockrank.RankedLock("ed25519.atable")
        self.hits = 0
        self.misses = 0
        self.first_sightings = 0
        self.evictions = 0

    @property
    def bytes_resident(self) -> int:
        return self._bytes

    def clear(self) -> None:
        """Forget every table and every sighting, as a process that has
        just started: the next batch of any key is a first sighting
        again.  The hit, miss and sighting counts run on."""
        from ..libs import metrics as libmetrics

        with self._lock:
            self._entries.clear()
            self._seen.clear()
            self._bytes = 0
            self._gauge_bytes(libmetrics.device_metrics())

    @staticmethod
    def _entry_bytes(entry) -> int:
        a_tab, _ = entry
        return int(a_tab.size) * a_tab.dtype.itemsize

    def _gauge_bytes(self, dm) -> None:
        if dm is not None:
            dm.a_table_cache_bytes.set(self._bytes)

    def get(self, a_words: np.ndarray, device=None):
        """(8, K) packed encodings -> (device table, device ok-flag).

        `device` places the built table on a specific mesh device (and
        keys the entry by it): each chip in a round-robin dispatch
        keeps its own resident copy of a hot valset's tables, so a
        window dispatched to chip i never pulls a table across ICI."""
        from ..libs import metrics as libmetrics

        dm = libmetrics.device_metrics()
        key = (a_words.tobytes(), device)
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.hits += 1
                if dm is not None:
                    dm.a_table_cache_hits.inc()
                return self._entries[key][0]
        from ..ops import ed25519 as dev

        if device is not None:
            import jax

            a_words = jax.device_put(np.ascontiguousarray(a_words),
                                     device)
        entry = dev.build_a_tables_device(a_words)
        nbytes = self._entry_bytes(entry)
        with self._lock:
            self.misses += 1
            if dm is not None:
                dm.a_table_cache_misses.inc()
            if nbytes > self._max_bytes:
                # a table larger than the whole budget would evict
                # everything and then be evicted itself: serve it
                # un-admitted
                self._gauge_bytes(dm)
                return entry
            if key not in self._entries:
                # a concurrent miss may have admitted this key while we
                # built outside the lock: admitting again would count
                # nbytes twice against the budget forever
                self._entries[key] = (entry, nbytes)
                self._bytes += nbytes
                while (self._bytes > self._max_bytes
                       or len(self._entries) > self._cap):
                    _, (_, freed) = self._entries.popitem(last=False)
                    self._bytes -= freed
                    self.evictions += 1
            self._gauge_bytes(dm)
        return entry

    # Below this many A slots the cached kernel can't win: the saved
    # decompression/table work is proportional to K, while the split
    # into two dispatches (and, on cold caches, a fresh compile of the
    # cached-kernel shape) is constant.  Small-K batches — live
    # consensus vote flushes — stay on the fused kernel.  Compared with
    # the PADDED K: on the chip pad_width returns no width under 128,
    # so every batch passes this there and it is the second-sighting
    # rule below that keeps one-shot flushes fused.
    MIN_K = int(os.environ.get("COMETBFT_TPU_A_CACHE_MIN_K", "64"))

    def get_if_worthwhile(self, a_words: np.ndarray, device=None):
        """Entry if cached; else None — and only SECOND sightings of a
        large-K key trigger a build.  One-shot batches (streaming vote
        flushes have nondeterministic signer subsets/order, so nearly
        every flush is a fresh key) must not thrash the LRU with ~MB
        device tables; a repeated large valset (light client windows,
        blocksync) shows up identically twice and earns its table."""
        import hashlib

        if a_words.shape[-1] < self.MIN_K:
            return None
        # a table the budget can never admit must stay on the fused
        # kernel: routing it through get() would rebuild the table on
        # EVERY sighting and still pay the split-dispatch overhead
        if a_words.shape[-1] * BYTES_PER_A_SLOT > self._max_bytes:
            return None
        key = (a_words.tobytes(), device)
        with self._lock:
            if key in self._entries:
                pass                       # hit: fall through to get()
            else:
                digest = (hashlib.sha256(key[0]).digest(), device)
                if digest not in self._seen:
                    self._seen[digest] = True
                    while len(self._seen) > 64:
                        self._seen.popitem(last=False)
                    self.first_sightings += 1
                    from ..libs import metrics as libmetrics

                    dm = libmetrics.device_metrics()
                    if dm is not None:
                        dm.a_table_cache_first_sightings.inc()
                    return None            # first sighting: stay fused
        return self.get(a_words, device=device)


_A_TABLE_CACHE = ATableCache(
    capacity=int(os.environ.get("COMETBFT_TPU_A_CACHE_CAP", "8")))

USE_A_CACHE = os.environ.get("COMETBFT_TPU_A_CACHE", "1") == "1"


def rlc_verify_async(packed, use_cache: bool | None = None,
                     device=None):
    """rlc_verify without the host sync: returns the (device-resident)
    verdict bit array so a caller splitting one window across a mesh
    (crypto/mesh.split_rlc_verify) can dispatch every chip's RLC
    program before blocking on any of them.

    `device` commits the packed arrays (and the cached A-table, keyed
    per device) to that device before dispatch, which is how jit
    placement works: the program runs where its committed inputs live.
    None keeps the default-device behavior byte-identical."""
    from ..ops import ed25519 as dev

    a_words, r_words, a_mag, a_neg, r_mag, r_neg = packed
    a_np = np.asarray(a_words)
    # the host's share of one dispatch, up to the program's
    # (asynchronous) return; k and n are the padded widths, kernel
    # says whether the program at those widths is the Pallas kernels
    # or has a stage on the XLA path
    k, n = a_np.shape[-1], r_words.shape[-1]
    with trace_span("verify", "dispatch", k=k, n=n,
                    kernel=dev.rlc_kernel_name(k, n)) as sp:
        entry = None
        if use_cache is True:
            entry = _A_TABLE_CACHE.get(a_np, device=device)
        elif use_cache is None and USE_A_CACHE:
            entry = _A_TABLE_CACHE.get_if_worthwhile(a_np, device=device)
        sp.note(cached=entry is not None)
        if device is not None:
            import jax

            r_words, a_mag, a_neg, r_mag, r_neg = (
                jax.device_put(np.asarray(x), device)
                for x in (r_words, a_mag, a_neg, r_mag, r_neg))
            if entry is None:
                a_words = jax.device_put(a_np, device)
        if entry is not None:
            a_tab, a_ok = entry
            return dev.rlc_verify_device_cached_a(
                a_tab, a_ok, r_words, a_mag, a_neg, r_mag, r_neg)
        return dev.rlc_verify_device(a_words, r_words,
                                     a_mag, a_neg, r_mag, r_neg)


def rlc_verify(packed, use_cache: bool | None = None,
               device=None) -> bool:
    """Dispatch a pack_rlc batch through the A-table cache when it
    pays.  use_cache=True forces the cached kernel (benchmarks /
    callers that KNOW the valset repeats), False forces the fused
    kernel, None (the default policy, COMETBFT_TPU_A_CACHE=0 disables)
    uses a cached table only for valsets seen before — one-shot
    batches keep the single fused dispatch.  Returns the verdict bit."""
    return _read_verdict(rlc_verify_async(
        packed, use_cache=use_cache, device=device))
