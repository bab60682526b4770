"""ctypes binding for the native commit codec (native/protowire/).

The repeated-CommitSig section dominates commit serialization (~33 ms
per 6668-sig commit in pure Python); the C encoder produces identical
bytes in well under a millisecond, leaving only the columnar gather
(~2-3 ms) on the Python side.  Commit.to_proto routes here when the
library is present and the commit is large enough to amortize the
gather; byte parity with the pure path is pinned by tests.

Mirrors the crypto/bls12381 native pattern: build() compiles with g++
on demand, load is lazy + self-tested, absence degrades silently to
the pure-Python encoder.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

from . import lockrank

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native", "protowire")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libcommitcodec.so")

# below this many signatures the columnar gather costs more than the
# pure encoder saves
MIN_SIGS = int(os.environ.get("COMETBFT_TPU_NATIVE_CODEC_MIN", "64"))

_lib = None
_failed = False          # sticky: one bad load/build attempt ends it
_lib_lock = lockrank.RankedLock("native_codec.lib")


def _stale() -> bool:
    """No .so, or a source newer than it (the check
    crypto/bls12381.build makes): a library left by an earlier build
    must not outlive a change to commit_codec.cc."""
    if not os.path.exists(_LIB_PATH):
        return True
    lib_mtime = os.path.getmtime(_LIB_PATH)
    return any(os.path.getmtime(os.path.join(_NATIVE_DIR, f)) > lib_mtime
               for f in os.listdir(_NATIVE_DIR)
               if f.endswith((".cc", ".h")))


def build() -> bool:
    """Compile the native library (g++, <1 s).  Returns True when the
    .so exists afterwards — same contract as crypto/bls12381.build()
    (tests skip on False instead of erroring on toolchain-less
    hosts)."""
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True)
    except (OSError, subprocess.CalledProcessError):
        pass
    return os.path.exists(_LIB_PATH)


def _load():
    global _lib, _failed
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _failed:
            return None
        if _stale() and not build():
            # no .so and no toolchain: don't retry per call — the
            # caller sits on the serialization hot path
            _failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            _failed = True
            return None
        fn = lib.pw_encode_commit_sigs
        fn.argtypes = [
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_int), ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_long,
        ]
        fn.restype = ctypes.c_long
        lib.pw_codec_selftest.restype = ctypes.c_int
        try:
            bad = lib.pw_codec_selftest() != 0
        except Exception:
            bad = True
        if bad:
            # stale/corrupt .so: cache the failure (a dlopen +
            # self-test per large commit would sit on the very hot
            # path this module exists to speed up) and fall back pure
            _failed = True
            raise RuntimeError("commit codec native self-test failed")
        _lib = lib
        return _lib


def enabled() -> bool:
    try:
        return _load() is not None
    except Exception:
        return False


def encode_commit_sigs(sigs) -> bytes | None:
    """The concatenated field-4-wrapped CommitSig messages for a
    signature list, or None when the native path doesn't apply."""
    if len(sigs) < MIN_SIGS:
        return None
    try:
        lib = _load()
    except Exception:
        return None
    if lib is None:
        return None
    n = len(sigs)
    flags = (ctypes.c_longlong * n)()
    ts_sec = (ctypes.c_longlong * n)()
    ts_nano = (ctypes.c_int * n)()
    addr_off = (ctypes.c_int * (n + 1))()
    sig_off = (ctypes.c_int * (n + 1))()
    addrs = []
    sblobs = []
    a_pos = s_pos = 0
    for i, s in enumerate(sigs):
        # negative decoded flags pass through as-is: the C side casts
        # to unsigned 64-bit, which IS Writer.int_field's (v & _U64)
        # 10-byte two's-complement encoding
        flags[i] = s.block_id_flag
        t = s.timestamp
        ts_sec[i] = t.seconds
        ts_nano[i] = t.nanos
        a = s.validator_address
        addrs.append(a)
        a_pos += len(a)
        addr_off[i + 1] = a_pos
        sg = s.signature
        sblobs.append(sg)
        s_pos += len(sg)
        sig_off[i + 1] = s_pos
    addr_blob = b"".join(addrs)
    sig_blob = b"".join(sblobs)
    # worst case per sig: 1+5 wrap + flag 11 + addr 6+len + ts 2+24 +
    # sig 6+len — 64 fixed bytes of headroom is generous
    cap = 64 * n + a_pos + s_pos
    out = ctypes.create_string_buffer(cap)
    w = lib.pw_encode_commit_sigs(
        n, flags, addr_off, addr_blob, ts_sec, ts_nano, sig_off,
        sig_blob, ctypes.cast(out, ctypes.c_char_p), cap)
    if w < 0:
        return None
    return out.raw[:w]
