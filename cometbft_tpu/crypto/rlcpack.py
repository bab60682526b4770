"""ctypes binding for the native RLC packer (native/rlcpack/).

crypto/ed25519.pack_rlc does a SHA-512, a reduction mod L, two
big-integer products and a recode for every signature; in Python that
is 11-15 us a signature under the interpreter lock, on the staging
thread every product path waits for.  The library does the same work in
one call a batch, outside the lock (ctypes.CDLL), and writes arrays bit
for bit equal to the Python packer's for the same random bytes
(tests/test_rlcpack.py).

The contract is libs/native_codec.py's: build() compiles with g++ on
demand, the load is lazy, locked and self-tested once, a failure is
sticky, and absence degrades silently to the Python packer - visibly
only in `cometbft_device_host_pack_signatures_total{packer}`.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from ..libs import lockrank
from . import ed25519 as ed

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native", "rlcpack")
_LIB_PATH = os.path.join(_NATIVE_DIR, "librlcpack.so")

# pack()'s answer where the library cannot serve (None is taken: it is
# the packer's structural reject)
UNAVAILABLE = object()

_lib = None
_failed = False          # sticky: one bad load/build attempt ends it
_lib_lock = lockrank.RankedLock("rlcpack.lib")


def _stale() -> bool:
    """No .so, or a source newer than it: a library left by an earlier
    build must not outlive a change to rlcpack.cc."""
    if not os.path.exists(_LIB_PATH):
        return True
    lib_mtime = os.path.getmtime(_LIB_PATH)
    return any(os.path.getmtime(os.path.join(_NATIVE_DIR, f)) > lib_mtime
               for f in os.listdir(_NATIVE_DIR)
               if f.endswith((".cc", ".h")))


def build() -> bool:
    """Compile the native library (g++, ~2 s).  True when the .so
    exists afterwards - libs/native_codec.build()'s contract (tests
    skip on False instead of erroring on toolchain-less hosts)."""
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True)
    except (OSError, subprocess.CalledProcessError):
        pass
    return os.path.exists(_LIB_PATH)


def _load():
    global _lib, _failed
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _failed:
            return None
        if _stale() and not build():
            # no .so and no toolchain: don't retry per batch
            _failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            _failed = True
            return None
        ptr = ctypes.c_void_p
        lib.rlc_pack.argtypes = [
            ctypes.c_long, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ptr, ctypes.c_char_p,
            ctypes.c_long, ctypes.c_long,
            ptr, ptr, ptr, ptr, ptr, ptr,
        ]
        lib.rlc_pack.restype = ctypes.c_long
        lib.rlc_sha512.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                   ctypes.c_char_p]
        lib.rlc_sha512.restype = None
        lib.rlc_sc_reduce.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.rlc_sc_reduce.restype = None
        lib.rlc_selftest.restype = ctypes.c_int
        try:
            bad = lib.rlc_selftest() != 0
        except Exception:
            bad = True
        if bad:
            # stale/corrupt .so, or a host of another byte order: cache
            # the failure and let the Python packer serve
            _failed = True
            raise RuntimeError("rlcpack native self-test failed")
        _lib = lib
        return _lib


def enabled() -> bool:
    try:
        return _load() is not None
    except Exception:
        return False


def pack(pubkeys: list[bytes], msgs: list[bytes], sigs: list[bytes],
         zblock: bytes):
    """crypto/ed25519.pack_rlc's six arrays for a non-empty batch, None
    on a structural reject (a key not of 32 bytes, a signature not of
    64, an s not below L), UNAVAILABLE where there is no library.
    `zblock` holds 16 random bytes a signature."""
    try:
        lib = _load()
    except Exception:
        return UNAVAILABLE
    if lib is None:
        return UNAVAILABLE
    from ..ops import ed25519 as dev

    n = len(pubkeys)
    if len(msgs) != n or len(sigs) != n or len(zblock) != 16 * n:
        raise ValueError("rlcpack.pack: ragged batch")
    if set(map(len, pubkeys)) != {32} or set(map(len, sigs)) != {64}:
        return None
    kw = dev.pad_width(1 + len(set(pubkeys)))
    nw = dev.pad_width(n)
    mlens = np.fromiter(map(len, msgs), dtype=np.int64, count=n)
    a_words = np.empty((8, kw), dtype=np.uint32)
    r_words = np.empty((8, nw), dtype=np.uint32)
    a_mag = np.empty((ed.NDIG_256, kw), dtype=np.int32)
    a_neg = np.empty((ed.NDIG_256, kw), dtype=bool)
    r_mag = np.empty((ed.NDIG_128, nw), dtype=np.int32)
    r_neg = np.empty((ed.NDIG_128, nw), dtype=bool)
    rc = lib.rlc_pack(
        n, b"".join(pubkeys), b"".join(sigs), b"".join(msgs),
        mlens.ctypes.data, zblock, kw, nw,
        a_words.ctypes.data, r_words.ctypes.data, a_mag.ctypes.data,
        a_neg.ctypes.data, r_mag.ctypes.data, r_neg.ctypes.data)
    if rc == -1:
        return None
    if rc < 0:
        raise RuntimeError(f"rlcpack.pack: native packer returned {rc}")
    return a_words, r_words, a_mag, a_neg, r_mag, r_neg


def sha512(msg: bytes) -> bytes:
    """The library's own SHA-512 (tests hold it to known answers)."""
    lib = _load()
    out = ctypes.create_string_buffer(64)
    lib.rlc_sha512(msg, len(msg), out)
    return out.raw


def sc_reduce(wide: bytes) -> bytes:
    """64 little-endian bytes mod L, as 32 (tests hold the reduction to
    Python's `%` at its edges)."""
    if len(wide) != 64:
        raise ValueError("sc_reduce takes 64 bytes")
    lib = _load()
    out = ctypes.create_string_buffer(32)
    lib.rlc_sc_reduce(wide, out)
    return out.raw
