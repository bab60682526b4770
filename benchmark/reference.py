"""The plain reference of a sync cell, and the comparison behind `correct`.

It imports nothing of the program.  Its inputs are plain bytes and
numbers: the validators' public keys as the fixture derived them from
the seed, and what the syncing node STORED (block hashes, app hash, the
commit of every stored height with its signatures).  It

- orders the validator set itself (power descending, then address, the
  first 20 bytes of SHA-256 of the key),
- builds each precommit's canonical sign-bytes itself (CometBFT's
  CanonicalVote, proto3, length-delimited),
- verifies every signature with OpenSSL's Ed25519 (the `cryptography`
  wheel; the device kernels share no code with it),

and the comparison then holds the system to the configuration's
guarantees, as far as an honest chain can show them: what is stored is
the source's, every signature the system accepted the reference accepts
too, and every signature of a stored commit has a verdict of the
system's own (its verdict record is handed in as a function).

Every number compared is a count and its limit is 0.
"""

from __future__ import annotations

import hashlib
import struct

PRECOMMIT = 2
FLAG_COMMIT = 2


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _varint_field(num: int, v: int) -> bytes:
    return b"" if v == 0 else _uvarint(num << 3) + _uvarint(v)


def _sfixed64_field(num: int, v: int) -> bytes:
    return b"" if v == 0 else _uvarint(num << 3 | 1) + struct.pack("<q", v)


def _bytes_field(num: int, b: bytes, keep_empty: bool = False) -> bytes:
    if not b and not keep_empty:
        return b""
    return _uvarint(num << 3 | 2) + _uvarint(len(b)) + b


def vote_sign_bytes(chain_id: str, height: int, round_: int,
                    block_hash: bytes, parts_total: int,
                    parts_hash: bytes, seconds: int, nanos: int) -> bytes:
    """CanonicalVote{type=1, height=2 sfixed64, round=3 sfixed64,
    block_id=4, timestamp=5 (always present), chain_id=6}, with its
    varint length in front."""
    psh = _varint_field(1, parts_total) + _bytes_field(2, parts_hash)
    bid = _bytes_field(1, block_hash) + _bytes_field(2, psh, True)
    ts = _varint_field(1, seconds) + _varint_field(2, nanos)
    body = (_varint_field(1, PRECOMMIT) + _sfixed64_field(2, height)
            + _sfixed64_field(3, round_) + _bytes_field(4, bid)
            + _bytes_field(5, ts, True)
            + _bytes_field(6, chain_id.encode()))
    return _uvarint(len(body)) + body


def validator_order(pubkeys: list, powers: list) -> list:
    """Indexes of the validators in the set's order."""
    def addr(pk):
        return hashlib.sha256(pk).digest()[:20]

    return sorted(range(len(pubkeys)),
                  key=lambda i: (-powers[i], addr(pubkeys[i])))


def verify(pubkey: bytes, msg: bytes, sig: bytes) -> bool:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey)

    try:
        Ed25519PublicKey.from_public_bytes(pubkey).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def check_commits(chain_id: str, pubkeys: list, powers: list,
                  commits: list, system_verdict, full_below: int) -> dict:
    """commits: one dict a stored height - height, round, block_hash,
    parts_total, parts_hash, sigs [(flag, seconds, nanos, signature)] in
    the set's order.  system_verdict(pubkey, msg, sig) -> True, False or
    None is the system's own record.

    The guarantee: a commit below `full_below` was checked in full at
    apply (every signature has a verdict); the last stored height was
    checked by verify_commit_light alone (more than 2/3 of the power).
    """
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey)

    order = validator_order(pubkeys, powers)
    keys = [Ed25519PublicKey.from_public_bytes(pubkeys[i]) for i in order]
    total = sum(powers)
    out = {"sigs_checked": 0, "sigs_ref_rejected": 0,
           "sigs_not_verified": 0, "sigs_verdict_differs": 0,
           "commits_short": 0}
    for c in commits:
        if len(c["sigs"]) != len(order):
            out["commits_short"] += 1
            continue
        have = 0
        for slot, (flag, secs, nanos, sig) in enumerate(c["sigs"]):
            if flag != FLAG_COMMIT:
                continue
            i = order[slot]
            msg = vote_sign_bytes(chain_id, c["height"], c["round"],
                                  c["block_hash"], c["parts_total"],
                                  c["parts_hash"], secs, nanos)
            try:
                keys[slot].verify(sig, msg)
                ref = True
            except Exception:              # noqa: BLE001 - InvalidSignature
                ref = False
            got = system_verdict(pubkeys[i], msg, sig)
            out["sigs_checked"] += 1
            if not ref:
                out["sigs_ref_rejected"] += 1
            if got is None:
                if c["height"] < full_below:
                    out["sigs_not_verified"] += 1
            else:
                have += powers[i]
                if got != ref:
                    out["sigs_verdict_differs"] += 1
        if have * 3 <= total * 2:
            out["commits_short"] += 1
    return out


def check_stored(source: dict, stored: dict) -> dict:
    """source / stored: {"hashes": {height: block hash}, "app_hash":
    bytes, "height": int} of the source up to the pass's target and of
    what a pass's node stored."""
    want = source["hashes"]
    got = stored["hashes"]
    missing = sum(1 for h in want if h not in got)
    wrong = sum(1 for h in want if h in got and got[h] != want[h])
    return {"blocks_missing": missing, "blocks_hash_differs": wrong,
            "app_hash_differs": int(stored["app_hash"]
                                    != source["app_hash"])}
