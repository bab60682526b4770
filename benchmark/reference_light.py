"""The plain reference of a light cell, and the comparison behind `correct`.

It imports nothing of the program.  Its inputs are plain bytes and
numbers: the raw fields of each header a client STORED, the commit
stored with it, and the keys and powers in force at each height as the
fixture derived them from the seed (in no particular order: the
reference orders the set itself).  From these it

- computes each validator set's Merkle hash itself: RFC 6962 leaves and
  inner nodes (SHA-256, prefixes 0x00 / 0x01, the split at the largest
  power of two below the count) over CometBFT's SimpleValidator proto
  (pub_key = 1 {ed25519 = 1}, voting_power = 2), in the set's order
  (power descending, then address: reference.validator_order);
- computes each header's hash itself: the Merkle root of its 14 fields,
  each proto-encoded as CometBFT's Header.Hash does (version as
  Consensus{block = 1, app = 2}, time as Timestamp, last_block_id as
  BlockID with its part-set header always present, every other field in
  its wrapper message, an empty value as an empty leaf);
- checks what sequential verification promises: heights adjacent, times
  rising, the trusted header inside the trusting period, no header from
  beyond the clock's drift, each header's validators_hash equal to its
  predecessor's next_validators_hash and to the hash of the set that is
  in force, each commit signing this header's hash;
- builds each precommit's sign-bytes (reference.vote_sign_bytes) and
  verifies with OpenSSL's Ed25519, in the set's order, until more than
  2/3 of the power has signed - the signatures verify_commit_light
  counts.

Every number compared is a count and its limit is 0.
"""

from __future__ import annotations

import hashlib

from benchmark.reference import (
    FLAG_COMMIT, _bytes_field, _varint_field, validator_order,
    vote_sign_bytes)


# -- RFC 6962 ---------------------------------------------------------------------

def merkle_root(leaves: list) -> bytes:
    """SHA-256(0x00 || leaf) at the leaves, SHA-256(0x01 || left ||
    right) inside, split at the largest power of two below the count;
    the hash of nothing for no leaf."""
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    k = 1 << ((n - 1).bit_length() - 1)
    return hashlib.sha256(b"\x01" + merkle_root(leaves[:k])
                          + merkle_root(leaves[k:])).digest()


# -- the two hashes -----------------------------------------------------------------

def valset_hash(pubkeys: list, powers: list) -> bytes:
    leaves = []
    for i in validator_order(pubkeys, powers):
        pk = _bytes_field(1, pubkeys[i])             # PublicKey.ed25519
        leaves.append(_bytes_field(1, pk, True)
                      + _varint_field(2, powers[i]))
    return merkle_root(leaves)


def _wrapped(b: bytes) -> bytes:
    """A value in its wrapper message (BytesValue, StringValue: field
    1); an empty value is an empty leaf."""
    return _bytes_field(1, b)


def header_hash(h: dict) -> bytes:
    version = _varint_field(1, h["version_block"]) \
        + _varint_field(2, h["version_app"])
    time_ = _varint_field(1, h["time_seconds"]) \
        + _varint_field(2, h["time_nanos"])
    parts = _varint_field(1, h["last_parts_total"]) \
        + _bytes_field(2, h["last_parts_hash"])
    last_id = _bytes_field(1, h["last_block_hash"]) \
        + _bytes_field(2, parts, True)
    height = _varint_field(1, h["height"])           # Int64Value
    return merkle_root([
        version, _wrapped(h["chain_id"].encode()), height, time_, last_id,
        _wrapped(h["last_commit_hash"]), _wrapped(h["data_hash"]),
        _wrapped(h["validators_hash"]),
        _wrapped(h["next_validators_hash"]),
        _wrapped(h["consensus_hash"]), _wrapped(h["app_hash"]),
        _wrapped(h["last_results_hash"]), _wrapped(h["evidence_hash"]),
        _wrapped(h["proposer_address"])])


# -- one pass's stored headers against the source's ----------------------------------

def check_stored(source_hashes: dict, stored_hashes: dict) -> dict:
    """{height: header hash} of the source from the trust root's
    successor to the target, and of what a pass's client stored."""
    missing = sum(1 for h in source_hashes if h not in stored_hashes)
    wrong = sum(1 for h in source_hashes if h in stored_hashes
                and stored_hashes[h] != source_hashes[h])
    return {"headers_missing": missing, "header_hash_differs": wrong}


# -- what sequential verification promises, over one pass's stored chain -------------

def check_chain(chain_id: str, blocks: list, keys_at, power_at,
                trusting_period_ns: int, now_ns: int, drift_ns: int,
                system_verdict) -> dict:
    """blocks: one dict a stored height, ascending from the trust root:
    `header` (the plain fields header_hash reads), `commit` {height, round, block_hash,
    parts_total, parts_hash, sigs [(flag, seconds, nanos, signature)] in
    the set's order}.  keys_at(height) / power_at(height) give the raw
    keys and the powers in force.  system_verdict(pubkey, msg, sig) ->
    True, False or None is the system's own record.  The first block is
    the trust root: it is hashed and linked from, not verified."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey)

    out = {"headers_checked": 0, "sigs_checked": 0,
           "header_hash_wrong": 0, "set_hash_differs": 0,
           "link_broken": 0, "time_order_broken": 0,
           "sigs_ref_rejected": 0, "sigs_verdict_differs": 0,
           "sigs_not_verified": 0, "commits_short": 0}
    prev = None
    for b in blocks:
        h, c = b["header"], b["commit"]
        height = h["height"]
        hh = header_hash(h)
        if c["height"] != height or c["block_hash"] != hh \
                or h["chain_id"] != chain_id:
            out["header_hash_wrong"] += 1
        pubkeys = keys_at(height)
        powers = power_at(height)
        if valset_hash(pubkeys, powers) != h["validators_hash"]:
            out["set_hash_differs"] += 1
        if prev is None:
            prev = h
            continue
        out["headers_checked"] += 1
        if height != prev["height"] + 1 \
                or h["validators_hash"] != prev["next_validators_hash"]:
            out["link_broken"] += 1
        t = h["time_seconds"] * 10 ** 9 + h["time_nanos"]
        tp = prev["time_seconds"] * 10 ** 9 + prev["time_nanos"]
        if t <= tp or tp + trusting_period_ns <= now_ns \
                or t >= now_ns + drift_ns:
            out["time_order_broken"] += 1
        prev = h
        order = validator_order(pubkeys, powers)
        if len(c["sigs"]) != len(order):
            out["commits_short"] += 1
            continue
        total, have = sum(powers), 0
        for slot, (flag, secs, nanos, sig) in enumerate(c["sigs"]):
            if flag != FLAG_COMMIT:
                continue
            i = order[slot]
            msg = vote_sign_bytes(chain_id, c["height"], c["round"],
                                  c["block_hash"], c["parts_total"],
                                  c["parts_hash"], secs, nanos)
            try:
                Ed25519PublicKey.from_public_bytes(pubkeys[i]).verify(
                    sig, msg)
                ref = True
            except Exception:              # noqa: BLE001 - InvalidSignature
                ref = False
            got = system_verdict(pubkeys[i], msg, sig)
            out["sigs_checked"] += 1
            if not ref:
                out["sigs_ref_rejected"] += 1
            if got is None:
                out["sigs_not_verified"] += 1
            elif got != ref:
                out["sigs_verdict_differs"] += 1
            if ref and got:
                have += powers[i]
            if have * 3 > total * 2:
                break                       # verify_commit_light stops here
        if have * 3 <= total * 2:
            out["commits_short"] += 1
    return out
