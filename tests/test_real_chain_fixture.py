"""Fixture-pinned wire parity: decode a recorded CometBFT-format
/commit + /validators RPC response pair (tests/fixtures/
real_chain_commit.json, reference wire shapes per rpc/core/blocks.go
and rpc/core/consensus.go) and re-derive every recorded value from
first principles — header merkle hash, validator-set hash, and the
light-client commit verification over the canonical vote sign-bytes.

Any drift in light/rpc_decode, types/canonical, merkle hashing, or
commit verification breaks a FROZEN pin, not a value computed by the
same code under test.  The fixture generator
(scripts/gen_real_chain_fixture.py) documents the serializer
correspondence; it is never run by tests.
"""

import base64
import copy
import json
import os

import pytest

from cometbft_tpu.light import rpc_decode
from cometbft_tpu.types.validator_set import ValidatorSet

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "real_chain_commit.json")

# frozen literals, independent of the fixture file's own "pinned" block
HEADER_HASH = "43D14604A8621DBD99EC550B4E59B61F9DE9F86F3500F730764B79F6C750AEFB"
CHAIN_ID = "pin-chain-1"
HEIGHT = 12


@pytest.fixture(scope="module")
def fx():
    with open(FIXTURE) as f:
        return json.load(f)


def _signed_header(fx):
    return rpc_decode.signed_header_from_rpc(
        fx["commit_response"]["result"]["signed_header"])


def _valset(fx):
    vals = rpc_decode.validators_from_rpc(
        fx["validators_response"]["result"]["validators"])
    return ValidatorSet(vals)


def test_header_hash_matches_recorded(fx):
    sh = _signed_header(fx)
    assert sh.header.chain_id == CHAIN_ID
    assert sh.header.height == HEIGHT
    got = sh.header.hash().hex().upper()
    # the chain-recorded block ID must equal the recomputed hash —
    # the invariant every live chain satisfies
    wire_block_id = fx["commit_response"]["result"]["signed_header"][
        "commit"]["block_id"]["hash"]
    assert got == wire_block_id
    assert got == HEADER_HASH
    assert got == fx["pinned"]["header_hash"]


def test_validator_set_hash_matches_header(fx):
    sh = _signed_header(fx)
    vals = _valset(fx)
    assert vals.hash() == sh.header.validators_hash
    assert vals.hash().hex().upper() == fx["pinned"]["validators_hash"]
    # addresses recompute from the decoded pubkeys
    for v, item in zip(vals.validators,
                       fx["validators_response"]["result"]["validators"]):
        assert v.pub_key.address().hex().upper() == item["address"]


def test_commit_verifies_against_recorded_valset(fx):
    sh = _signed_header(fx)
    vals = _valset(fx)
    vals.verify_commit_light(CHAIN_ID, sh.commit.block_id, HEIGHT,
                             sh.commit)
    # full verification (every non-absent sig) also holds
    vals.verify_commit(CHAIN_ID, sh.commit.block_id, HEIGHT, sh.commit)


def test_tampered_signature_rejected(fx):
    bad = copy.deepcopy(fx)
    sig_json = bad["commit_response"]["result"]["signed_header"][
        "commit"]["signatures"][0]
    raw = bytearray(base64.b64decode(sig_json["signature"]))
    raw[17] ^= 0x20
    sig_json["signature"] = base64.b64encode(bytes(raw)).decode()
    sh = _signed_header(bad)
    vals = _valset(bad)
    with pytest.raises(Exception):
        vals.verify_commit_light(CHAIN_ID, sh.commit.block_id, HEIGHT,
                                 sh.commit)


def test_tampered_header_field_breaks_block_id(fx):
    bad = copy.deepcopy(fx)
    hdr = bad["commit_response"]["result"]["signed_header"]["header"]
    hdr["app_hash"] = "00" * 8
    sh = _signed_header(bad)
    wire_block_id = bad["commit_response"]["result"]["signed_header"][
        "commit"]["block_id"]["hash"]
    assert sh.header.hash().hex().upper() != wire_block_id
