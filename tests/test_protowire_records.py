"""The records a node writes, hashes, signs and sends, through the shipped
`libs/protowire.Writer` and through the plain oracle of
`tests/test_protowire.py`: the same bytes, and bytes that decode back to
the record.  A real chain (12 validators, transactions, five blocks grown
through a node's own executor), so the fields are the ones a block has:
20-byte addresses, 32-byte keys, negative priorities, second-and-nanosecond
timestamps, records of more than 127 and of more than 16,383 bytes, an ABCI
oneof whose field is above 15."""

import dataclasses

import pytest

from cometbft_tpu.abci import types as at
from cometbft_tpu.libs import protowire as pw
from cometbft_tpu.state.state import State, tx_results_hash
from cometbft_tpu.types.block import Block
from cometbft_tpu.types.validator_set import ValidatorSet

from tests.test_protowire import OracleWriter, oracle_encode_uvarint

VALIDATORS = 12
BLOCKS = 5
TXS = 6


class CountingOracle(OracleWriter):
    """The oracle, counting its writers: a comparison in which nothing
    was written through it compared nothing."""

    made = 0

    def __init__(self):
        super().__init__()
        CountingOracle.made += 1


@pytest.fixture(scope="module")
def chain():
    from benchmark import fixture

    chain = fixture.build_chain(
        {"validators": VALIDATORS, "power": 10, "chain_blocks": BLOCKS - 1,
         "txs_per_block": TXS, "tx_bytes": 3000, "chain_id": "pw-records"},
        seed=2 ** 31 + 32)
    yield chain
    chain.src.stop()


@pytest.fixture
def oracle(monkeypatch):
    """Put the oracle in the shipped writer's place for what follows."""
    def engage():
        CountingOracle.made = 0
        monkeypatch.setattr(pw, "Writer", CountingOracle)
        monkeypatch.setattr(pw, "encode_uvarint", oracle_encode_uvarint)
    return engage


def same(a, b) -> bool:
    """Equal by value.  ValidatorSet has no equality of its own."""
    if isinstance(a, ValidatorSet):
        return (isinstance(b, ValidatorSet)
                and same(a.validators, b.validators)
                and same(a.get_proposer(), b.get_proposer()))
    if dataclasses.is_dataclass(a) and type(a) is type(b):
        return all(same(getattr(a, f.name), getattr(b, f.name))
                   for f in dataclasses.fields(a) if f.compare)
    if isinstance(a, (list, tuple)):
        return (isinstance(b, (list, tuple)) and len(a) == len(b)
                and all(same(x, y) for x, y in zip(a, b)))
    return a == b


def _finalize_request(chain, height: int) -> at.FinalizeBlockRequest:
    src = chain.src
    block = src.block_store.load_block(height)
    state = src.state_store.load()
    return at.FinalizeBlockRequest(
        hash=block.hash(),
        next_validators_hash=block.header.next_validators_hash,
        proposer_address=block.header.proposer_address,
        height=height, time=block.header.time,
        decided_last_commit=src.block_exec._build_last_commit_info(
            block, state),
        txs=list(block.data.txs), syncing_to_height=height)


# name -> (the record afresh from the chain, its encoder, its decoder).
# Afresh: Commit and Data keep what they hashed and signed, so each side
# of a comparison encodes objects nothing was asked of before.
def _state(chain, h=None):
    # the store keeps the tip's state alone
    return State.from_proto(chain.src.state_store.load().to_proto())


def _validators(chain, h):
    return chain.src.state_store.load_validators(h)


def _response(chain, h):
    return at.FinalizeBlockResponse.from_proto(
        chain.src.state_store.load_finalize_block_response(h))


def _block(chain, h):
    return Block.from_proto(chain.src.block_store.load_block(h).to_proto())


RECORDS = {
    "state": (_state, lambda s: s.to_proto(), State.from_proto),
    "finalize_block_response": (
        _response, lambda r: r.to_proto(),
        at.FinalizeBlockResponse.from_proto),
    "validator_set": (
        _validators, lambda v: v.to_proto(), ValidatorSet.from_proto),
    "block": (_block, lambda b: b.to_proto(), Block.from_proto),
    "abci_request_finalize_block": (
        _finalize_request, at.wrap_request,
        lambda p: at.unwrap_request(p)[1]),
    "abci_response_finalize_block": (
        _response, at.wrap_response, lambda p: at.unwrap_response(p)[1]),
}


@pytest.mark.parametrize("name,height", [
    (name, height) for name in RECORDS
    for height in ([BLOCKS] if name == "state" else [2, BLOCKS])])
def test_a_record_is_the_oracles_bytes_and_decodes_back(
        chain, oracle, name, height):
    fresh, encode, decode = RECORDS[name]
    shipped = encode(fresh(chain, height))
    oracle()
    record = fresh(chain, height)
    assert encode(record) == shipped
    assert CountingOracle.made > 0
    back = decode(shipped)
    assert same(back, record)
    assert encode(back) == shipped


def test_the_records_have_the_sizes_and_fields_they_are_here_for(chain):
    validators = _validators(chain, BLOCKS)
    assert len(_block(chain, BLOCKS).to_proto()) > 16383
    assert 127 < len(validators.to_proto()) < 16384
    assert any(v.proposer_priority < 0 for v in validators.validators)
    # the two abci cases: two-byte tags
    req = at.wrap_request(_finalize_request(chain, 2))
    resp = at.wrap_response(_response(chain, 2))
    assert pw.Reader(req).read_tag() == (20, pw.BYTES)
    assert pw.Reader(resp).read_tag() == (21, pw.BYTES)
    assert req[:2] == b"\xa2\x01" and resp[:2] == b"\xaa\x01"


def _hashes(chain, height):
    block = _block(chain, height)
    return {
        "validators": _validators(chain, height).hash(),
        "next_validators": _validators(chain, height + 1).hash(),
        "block": block.hash(),
        "last_commit": block.last_commit.hash(),
        "data": block.data.hash(),
        "results": tx_results_hash(_response(chain, height).tx_results),
    }


@pytest.mark.parametrize("height", [2, BLOCKS])
def test_every_hash_is_the_oracles(chain, oracle, height):
    shipped = _hashes(chain, height)
    oracle()
    assert _hashes(chain, height) == shipped
    assert CountingOracle.made > 0
    src = chain.src
    header = src.block_store.load_block(height).header
    assert shipped["block"] == src.block_store.load_block_meta(
        height).block_id.hash
    assert shipped["last_commit"] == header.last_commit_hash
    assert shipped["data"] == header.data_hash
    assert shipped["validators"] == header.validators_hash
    assert shipped["next_validators"] == header.next_validators_hash


def _sign_bytes(chain, height):
    commit = _block(chain, height).last_commit
    return [commit.vote_sign_bytes(chain.genesis.chain_id, i)
            for i in range(len(commit.signatures))]


@pytest.mark.parametrize("height", range(2, BLOCKS + 1))
def test_every_precommits_sign_bytes_are_the_oracles(chain, oracle, height):
    from cometbft_tpu.crypto import ed25519

    shipped = _sign_bytes(chain, height)
    assert len(shipped) == VALIDATORS
    oracle()
    assert _sign_bytes(chain, height) == shipped
    assert CountingOracle.made > 0
    # and they are what the validators signed
    commit = chain.src.block_store.load_block(height).last_commit
    by_addr = {ed25519.PubKey(raw).address(): ed25519.PubKey(raw)
               for raw in chain.pubkeys}
    for sb, sig in zip(shipped, commit.signatures):
        assert by_addr[sig.validator_address].verify_signature(
            sb, sig.signature)
