"""StateStore.load_validators resumes a pointer record's catch-up from a
height it already answered; every answer must still be the one a fresh
decode of the full record and increment_proposer_priority(h - lhc) give
(`_afresh` below: the store's code before it kept anything), in every
field, for any order of requests, and follow the store through prunes,
a bootstrap and a rollback.
"""

from __future__ import annotations

import random
import sys
import threading
import types

import pytest

import cometbft_tpu.state.store as sstore
from cometbft_tpu.libs.metrics import Registry, StateMetrics
from cometbft_tpu.state import StateStore, make_genesis_state
from cometbft_tpu.state.rollback import rollback_state
from cometbft_tpu.store import MemDB
from cometbft_tpu.types.block import BlockID, PartSetHeader
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.validator_set import Validator, ValidatorSet

from helpers import gen_privkeys

TOP = 40                # validators records exist for heights 1..TOP + 2
CHANGE_AT = 18          # the set changes there: the second full record
HEIGHTS = list(range(1, TOP + 3))


def _chain(db, top: int = TOP, change_at: int = CHANGE_AT):
    """A store whose set is the genesis one up to `change_at` and another
    from there on, saved height by height as the executor saves it;
    powers differ, so that the priorities do."""
    privs = gen_privkeys(6)
    doc = GenesisDoc(
        chain_id="test-chain", genesis_time=Timestamp(1_700_000_000, 0),
        validators=[GenesisValidator(p.pub_key(), 3 + 4 * i)
                    for i, p in enumerate(privs[:5])])
    st = make_genesis_state(doc)
    ss = StateStore(db)
    ss.save(st)
    states = {0: st}
    for h in range(1, top + 1):
        st = st.copy()
        st.last_block_height = h
        st.last_validators = st.validators
        st.validators = st.next_validators
        nxt = st.next_validators.copy()
        if h + 2 == change_at:
            nxt.update_with_change_set(
                [Validator(privs[5].pub_key(), 11),
                 Validator(privs[0].pub_key(), 0)])
            st.last_height_validators_changed = change_at
        nxt.increment_proposer_priority(1)
        st.next_validators = nxt
        ss.save(st)
        states[h] = st
    return ss, states


def _afresh(db, height: int) -> ValidatorSet:
    """load_validators as it was before it kept anything."""
    raw = db.get(sstore._k_vals(height))
    if raw is None:
        raise KeyError(height)
    lhc, payload = sstore._info_parse(raw)
    if payload is None:
        _, payload = sstore._info_parse(db.get(sstore._k_vals(lhc)))
        vals = ValidatorSet.from_proto(payload)
        vals.increment_proposer_priority(height - lhc)
        return vals
    return ValidatorSet.from_proto(payload)


def _fields(vals: ValidatorSet):
    def one(v):
        return (v.address, v.pub_key.bytes(), v.voting_power,
                v.proposer_priority)
    return ([one(v) for v in vals.validators], one(vals.proposer),
            vals.total_voting_power(), vals.to_proto())


def _check(ss, db, height: int) -> ValidatorSet:
    got = ss.load_validators(height)
    assert _fields(got) == _fields(_afresh(db, height)), height
    # nothing the caller holds is the store's, the proposer included
    lhc, payload = sstore._info_parse(db.get(sstore._k_vals(height)))
    if payload is None:
        assert any(v is got.proposer for v in got.validators)
    return got


def _interleaved():
    a = list(range(2, 17))
    b = list(range(9, 17)) + list(range(19, 26))
    out = []
    for x, y in zip(a, b):
        out += [x, x, y, y]
    return out


ORDERS = {
    "ascending": HEIGHTS,
    "descending": HEIGHTS[::-1],
    "repeated": [7, 7, 7, 30, 30, 7, 1, 1, 18, 18, 30],
    "shuffled": random.Random(30).sample(HEIGHTS * 3, len(HEIGHTS) * 3),
    # light.Client.verify_light_block_at_height: the target, then the
    # heights from the trust root up, each height twice (two pages)
    "target_first": [TOP] * 2 + [h for h in range(2, TOP + 1)
                                 for _ in (0, 1)],
    "two_readers": _interleaved(),
    "across_full_records": [16, 17, 18, 19, 17, 20, 16, 18, 41, 2, 19],
    # more readers than a record keeps resume points
    "many_readers": [h + 3 * r for h in range(2, 8) for r in range(6)],
}


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_answers_are_the_fresh_catch_up(order):
    db = MemDB()
    ss, _ = _chain(db)
    for h in ORDERS[order]:
        _check(ss, db, h)
    assert len(ss._decoded) <= sstore._KEPT_RECORDS
    assert all(len(r.points) <= sstore._KEPT_RESUME_POINTS
               for r in ss._decoded.values())


def test_interval_checkpoint_records(monkeypatch):
    """A record that holds a full set for the interval's sake only
    (lhc below its height): served as stored, never a catch-up base."""
    monkeypatch.setattr(sstore, "VALSET_CHECKPOINT_INTERVAL", 8)
    db = MemDB()
    ss, _ = _chain(db)
    for h in [8, 9, 8, 16, 15, 17, 24, 25, 32, 31, 24, 8]:
        _check(ss, db, h)


@pytest.mark.parametrize("how", ["priorities", "proposer", "membership"])
def test_mutating_an_answer_changes_no_later_one(how):
    db = MemDB()
    ss, _ = _chain(db)
    for h in (1, 9, 9, 10, 18, 25):
        got = _check(ss, db, h)
        if how == "priorities":
            got.increment_proposer_priority(3)
            for v in got.validators:
                v.proposer_priority += 1_000
        elif how == "proposer":
            got.proposer.proposer_priority -= 77
            got.proposer.voting_power += 5
        else:
            got.update_with_change_set(
                [Validator(got.validators[1].pub_key, 0)])
        _check(ss, db, h)
        _check(ss, db, h + 1)


def test_prune_states_is_followed():
    db = MemDB()
    ss, _ = _chain(db)
    for h in (3, 12, 25, 30):
        _check(ss, db, h)
    assert ss.prune_states(22) > 0
    for h in HEIGHTS:
        if h < 22 and h != CHANGE_AT:
            with pytest.raises(KeyError):
                ss.load_validators(h)
        else:
            _check(ss, db, h)


def test_bootstrap_is_followed():
    """A bootstrap writes another set over a full record that pointer
    records above it still name: the answers are the new record's."""
    db = MemDB()
    ss, states = _chain(db)
    for h in (20, 21, 30):
        _check(ss, db, h)
    before = _fields(ss.load_validators(30))
    st = states[CHANGE_AT - 2].copy()       # its next_validators: record 18
    st.last_block_height = CHANGE_AT - 2
    st.next_validators = st.next_validators.copy()
    st.next_validators.update_with_change_set(
        [Validator(st.next_validators.validators[2].pub_key, 29)])
    ss.bootstrap(st)
    for h in (CHANGE_AT, 20, 21, 30, 19):
        _check(ss, db, h)
    assert _fields(ss.load_validators(30)) != before


def test_rollback_is_followed():
    db = MemDB()
    ss, states = _chain(db)
    for h in HEIGHTS:
        _check(ss, db, h)

    def meta(h):
        st = states[h]
        return types.SimpleNamespace(
            block_id=BlockID(bytes([h]) * 32, PartSetHeader(1, b"p" * 32)),
            header=types.SimpleNamespace(
                validators_hash=st.last_validators.hash()
                if h > 1 else st.validators.hash(),
                consensus_hash=b"c" * 32, time=Timestamp(1_700_000_000 + h),
                last_results_hash=b"r" * 32, app_hash=b"a" * 32))

    blocks = types.SimpleNamespace(height=lambda: TOP, load_block_meta=meta)
    assert rollback_state(ss, blocks)[0] == TOP - 1
    assert ss.load().last_block_height == TOP - 1
    for h in HEIGHTS[::-1] + HEIGHTS:
        _check(ss, db, h)


def test_eight_threads_read_right():
    db = MemDB()
    ss, _ = _chain(db)
    want = {h: _fields(_afresh(db, h)) for h in HEIGHTS}
    wrong, errors = [], []

    def reader(seed: int):
        try:
            rng = random.Random(seed)
            for h in rng.choices(HEIGHTS, k=150):
                if _fields(ss.load_validators(h)) != want[h]:
                    wrong.append((seed, h))
        except Exception as e:  # noqa: BLE001 - the test reports it
            errors.append(repr(e))

    threads = [threading.Thread(target=reader, args=(s,), daemon=True)
               for s in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # switch inside the rounds and copies
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong


def test_loads_are_counted_by_path():
    db = MemDB()
    ss, _ = _chain(db)
    ss.metrics = m = StateMetrics(Registry("t"))

    def read():
        return ({p: m.validators_loads._values.get((p,), 0.0)
                 for p in ("stored", "restarted", "resumed")},
                m.validators_catchup_rounds._values.get((), 0.0))

    for h in (TOP, TOP, 2, 2, 3, 3, 4, 1, 18, TOP):
        ss.load_validators(h)
    loads, rounds = read()
    # 40: restarted from 18 (22 rounds), then the same height again; 2:
    # restarted from 1 (1 round), again; 3, 3, 4: one round each height;
    # 1 and 18 are full records; 40 once more runs nothing
    assert loads == {"stored": 2.0, "restarted": 2.0, "resumed": 6.0}
    assert rounds == 22 + 1 + 2
