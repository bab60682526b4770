"""Byzantine behavior through the live reactor stack + fuzzed links
(reference internal/consensus/byzantine_test.go
TestByzantinePrevoteEquivocation, p2p/fuzz.go).

The byzantine validator double-signs prevotes (bypassing its FilePV
with the raw key) and sends the conflicting vote to a single peer.
Honest nodes detect the conflict in their vote sets, convert it to
DuplicateVoteEvidence, gossip it, and a proposer commits it in a block.
"""

import os
import time

import pytest

from cometbft_tpu.consensus import messages as cmsgs
from cometbft_tpu.consensus.reactor import VOTE_CHANNEL
from cometbft_tpu.p2p.fuzz import FuzzConfig, FuzzedConnection
from cometbft_tpu.types.block import BlockID, PartSetHeader
from cometbft_tpu.types.evidence import DuplicateVoteEvidence
from cometbft_tpu.types.timestamp import Timestamp
from cometbft_tpu.types.vote import PREVOTE_TYPE, Vote

from tests.test_reactors import (
    P2PNode, connect_all, make_genesis)
from cometbft_tpu.crypto.ed25519 import PrivKey


def _make_byzantine(node: P2PNode, priv) -> None:
    """Swap the node's vote signing for an equivocating version: after
    the honest vote, sign a conflicting prevote with the RAW key (the
    FilePV would refuse) and send it to exactly one peer."""
    cs = node.cs
    orig = cs._sign_add_vote

    def byz_sign_add_vote(msg_type, hash_, header, block=None):
        orig(msg_type, hash_, header, block)
        if msg_type != PREVOTE_TYPE or not hash_:
            return
        addr = cs.priv_validator_pub_key.address()
        val_idx, _ = cs.validators.get_by_address(addr)
        conflicting = Vote(
            type=PREVOTE_TYPE, height=cs.height, round=cs.round,
            block_id=BlockID(os.urandom(32),
                             PartSetHeader(1, os.urandom(32))),
            timestamp=Timestamp.now(),
            validator_address=addr, validator_index=val_idx)
        conflicting.signature = priv.sign(
            conflicting.sign_bytes(cs.state.chain_id))
        # ALL peers, not one (reference byzantine_test.go splits its
        # conflicting votes across half the net): a single target can
        # be past this round on a loaded box and silently drop the
        # vote, which is exactly the scheduler-luck flake the old
        # fresh-testnet retry papered over — any ONE honest peer still
        # inside the round turns the pair into evidence
        msg = cmsgs.wrap_message(cmsgs.VoteMessage(conflicting))
        for peer in node.switch.peers.list():
            peer.try_send(VOTE_CHANNEL, msg)

    cs._sign_add_vote = byz_sign_add_vote

    # a byzantine node does not crash on its own equivocation echoing
    # back through gossip (honest nodes keep the "from ourselves" panic)
    orig_try_add = cs._try_add_vote

    def byz_try_add_vote(vote, peer_id):
        try:
            return orig_try_add(vote, peer_id)
        except Exception:
            return False

    cs._try_add_vote = byz_try_add_vote


def _find_duplicate_vote_evidence(nodes, byz_addr):
    """Scan committed blocks for duplicate-vote evidence from byz_addr."""
    for n in nodes:
        for h in range(1, n.block_store.height() + 1):
            block = n.block_store.load_block(h)
            if block is None:
                continue
            for ev_item in block.evidence:
                if isinstance(ev_item, DuplicateVoteEvidence) and \
                        ev_item.vote_a.validator_address == byz_addr:
                    return n, h, ev_item
    return None


class TestByzantineEquivocation:
    def test_equivocation_evidence_lands_in_block(self):
        # No retry: the conflicting vote now goes
        # to EVERY peer each prevote, so evidence forms whenever any
        # honest peer is still inside the round — per-height detection
        # is near-certain instead of scheduler luck against a single
        # possibly-lagging target.
        self._run_equivocation_net(0)

    def _run_equivocation_net(self, attempt: int):
        privs = [PrivKey.generate(bytes([i + 7]) * 32) for i in range(4)]
        genesis = make_genesis(privs)
        nodes = [P2PNode(p, genesis, f"byz-net-{attempt}-{i}")
                 for i, p in enumerate(privs)]
        _make_byzantine(nodes[0], privs[0])
        byz_addr = privs[0].pub_key().address()
        for n in nodes:
            n.start()
        connect_all(nodes)
        try:
            # Progress-adaptive wait: 90 s is plenty on a quiet box,
            # but under heavy CPU contention the net may still be
            # committing heights when a fixed deadline fires (observed
            # at heights [3,3,3,3] on a 3x-loaded host).  Keep waiting
            # while the chain demonstrably progresses, up to a hard
            # cap — asserting liveness, not speed.
            soft = time.monotonic() + 90
            hard = time.monotonic() + 360
            found = None
            last_h = 0
            last_progress = time.monotonic()
            while found is None:
                now = time.monotonic()
                h = max(n.block_store.height() for n in nodes)
                if h > last_h:
                    last_h, last_progress = h, now
                if now > hard or (now > soft
                                  and now - last_progress > 45):
                    break
                found = _find_duplicate_vote_evidence(nodes[1:], byz_addr)
                time.sleep(0.25)
            assert found is not None, (
                "no DuplicateVoteEvidence committed; heights: "
                + str([n.block_store.height() for n in nodes]))
            _, h, ev_item = found
            assert ev_item.vote_a.height == ev_item.vote_b.height
            assert ev_item.vote_a.block_id.hash != \
                ev_item.vote_b.block_id.hash
            # the honest majority keeps committing after the evidence
            # (liveness, not speed: one more height within a generous
            # window — the full suite runs this box at 100% CPU)
            target = max(n.block_store.height() for n in nodes[1:]) + 1
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                if any(n.block_store.height() >= target
                       for n in nodes[1:]):
                    break
                time.sleep(0.25)
            assert any(n.block_store.height() >= target
                       for n in nodes[1:]), "network stalled after evidence"
        finally:
            for n in nodes:
                n.stop()


def _fuzz_node_conns(node: P2PNode, config: FuzzConfig) -> None:
    """Wrap every future connection of the node's transport."""
    transport = node.switch.transport
    orig_dial = transport.dial
    orig_upgrade = transport.upgrade

    def dial(addr):
        conn, info = orig_dial(addr)
        return FuzzedConnection(conn, config), info

    def upgrade(raw, expected_id=""):
        conn, info = orig_upgrade(raw, expected_id)
        return FuzzedConnection(conn, config), info

    transport.dial = dial
    transport.upgrade = upgrade


class TestFuzzedConnections:
    def test_network_live_under_delay_fuzz(self):
        """Liveness with every link delay-fuzzed (reference fuzz mode
        'delay'): consensus still commits."""
        privs = [PrivKey.generate(bytes([i + 31]) * 32) for i in range(4)]
        genesis = make_genesis(privs)
        nodes = [P2PNode(p, genesis, f"fuzz-{i}")
                 for i, p in enumerate(privs)]
        for n in nodes:
            _fuzz_node_conns(n, FuzzConfig(
                mode=FuzzConfig.MODE_DELAY, max_delay=0.005, seed=42))
            n.start()
        connect_all(nodes)
        try:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if min(n.block_store.height() for n in nodes) >= 3:
                    break
                time.sleep(0.2)
            assert min(n.block_store.height() for n in nodes) >= 3
        finally:
            for n in nodes:
                n.stop()

    def test_drop_fuzz_degrades_gracefully(self):
        """One node's links drop 20% of writes: AEAD desync must surface
        as clean peer eviction (no hangs, no unhandled exceptions), and
        the honest 3/4 supermajority keeps committing."""
        privs = [PrivKey.generate(bytes([i + 63]) * 32) for i in range(4)]
        genesis = make_genesis(privs)
        nodes = [P2PNode(p, genesis, f"drop-{i}")
                 for i, p in enumerate(privs)]
        # fuzz starts after 2s so handshakes + first blocks succeed
        _fuzz_node_conns(nodes[3], FuzzConfig(
            mode=FuzzConfig.MODE_DROP, prob_drop=0.2, start_after=2.0,
            seed=7))
        for n in nodes:
            n.start()
        connect_all(nodes)
        try:
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                if min(n.block_store.height() for n in nodes[:3]) >= 6:
                    break
                time.sleep(0.2)
            assert min(n.block_store.height() for n in nodes[:3]) >= 6, (
                "honest nodes stalled under drop fuzz: "
                + str([n.block_store.height() for n in nodes]))
        finally:
            for n in nodes:
                n.stop()
