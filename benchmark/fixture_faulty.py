"""The peers a `faulty` cell's syncing nodes catch up from, some of which
forge, made from --seed.

Every peer is a real SimNode that serves through its real blocksync
reactor; its block store is a read-only view over the source's
(fixture.build_chain grows the chain once: a peer only serves), which
notes every request and can hand out a forged block in place of the
true one.  peers + 2 x windows nodes exist for the whole run: the first
`peers` are the ones a pass starts with, the rest are the fresh
connections that fill the slots of dropped ones.

THE FORGERY.  In the LastCommit of a block, the signature at one index
is replaced by the same validator's VALID signature of its precommit for
ANOTHER block id at the same height and round: R decodes and s < L, so
the RLC packer packs it and only the verification equation can tell.
The header is untouched.  The index is drawn from the seed inside the
prefix past two thirds of the power that verify_commit_light reads.

WHERE.  In a block h+1 whose lower neighbour h lies in the same verify
window, so that the pair (h, h+1) is judged in one window: the reactor
judges h on (h+1).LastCommit, as upstream does, and the forged
signature is in that window's batch.  (The altered LastCommit also
changes block h+1's own part-set hash, so the pair above, h+1 on
(h+2).LastCommit, fails on its block id; the reactor leaves that pair
out and judges the ones below it first - reactor._collect_pairs.)  A
window of one block has no such pair and gets no forgery.

WHO.  The pool draws who serves a height; a forger chooses among what
it is asked.  When the first request for a block of a window arrives,
the window's forger is drawn from the seed among the peers the pass
began with that are still connected, have not been drawn before and
have been asked for a block in this pass: six different peers in a pass
of six windows, each serving true blocks until its window, and never a
fresh connection.  The forger alters the first such block h+1 it is
asked for, once.  One time in 26 at ten peers the pool asks it for none
of the window's 31; whoever is asked for the window's last block then
forges in its place (the account says so: `designated` false), since a
window without its forgery would not be this cell's traffic.  Asked
again after the reject, whoever is asked serves the true block.
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
import threading
import time

from benchmark import fixture


@dataclasses.dataclass
class Forgery:
    """The fixture's own account of one forged signature handed out."""
    number: int                 # of the pass, from 1
    window: int                 # of the pass, from 1
    block_height: int           # the block whose LastCommit holds it
    commit_height: int          # the height that commit is for
    index: int                  # in the commit's signatures
    peer: str                   # who served it
    at: float                   # time.perf_counter() of the serve
    forged: bytes
    designated: bool            # by the seed's draw, or in its place


class PassAccount:
    """What the peers did in one pass."""

    def __init__(self, number: int):
        self.number = number
        self.forgeries: list = []
        self.forgers: dict = {}         # {window: the peer drawn for it}
        self.served: list = []          # (perf_counter, peer, height)
        self.dialled: list = []         # peer names, in order
        self.dropped: list = []         # (perf_counter, peer) stopped
        self.connected_at_end: list = []


class _View:
    """A peer's block store: the source's, through the fixture."""

    def __init__(self, peers: "Peers", name: str):
        self._peers, self._name = peers, name

    def height(self):
        # until a pass's first peers are all connected nobody has a
        # block to offer: the pool then spreads its first requests
        # over all of them, not over whoever connected first
        return self._peers.store.height() if self._peers.announced else 0

    def base(self):
        return self._peers.store.base()

    def load_block_bytes(self, height):
        return self._peers.serve(self._name, height)

    def load_extended_commit(self, height):
        return self._peers.store.load_extended_commit(height)


class Peers:
    def __init__(self, chain, config: dict, traffic: dict,
                 window_blocks: int):
        from cometbft_tpu.simnet import SimNode

        t0 = time.perf_counter()
        self.chain = chain
        self.store = chain.src.block_store
        self.n = int(config["peers"])
        self.per_window = int(traffic["forged_signatures_per_verify_window"])
        # {height: (window, its first height, its last)}, by the
        # reactor's own quantising: full windows, then the largest
        # power of two that is left (modes/catchup.py dispatches_short)
        self.window_of = {}
        self.windows = 0                # those that hold a pair
        top, number = 0, 0
        while top < chain.n_blocks:
            left = chain.n_blocks - top
            size = min(window_blocks, 1 << (left.bit_length() - 1))
            number += 1
            for h in range(top + 1, top + size + 1):
                self.window_of[h] = (number, top + 1, top + size)
            self.windows += size > 1
            top += size
        n_vals = int(config["validators"])
        self.signers = n_vals * 2 // 3 + 1
        if self.per_window > self.signers:
            raise ValueError("more forged signatures a window than "
                             "verify_commit_light reads")
        # the validators' keys, as fixture.build_chain derived them
        self._keys = {}
        for i in range(n_vals):
            s = fixture._Signer(fixture._seed_bytes(f"val-{i}", chain.seed))
            self._keys[s.pub.address()] = s._key
        self._lock = threading.Lock()
        self.announced = False
        self.account = PassAccount(0)
        self._node = None               # the pass's syncing node
        self.nodes = []
        for i in range(self.n + 2 * self.windows):
            node = SimNode(f"peer{i}", chain.genesis, chain.net, seed=i,
                           app=fixture.make_app(config))
            node.blocksync_reactor.store = _View(self, node.name)
            node.start()
            self.nodes.append(node)
        self.by_id = {n.node_key.id: n for n in self.nodes}
        self._next = 0
        self.build_s = time.perf_counter() - t0

    # -- a pass -----------------------------------------------------------
    def begin_pass(self, number: int) -> PassAccount:
        with self._lock:
            self.account = PassAccount(number)
            self.announced = False
            self._next = 0
        return self.account

    def connect(self, node) -> None:
        """The pass's first peers, then every one of them says what it
        holds."""
        from cometbft_tpu.blocksync import messages as bm
        from cometbft_tpu.blocksync.reactor import BLOCKSYNC_CHANNEL

        self._node = node
        for _ in range(self.n):
            self._dial_next(node)
        self.announced = True
        status = bm.wrap(bm.StatusResponse(height=self.store.height(),
                                           base=self.store.base()))
        for peer in self.nodes[:self.n]:
            peer.switch.try_broadcast(BLOCKSYNC_CHANNEL, status)

    def _dial_next(self, node) -> bool:
        with self._lock:
            if self._next >= len(self.nodes):
                return False
            peer = self.nodes[self._next]
            self._next += 1
            self.account.dialled.append(peer.name)
        node.dial(peer)
        return True

    def refill(self, node) -> int:
        """A fresh connection for every slot a drop left empty (the
        stand-in for PEX keeping the outbound count).  Returns how many
        were dialled."""
        n = 0
        while node.switch.peers.size() < self.n and self._dial_next(node):
            n += 1
        return n

    def _connected(self, node) -> list:
        return [self.by_id[p.id].name for p in node.switch.peers.list()
                if p.id in self.by_id]

    def watch_drops(self, node) -> None:
        """Note when the node stops a peer for an error: guarantee 3
        names who goes at a reject, and the end of a pass no longer
        shows when each went."""
        acct, stop = self.account, node.switch.stop_peer_for_error

        def noting(peer, reason):
            if peer.id in self.by_id:
                acct.dropped.append((time.perf_counter(),
                                     self.by_id[peer.id].name))
            return stop(peer, reason)

        node.switch.stop_peer_for_error = noting

    def end_pass(self, node) -> None:
        self.account.connected_at_end = self._connected(node)
        self._node = None

    def close(self) -> None:
        for node in self.nodes:
            node.stop()

    # -- serving -------------------------------------------------------------
    def serve(self, peer: str, height: int):
        true_bytes = self.store.load_block_bytes(height)
        now = time.perf_counter()
        window, first, last = self.window_of.get(height, (0, 0, 0))
        node = self._node
        connected = None
        if window and self.per_window and node is not None \
                and window not in self.account.forgers:
            connected = self._connected(node)
        with self._lock:
            acct = self.account
            acct.served.append((now, peer, height))
            if not (window and self.per_window and last > first
                    and true_bytes is not None):
                return true_bytes
            if window not in acct.forgers and connected is not None:
                acct.forgers[window] = self._draw_forger(
                    acct, window, connected)
            drawn = acct.forgers.get(window) == peer
            if height == first or any(f.window == window
                                      for f in acct.forgeries) \
                    or not (drawn or height == last):
                return true_bytes
            data, made = self._forge(acct, window, height, peer, now,
                                     drawn)
            acct.forgeries.extend(made)
        return data

    def _draw_forger(self, acct: PassAccount, window: int,
                     connected: list):
        """From the seed, among the peers the pass began with that are
        still there, were not drawn for an earlier window and have been
        asked for a block in this pass (a peer the pool has not turned
        to yet has nothing to alter: when a pass begins the pool asks
        whoever announced first for most of the first two windows).
        Where that leaves nobody, the conditions go one by one, the
        last first."""
        began = {n.name for n in self.nodes[:self.n]}
        asked = {p for _, p, _ in acct.served}
        free = sorted(set(connected) - set(acct.forgers.values()))
        for among in ([p for p in free if p in began and p in asked],
                      [p for p in free if p in began], free):
            if among:
                return random.Random(
                    f"{self.chain.seed}/{acct.number}/{window}/who"
                ).choice(among)
        return None

    def _forge(self, acct: PassAccount, window: int, height: int,
               peer: str, now: float, designated: bool):
        from cometbft_tpu.types import canonical
        from cometbft_tpu.types.block import (
            Block, BlockID, Commit, PartSetHeader)

        block = self.store.load_block(height)
        lc = block.last_commit
        rng = random.Random(f"{self.chain.seed}/{acct.number}/{window}")
        sigs = list(lc.signatures)
        made = []
        for index in sorted(rng.sample(range(self.signers),
                                       self.per_window)):
            cs = sigs[index]
            other = BlockID(hashlib.sha256(
                b"forged" + lc.block_id.hash).digest(),
                PartSetHeader(1, hashlib.sha256(b"parts").digest()))
            sb = canonical.vote_sign_bytes(
                self.chain.genesis.chain_id, canonical.PRECOMMIT,
                lc.height, lc.round, other, cs.timestamp)
            forged = self._keys[cs.validator_address].sign(sb)
            sigs[index] = dataclasses.replace(cs, signature=forged)
            made.append(Forgery(len(acct.forgeries) + len(made) + 1,
                                window, height, lc.height, index, peer,
                                now, forged, designated))
        # a new Commit: the stored one keeps its hash and its bytes
        commit = Commit(lc.height, lc.round, lc.block_id, sigs)
        return Block(block.header, block.data, block.evidence,
                     commit).to_proto(), made
