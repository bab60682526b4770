"""Blocksync's reject path, at 7 validators on the CPU: a fresh node
catches up from several simnet peers of which some serve a block whose
LastCommit holds a forged signature.

The peers are real SimNodes that serve through their real blocksync
reactors; only their block store is a view over the source's, which can
alter a block the first time it is asked for.  The device lane is a host
judge (OpenSSL), as in tests/test_chip_smoke.py: it answers a window as
the device path does - one verdict for the batch where every signature
verifies, a fallback and per-signature verdicts where one does not - and
keeps the device path's counters.  What is pinned: the forged signature
is rejected, the reject names the height whose commit holds it, exactly
the two suppliers of that pair are dropped, exactly the two blocks are
fetched again, nothing forged is stored, and the stored chain and the
app hash are the source's.
"""

import copy
import dataclasses
import threading
import time

import pytest

from cometbft_tpu.blocksync import messages as bm
from cometbft_tpu.blocksync import pool as bpool
from cometbft_tpu.blocksync import reactor as breactor
from cometbft_tpu.crypto import batch as cb
from cometbft_tpu.crypto import dispatch, sigcache
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.libs import flightrec
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import trace as libtrace
from cometbft_tpu.simnet import (SimNetwork, SimNode, grow_chain,
                                 make_sim_genesis)
from cometbft_tpu.types import canonical, validation
from cometbft_tpu.types.block import (Block, BlockID, Commit,
                                      PartSetHeader)

N_VALS = 7
SIGNERS = N_VALS * 2 // 3 + 1          # equal powers: 5 pass two thirds
BLOCKS = 14                            # heights a syncer can complete
WINDOW = 4
SEED = 2 ** 31 + 7


def _judge(triples):
    verdicts = [ed.PubKey(bytes(pk)).verify_signature(m, s)
                for pk, m, s in triples]
    return all(verdicts) and bool(verdicts), verdicts


def host_judge_window(self, win, device=None):
    """Stands in for VerifyPipeline._device_dispatch: the RLC equation
    accepts a batch whose signatures all verify; otherwise the fallback
    is counted and every signature gets its own verdict."""
    ok, verdicts = _judge((dispatch._pk_bytes(pk), m, s)
                          for pk, m, s in win.items)
    if ok:
        cb._count_verified("rlc", len(verdicts))
        return ok, verdicts
    dm = libmetrics.device_metrics()
    if dm is not None:
        dm.rlc_fallbacks.inc()
    flightrec.record(flightrec.EV_RLC_FALLBACK, batch=len(verdicts))
    cb._count_verified("persig", len(verdicts))
    return ok, verdicts


def host_judge_batch(self):
    return _judge(self._items)


@pytest.fixture
def stub_device(monkeypatch):
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD", 4)
    monkeypatch.setattr(cb, "DEVICE_THRESHOLD", 2)
    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        host_judge_window)
    monkeypatch.setattr(cb.TpuEd25519BatchVerifier, "_verify_items",
                        host_judge_batch)
    monkeypatch.setattr(breactor, "VERIFY_WINDOW", WINDOW)
    monkeypatch.setattr(bpool, "RETRY_JITTER", 0.005)


# -- the chain, the peers that serve it, the forgeries ---------------------------

@pytest.fixture(scope="module")
def chain():
    genesis, privs = make_sim_genesis(N_VALS, chain_id="forged-chain",
                                      seed=SEED & 0xFFFF)
    net = SimNetwork(seed=SEED & 0x7FFFFFFF)
    src = SimNode("src", genesis, net, seed=1)
    grow_chain(src, privs, BLOCKS + 1, txs_per_block=2)
    return {"genesis": genesis, "privs": privs, "net": net, "src": src,
            "by_addr": {p.pub_key().address(): p for p in privs}}


def forged_sig(chain, block, idx: int, kind: str = "other_block") -> bytes:
    """A signature to put at `idx` of `block`'s LastCommit.  other_block:
    the same validator's VALID signature of its precommit for another
    block id at the same height and round - well formed, so only the
    verification equation can tell; malformed: s >= L, which the RLC
    packer refuses before any equation."""
    commit = block.last_commit
    cs = commit.signatures[idx]
    if kind == "malformed":
        return cs.signature[:32] + b"\xff" * 32
    other = BlockID(b"\x5a" * 32, PartSetHeader(1, b"\xa5" * 32))
    sb = canonical.vote_sign_bytes(
        chain["genesis"].chain_id, canonical.PRECOMMIT, commit.height,
        commit.round, other, cs.timestamp)
    return chain["by_addr"][cs.validator_address].sign(sb)


def forge(chain, height: int, idx: int, kind: str = "other_block",
          fix_hash: bool = False):
    """(block bytes, forged signature): block `height` with signature
    `idx` of its LastCommit replaced.  The header is left as it was
    unless fix_hash, which recomputes LastCommitHash as a forger that
    wants to pass validate_basic would - and so changes the block's own
    hash."""
    block = chain["src"].block_store.load_block(height)
    lc = block.last_commit
    sigs = list(lc.signatures)
    forged = []
    for i in (idx if isinstance(idx, tuple) else (idx,)):
        forged.append(forged_sig(chain, block, i, kind))
        sigs[i] = dataclasses.replace(sigs[i], signature=forged[-1])
    # a new Commit: the old one keeps its hash and its bytes
    commit = Commit(lc.height, lc.round, lc.block_id, sigs)
    header = copy.copy(block.header)
    if fix_hash:
        header.last_commit_hash = commit.hash()
    return (Block(header, block.data, block.evidence, commit).to_proto(),
            tuple(forged) if isinstance(idx, tuple) else forged[0])


class Plan:
    """What the peers of one run do: {height: block bytes} handed out
    in place of the true block the FIRST time any peer is asked for that
    height (the pool chooses who serves what), and the log of every
    serve, in order."""

    def __init__(self, forged: dict):
        self.forged = dict(forged)
        self.served: list = []          # (peer name, height, forged?)
        self.open = False               # peers announce no block yet
        self._lock = threading.Lock()

    def serve(self, peer: str, height: int, true_bytes):
        with self._lock:
            bad = self.forged.pop(height, None)
            self.served.append((peer, height, bad is not None))
        return bad if bad is not None else true_bytes

    def first_supplier(self, height: int) -> str:
        return next(p for p, h, _ in self.served if h == height)

    def times_served(self, height: int) -> int:
        return sum(1 for _, h, _ in self.served if h == height)

    def asked_again_for_nothing(self, dropped: set) -> list:
        """Heights some peer was asked for although a peer that is still
        connected had been asked before: a request repeats only where
        whoever was asked first has been dropped (its answer, if it was
        still under way, is lost with the connection)."""
        out, asked = [], {}
        for peer, h, _ in self.served:
            if any(p not in dropped for p in asked.get(h, ())):
                out.append(h)
            asked.setdefault(h, []).append(peer)
        return out


class ServeView:
    """A peer's block store: the source's, read-only, through a Plan."""

    def __init__(self, store, peer: str, plan: Plan):
        self._store, self._peer, self._plan = store, peer, plan

    def height(self):
        # until every peer is connected nobody has a block to offer:
        # the pool then spreads its first requests over all of them
        return self._store.height() if self._plan.open else 0

    def base(self):
        return self._store.base()

    def load_block_bytes(self, height):
        return self._plan.serve(self._peer, height,
                                self._store.load_block_bytes(height))

    def load_extended_commit(self, height):
        return self._store.load_extended_commit(height)


class Run:
    """One catch-up: n peers serving through `plan`, one fresh node."""

    def __init__(self, chain, plan: Plan, n_peers: int = 4, tag: str = "a",
                 seed: int = 5):
        self.chain, self.plan = chain, plan
        net, genesis = chain["net"], chain["genesis"]
        self.peers = {}
        for i in range(n_peers):
            p = SimNode(f"peer{tag}{i}", genesis, net, seed=i)
            p.blocksync_reactor.store = ServeView(
                chain["src"].block_store, p.name, plan)
            self.peers[p.node_key.id] = p
        self.node = SimNode(f"sync{tag}", genesis, net, block_sync=True,
                            seed=seed)
        self.metrics = libmetrics.BlockSyncMetrics(libmetrics.Registry())
        self.node.blocksync_reactor.metrics = self.metrics
        self.dropped: list = []         # (peer name, reason)
        stop = self.node.switch.stop_peer_for_error

        def recording(peer, reason):
            self.dropped.append((self.peers[peer.id].name, str(reason)))
            return stop(peer, reason)

        self.node.switch.stop_peer_for_error = recording
        self.hold()

    def hold(self):
        """Show the reactor a window only once it is full (or reaches
        the tip), as the benchmark does: the windows are then the same
        heights whatever the timing."""
        pool = self.node.blocksync_reactor.pool
        peek, tip = pool.peek_window, self.chain["src"].height()

        def peek_full(max_blocks, offset=0):
            window, after = peek(max_blocks, offset)
            if window and window[-1][0].header.height != tip and not (
                    len(window) == max_blocks and after is not None):
                return [], None
            return window, after

        pool.peek_window = peek_full

    def sync(self, timeout: float = 30.0) -> bool:
        sigcache.reset()
        for p in self.peers.values():
            p.start()
        self.node.start()
        try:
            for p in self.peers.values():
                self.node.dial(p)
            self.plan.open = True
            for p in self.peers.values():
                store = p.blocksync_reactor.store
                p.switch.try_broadcast(
                    breactor.BLOCKSYNC_CHANNEL,
                    bm.wrap(bm.StatusResponse(height=store.height(),
                                              base=store.base())))
            return self.node.wait_for_height(BLOCKS, timeout=timeout)
        finally:
            self.node.stop()
            for p in self.peers.values():
                p.stop()
            for t in threading.enumerate():
                if t.name == "blocksync-pool":
                    t.join(timeout=10.0)

    def counter(self, name: str, *labels) -> float:
        m = getattr(self.metrics, name)
        return m._values.get(tuple(labels), 0.0)

    def assert_chain_is_the_sources(self):
        src, node = self.chain["src"], self.node
        for h in range(1, BLOCKS + 1):
            assert node.block_store.load_block_meta(h).block_id.hash == \
                src.block_store.load_block_meta(h).block_id.hash, h
        st = node.state_store.load()
        assert st.last_block_height >= BLOCKS
        assert st.app_hash == src.block_store.load_block(
            st.last_block_height + 1).header.app_hash

    def stored_signatures(self) -> set:
        out = set()
        for h in range(1, BLOCKS + 1):
            c = self.node.block_store.load_block_commit(h) \
                or self.node.block_store.load_seen_commit(h)
            out.update(s.signature for s in c.signatures)
            b = self.node.block_store.load_block(h)
            if b.last_commit is not None:
                out.update(s.signature for s in b.last_commit.signatures)
        return out


@pytest.fixture
def instruments():
    dm = libmetrics.DeviceMetrics(libmetrics.Registry())
    libmetrics.set_device_metrics(dm)
    rec = flightrec.FlightRecorder(capacity=1 << 12)
    flightrec.set_recorder(rec)
    tracer = libtrace.StageTracer()
    libtrace.set_tracer(tracer)
    yield {"dm": dm, "rec": rec, "tracer": tracer}
    libmetrics.set_device_metrics(None)
    flightrec.set_recorder(None)
    libtrace.set_tracer(None)


def _value(metric, *labels) -> float:
    return metric._values.get(tuple(str(x) for x in labels), 0.0)


def _assert_one_pair_rejected(run, pair_low: int, sig: bytes):
    """The outcome every single forgery must have."""
    plan = run.plan
    suppliers = {plan.first_supplier(pair_low),
                 plan.first_supplier(pair_low + 1)}
    assert {name for name, _ in run.dropped} == suppliers
    assert all("served invalid block" in why for _, why in run.dropped)
    assert run.counter("windows_rejected") == 1
    assert run.counter("peers_dropped", "served_invalid_block") == \
        len(suppliers)
    assert run.counter("blocks_refetched") == 2
    assert plan.asked_again_for_nothing(suppliers) == []
    assert plan.times_served(pair_low) >= 2
    assert plan.times_served(pair_low + 1) >= 2
    run.assert_chain_is_the_sources()
    assert sig not in run.stored_signatures()


# -- one forged signature ------------------------------------------------------------
#
# Windows are shown to the reactor full or not at all (Run.hold), so they
# are heights 1-4, 5-8, 9-12 and 13-14.  A window's signature checks are
# those of the commits in the blocks 2..5 (6..9, ...): block h is judged
# on the LastCommit of block h + 1, as upstream judges it, and the block
# ABOVE a window (5, 9, 13, and 15 above the last) lends its LastCommit
# and is not itself looked at until the next window.  A forged signature
# in block h + 1 is therefore in pair h's checks wherever h + 1 lies.
# Inside a window the altered bytes also change block h + 1's own
# part-set hash, to which block h + 2 does not commit: that pair fails on
# its block id, is left out of the batch, and the pairs below it are
# judged first - the signature is what the device rejects, and h is
# what the reject names.

@pytest.mark.parametrize("height,idx", [(5, 0), (9, SIGNERS - 1), (13, 2),
                                        (15, 1)])
def test_forgery_above_a_window_is_rejected_by_the_device_and_named(
        stub_device, instruments, chain, height, idx):
    blk, sig = forge(chain, height, idx)
    run = Run(chain, Plan({height: blk}), tag=f"p{height}x{idx}")
    assert run.sync()
    # the commit in block `height` is height - 1's: that is the name
    _assert_one_pair_rejected(run, height - 1, sig)
    dm, tracer = instruments["dm"], instruments["tracer"]
    assert _value(dm.rlc_fallbacks) == 1
    # the per-signature verdicts of the rejected window, true and
    # false, are what the verdict cache holds afterwards
    true_block = chain["src"].block_store.load_block(height)
    cs = true_block.last_commit.signatures[idx]
    pk = chain["by_addr"][cs.validator_address].pub_key()
    sb = true_block.last_commit.vote_sign_bytes_all(
        chain["genesis"].chain_id)[idx]
    assert sigcache.get(pk, sb, sig) is False
    assert sigcache.get(pk, sb, cs.signature) is True
    # the spans of the episode, with what they name
    rejects = tracer.intervals("blocksync", "reject")
    refetches = tracer.intervals("blocksync", "refetch")
    assert len(rejects) == len(refetches) == 1
    assert set(libtrace.REJECT_STAGES) <= {
        iv["stage"] for iv in tracer.intervals("blocksync")}
    assert rejects[0]["height"] == height - 1
    assert rejects[0]["peers_dropped"] == len({n for n, _ in run.dropped})
    assert refetches[0]["blocks"] == 2
    assert rejects[0]["start"] <= refetches[0]["start"]
    assert refetches[0]["end"] <= rejects[0]["end"]
    # no window and no remainder was left to the host loop
    flushes = [e for e in instruments["rec"].events()
               if e["kind"] == flightrec.EV_VERIFY_FLUSH]
    assert flushes and {e["path"] for e in flushes} <= {"device", "cache"}


@pytest.mark.parametrize("height,first,misses", [(5, WINDOW, 1),
                                                 (3, WINDOW - 1, 6)])
def test_refetched_window_goes_to_the_device_whole(
        stub_device, instruments, chain, monkeypatch, height, first,
        misses):
    # the rejected window comes back with the same blocks and, of its
    # signatures, the verdict cache has not seen ONE (the forged block
    # lay above the window) or that one and the commit of the pair that
    # had been left out (it lay inside): too few for a device batch of
    # their own, so the pipeline keeps the window together
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD",
                        misses + 1)
    widths = []

    def recording(self, win, device=None):
        widths.append(len(win.items))
        return host_judge_window(self, win, device)

    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        recording)
    blk, sig = forge(chain, height, 1)
    run = Run(chain, Plan({height: blk}), tag=f"whole{height}")
    assert run.sync()
    _assert_one_pair_rejected(run, height - 1, sig)
    full = SIGNERS * WINDOW
    # 1-4 twice (rejected, then whole again), 5-8, 9-12, then 13-14;
    # never a window of the new signatures alone
    assert widths == [SIGNERS * first, full, full, full, SIGNERS * 2]
    dm = instruments["dm"]
    assert _value(dm.signatures_verified, "persig") == SIGNERS * first
    assert _value(dm.signatures_verified, "rlc") == \
        sum(widths) - SIGNERS * first
    # no window and no remainder was left to the host loop
    flushes = [e for e in instruments["rec"].events()
               if e["kind"] == flightrec.EV_VERIFY_FLUSH]
    assert flushes and {e["path"] for e in flushes} <= {"device", "cache"}


def test_misses_enough_for_a_batch_of_their_own_are_split_off(
        stub_device, instruments, chain, monkeypatch):
    # the same refetched window where six new signatures ARE a device
    # batch (threshold 4): the cached ones stay at home
    widths = []

    def recording(self, win, device=None):
        widths.append(len(win.items))
        return host_judge_window(self, win, device)

    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        recording)
    blk, sig = forge(chain, 3, 1)
    run = Run(chain, Plan({3: blk}), tag="split")
    assert run.sync()
    _assert_one_pair_rejected(run, 2, sig)
    full = SIGNERS * WINDOW
    assert widths == [SIGNERS * (WINDOW - 1), SIGNERS + 1, full, full,
                      SIGNERS * 2]


@pytest.mark.parametrize("height,idx,depth", [
    (6, 0, 2), (3, 2, 2), (12, SIGNERS - 1, 2), (2, 1, 2), (7, 3, 1)])
def test_forgery_inside_a_window_is_judged_in_the_pair_below_it(
        stub_device, instruments, chain, monkeypatch, height, idx, depth):
    # the altered block no longer has the part-set hash the block above
    # commits to, so its own pair is left out of the window's batch; the
    # pair below it holds the forged signature, the device rejects it,
    # and the height named is the commit's, as upstream names it.  The
    # serial loop (depth 1) judges a window the same way
    monkeypatch.setattr(breactor, "PIPELINE_DEPTH", depth)
    blk, sig = forge(chain, height, idx)
    run = Run(chain, Plan({height: blk}), tag=f"i{height}")
    assert run.sync()
    _assert_one_pair_rejected(run, height - 1, sig)
    rejects = instruments["tracer"].intervals("blocksync", "reject")
    assert [r["height"] for r in rejects] == [height - 1]
    if depth > 1:
        dm = instruments["dm"]
        assert _value(dm.rlc_fallbacks) == 1
        # the batch the reject judged: the window less the pair left out
        assert _value(dm.signatures_verified, "persig") == \
            SIGNERS * (WINDOW - 1)


def test_a_block_that_is_not_the_one_committed_to_is_blamed_in_its_turn(
        stub_device, instruments, chain):
    # block 7's data is another block's: no signature is forged, so the
    # pairs below it (5, 6) verify true and are applied; block 7 is then
    # first in line and is named itself, with the block above it
    true7 = chain["src"].block_store.load_block(7)
    other = chain["src"].block_store.load_block(8)
    blk = Block(true7.header, other.data, true7.evidence,
                true7.last_commit).to_proto()
    run = Run(chain, Plan({7: blk}), tag="data")
    assert run.sync()
    plan = run.plan
    suppliers = {plan.first_supplier(7), plan.first_supplier(8)}
    assert {name for name, _ in run.dropped} == suppliers
    assert run.counter("windows_rejected") == 1
    assert run.counter("blocks_refetched") == 2
    assert _value(instruments["dm"].rlc_fallbacks) == 0
    rejects = instruments["tracer"].intervals("blocksync", "reject")
    assert [r["height"] for r in rejects] == [7]
    run.assert_chain_is_the_sources()


@pytest.mark.parametrize("height", [9, 7], ids=["above", "inside"])
@pytest.mark.parametrize("fix_hash", [False, True],
                         ids=["header-untouched", "hash-recomputed"])
def test_forgery_in_the_last_third_is_caught_before_it_is_applied(
        stub_device, instruments, chain, fix_hash, height):
    # verify_commit_light reads the first 5 of 7 and never sees index 6,
    # so window 5-8 verifies true and its blocks are applied.  With the
    # header untouched validate_block refuses block 9 at apply
    # (validate_basic: LastCommitHash no longer matches; the full
    # verify_commit at state/validation.py:69 would refuse it next).  A
    # forger that recomputes LastCommitHash changes the block's hash,
    # and block 10 does not commit to that: the next window's
    # collection refuses it.  Either way block 9 is named itself.
    # Inside a window (7) nothing below the block fails, so 5 and 6
    # are applied and 7 is refused when it is first in line.
    blk, sig = forge(chain, height, N_VALS - 1, fix_hash=fix_hash)
    run = Run(chain, Plan({height: blk}),
              tag=f"l{int(fix_hash)}h{height}")
    assert run.sync()
    _assert_one_pair_rejected(run, height, sig)
    # no RLC batch ever held the signature
    assert _value(instruments["dm"].rlc_fallbacks) == 0
    assert len(instruments["tracer"].intervals("blocksync",
                                               "reject")) == 1


def test_malformed_signature_is_rejected_where_the_packer_refuses(
        stub_device, instruments, chain):
    # s >= L: pack_rlc returns None for the batch and the device lane
    # goes straight to per-signature verdicts
    blk, sig = forge(chain, 9, 3, kind="malformed")
    pks = [p.pub_key().bytes() for p in chain["privs"]]
    assert ed.pack_rlc(pks[:2], [b"m", b"m"],
                       [sig, chain["privs"][1].sign(b"m")]) is None
    assert ed.parse_signature(sig) is None
    run = Run(chain, Plan({9: blk}), tag="mal")
    assert run.sync()
    _assert_one_pair_rejected(run, 8, sig)


# -- more than one --------------------------------------------------------------------

def test_two_forged_signatures_in_one_commit_cost_one_localisation(
        stub_device, instruments, chain):
    blk, sigs = forge(chain, 9, (0, 4))
    run = Run(chain, Plan({9: blk}), tag="two")
    assert run.sync()
    _assert_one_pair_rejected(run, 8, sigs[0])
    assert sigs[1] not in run.stored_signatures()
    assert _value(instruments["dm"].rlc_fallbacks) == 1
    loc = instruments["tracer"].intervals("blocksync", "reject")
    assert len(loc) == 1


def test_two_forgeries_in_one_window_are_both_named_by_one_reject(
        stub_device, instruments, chain):
    # block 6 (inside 5-8) and block 9 (above it): pairs 5 and 8 each
    # hold a forged signature, pair 6 is left out; one batch, one
    # localisation, both heights named and both pairs fetched again
    b6, s6 = forge(chain, 6, 0)
    b9, s9 = forge(chain, 9, 4)
    run = Run(chain, Plan({6: b6, 9: b9}), n_peers=6, tag="both")
    assert run.sync()
    plan = run.plan
    suppliers = {plan.first_supplier(h) for h in (5, 6, 8, 9)}
    assert {n for n, _ in run.dropped} == suppliers
    run.assert_chain_is_the_sources()
    stored = run.stored_signatures()
    assert s6 not in stored and s9 not in stored
    assert run.counter("windows_rejected") == 1
    assert run.counter("blocks_refetched") == 4
    assert _value(instruments["dm"].rlc_fallbacks) == 1
    rejects = instruments["tracer"].intervals("blocksync", "reject")
    assert [r["height"] for r in rejects] == [5]
    assert plan.asked_again_for_nothing(
        {n for n, _ in run.dropped}) == []


def test_a_node_left_with_one_peer_still_catches_up(stub_device,
                                                    instruments, chain):
    # three peers, one forgery: the forger and whoever supplied the
    # block below go - possibly the only other peer that was serving -
    # and the node finishes from what is left
    blk, sig = forge(chain, 5, 1)
    run = Run(chain, Plan({5: blk}), n_peers=3, tag="one")
    assert run.sync()
    _assert_one_pair_rejected(run, 4, sig)
    assert 1 <= len({n for n, _ in run.dropped}) <= 2


def test_an_honest_run_rejects_nothing(stub_device, instruments, chain):
    run = Run(chain, Plan({}), tag="hon")
    assert run.sync()
    assert run.dropped == []
    for name in ("windows_rejected", "blocks_refetched"):
        assert run.counter(name) == 0
    assert _value(instruments["dm"].rlc_fallbacks) == 0
    assert _value(instruments["dm"].signatures_verified, "persig") == 0
    assert instruments["tracer"].intervals("blocksync", "reject") == []
    assert all(run.plan.times_served(h) == 1
               for h in range(1, BLOCKS + 2))
    run.assert_chain_is_the_sources()


# -- the pool's own generator ---------------------------------------------------------

def _draws(seed):
    sent = []
    pool = bpool.BlockPool(1, lambda h, pid: sent.append((h, pid)),
                           retry_jitter=0.05, seed=seed)
    for name in ("a", "b", "c", "d", "e"):
        pool.set_peer_range(name, 1, 40)
    jitters = []
    for h in range(1, 25):
        req = bpool._Requester(h)
        pool._requesters[h] = req
        assert pool._assign_and_send(req)
        t0 = time.monotonic()
        pool._redo_request(h, req.peer_id)
        jitters.append(round(req.not_before - t0, 3))
    return sent, jitters


def test_a_seeded_pool_draws_the_same_peers_and_jitter_twice():
    first, second, other = _draws(11), _draws(11), _draws(12)
    assert first[0] == second[0]
    assert first[1] == pytest.approx(second[1], abs=2e-3)
    assert all(0 <= j <= 0.051 for j in first[1])
    assert len(set(first[1])) > 10             # drawn, not constant
    assert other[0] != first[0]
    # the module's generator is not the pool's: reseeding it moves nothing
    import random

    random.seed(1)
    again = _draws(11)
    assert again[0] == first[0]


def test_blocks_present_says_when_a_refetch_is_over():
    pool = bpool.BlockPool(3, lambda h, pid: None, seed=1)
    pool.set_peer_range("a", 1, 40)
    for h in (3, 4, 5):
        pool._requesters[h] = bpool._Requester(h)
    assert not pool.blocks_present({3, 4})
    pool._requesters[3].block = object()
    assert not pool.blocks_present({3, 4})
    pool._requesters[4].block = object()
    assert pool.blocks_present({3, 4})
    assert pool.blocks_present({1, 2, 3})      # below the pool: applied
    assert not pool.blocks_present({5})
