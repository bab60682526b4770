"""SimNode: a full node's internals over the in-memory transport.

The assembly mirrors node/node.go in miniature — real BlockStore /
StateStore / mempool / evidence pool / BlockExecutor, real consensus +
mempool + evidence + blocksync REACTORS on a real p2p.Switch — with
only the transport swapped for simnet's conditioned in-memory links.
Everything between a peer's send queue and the block store (packet
framing, reactor dispatch, pool scheduling, DeferredSigBatch device
verification, ABCI execution) is the production code path.

grow_chain() extends a node's chain with REAL blocks: proposals built
by its own BlockExecutor (PrepareProposal consulted, mempool reaped),
commits signed by the genesis validators' real Ed25519 keys, every
block applied through apply_block so state/app/store agree — the
deterministic substitute for running multi-round consensus when a
bench or test needs a serving node with history.
"""

from __future__ import annotations

import hashlib
import time

from ..abci import types as at
from ..abci.client import LocalClient
from ..apps.kvstore import KVStoreApplication
from ..blocksync.reactor import BlocksyncReactor
from ..consensus.reactor import ConsensusReactor
from ..consensus.state import ConsensusState, test_consensus_config
from ..crypto import ed25519
from ..evidence import EvidencePool, EvidenceReactor
from ..mempool import CListMempool
from ..mempool.reactor import MempoolReactor
from ..node.node import NODE_CHANNELS
from ..p2p.key import NodeKey
from ..p2p.node_info import NodeInfo, ProtocolVersion
from ..p2p.switch import Switch
from ..privval import FilePV
from ..state.execution import BlockExecutor
from ..state.state import make_genesis_state
from ..state.store import StateStore
from ..store.blockstore import BlockStore
from ..store.kv import MemDB
from ..types import canonical
from ..types import events as ev
from ..types.block import (
    BLOCK_ID_FLAG_COMMIT, BlockID, ExtendedCommit, ExtendedCommitSig,
)
from ..types.genesis import GenesisDoc, GenesisValidator
from ..types.part_set import PartSet
from ..types.timestamp import Timestamp
from .transport import SimNetwork, SimTransport

GENESIS_TIME = Timestamp(1_700_000_000, 0)
PRECOMMIT_TYPE = 2


def _seed_bytes(tag: str, seed: int) -> bytes:
    return hashlib.sha256(f"simnet/{seed}/{tag}".encode()).digest()


def make_sim_genesis(n_vals: int = 4, chain_id: str = "simnet-chain",
                     power: int = 10, seed: int = 0,
                     key_module=ed25519):
    """Deterministic genesis + the validators' private keys.
    key_module picks the validator key type (crypto/ed25519 default;
    crypto/secp256k1 builds an ECDSA validator set — the simnet arm
    for the unified-MSM engine A/B)."""
    privs = [key_module.PrivKey.generate(_seed_bytes(f"val-{i}", seed))
             for i in range(n_vals)]
    genesis = GenesisDoc(
        chain_id=chain_id, genesis_time=GENESIS_TIME,
        validators=[GenesisValidator(pub_key=p.pub_key(), power=power)
                    for p in privs])
    return genesis, privs


class _LocalAppConns:
    """proxy.AppConns stand-in over one LocalClient: every connection
    is the same in-proc client (one mutex already serializes access),
    which is all the Handshaker needs (.query.info / .consensus)."""

    def __init__(self, client):
        self.consensus = client
        self.mempool = client
        self.query = client
        self.snapshot = client


class SimNode:
    """One in-process node on a SimNetwork.

    name        — unique within the network; doubles as the transport
                  host ('name:0' is the listen key).
    block_sync  — start the blocksync pool routine (a syncing node).
    consensus_active — run the consensus state machine (a live
                  validator); off by default so serving nodes with
                  pre-built chains don't churn rounds against stale
                  state.  Blocksync hands off to consensus on catch-up
                  only when active.
    dbs         — optional (state_db, block_db, evidence_db) MemDBs.
                  Passing the same triple to a SECOND construction is
                  the crash-restart path (cometbft_tpu/chaos): the
                  stores resume where they were and the production
                  Handshaker replays committed blocks into the fresh
                  app until app and store agree — the same recovery a
                  real node runs at startup (consensus/replay.py).
    wal         — optional consensus WAL (consensus/wal.WAL); the
                  chaos cluster gives validators one so crash-restart
                  can catchup_replay the in-flight height.
    priv_validator — an ed25519 PrivKey, or a prepared FilePV (the
                  restart path reuses the SAME FilePV so last-sign
                  state survives the crash, as the state file would).
    """

    def __init__(self, name: str, genesis: GenesisDoc,
                 network: SimNetwork, *, priv_validator=None,
                 block_sync: bool = False,
                 consensus_active: bool = False,
                 seed: int = 0, app=None, dbs=None, wal=None,
                 peer_timeout: float | None = None):
        self.name = name
        self.genesis = genesis
        self.network = network

        if dbs is None:
            dbs = (MemDB(), MemDB(), MemDB())
        self.dbs = dbs
        state_db, block_db, evidence_db = dbs
        self.state_store = StateStore(state_db)
        resumed = self.state_store.load()
        if resumed is None:
            state = make_genesis_state(genesis)
            self.state_store.bootstrap(state)
        else:
            state = resumed
        self.block_store = BlockStore(block_db)

        self.app = app if app is not None else KVStoreApplication()
        self.client = LocalClient(self.app)
        if resumed is None:
            self.client.init_chain(at.InitChainRequest(
                chain_id=genesis.chain_id,
                initial_height=state.initial_height))
        else:
            # crash-restart: the in-memory app came back empty while
            # the stores kept their history — run the REAL recovery
            # (ABCI handshake replays committed blocks until the app
            # hash agrees with the state store, replay.go semantics)
            from ..consensus.replay import Handshaker
            Handshaker(self.state_store, state, self.block_store,
                       genesis).handshake(_LocalAppConns(self.client))
            state = self.state_store.load() or state
        self.mempool = CListMempool(self.client)
        self.event_bus = ev.EventBus()
        self.evidence_pool = EvidencePool(evidence_db, self.state_store,
                                          self.block_store)
        self.block_exec = BlockExecutor(
            self.state_store, self.client, self.mempool,
            evidence_pool=self.evidence_pool,
            block_store=self.block_store, event_bus=self.event_bus)

        if priv_validator is None:
            pv = None
        elif isinstance(priv_validator, FilePV):
            pv = priv_validator      # restart: keep last-sign state
        else:
            pv = FilePV(priv_validator)
        self.priv_validator = pv
        self.wal = wal
        self.consensus_state = ConsensusState(
            test_consensus_config(), state, self.block_exec,
            self.block_store, wal=wal, priv_validator=pv,
            event_bus=self.event_bus, evidence_pool=self.evidence_pool,
            mempool=self.mempool)
        # per-node flight recorder (libs/flightrec.py): many nodes share
        # this process, so each consensus state records into its own
        # ring; benches/tests read recorder_summary() per node
        from ..libs.flightrec import FlightRecorder
        self.flight_recorder = FlightRecorder()
        self.consensus_state.recorder = self.flight_recorder
        # per-node event timeline (libs/tracetl.py), installed by
        # simnet/tracing.TraceSession; None = uninstrumented
        self.timeline = None
        # an inactive consensus reactor still gossips/receives (real
        # wiring) but never starts the state machine
        self.consensus_reactor = ConsensusReactor(
            self.consensus_state,
            wait_sync=block_sync or not consensus_active)
        self.blocksync_reactor = BlocksyncReactor(
            state, self.block_exec, self.block_store, block_sync,
            consensus_reactor=(self.consensus_reactor
                               if consensus_active else None),
            peer_timeout=peer_timeout, seed=seed)

        self.node_key = NodeKey(ed25519.PrivKey.generate(
            _seed_bytes(f"node-key-{name}", seed)))
        self.node_info = NodeInfo(
            protocol_version=ProtocolVersion(),
            node_id=self.node_key.id,
            listen_addr=f"{name}:0",
            network=genesis.chain_id,
            version="0.1.0-tpu",
            channels=NODE_CHANNELS,
            moniker=name)
        self.transport = SimTransport(network, self.node_key,
                                      self.node_info)
        self.switch = Switch(self.transport, listen_addr=f"{name}:0")
        self.switch.add_reactor("CONSENSUS", self.consensus_reactor)
        self.switch.add_reactor("MEMPOOL", MempoolReactor(self.mempool))
        self.switch.add_reactor("EVIDENCE",
                                EvidenceReactor(self.evidence_pool))
        self.switch.add_reactor("BLOCKSYNC", self.blocksync_reactor)

        self.rpc_server = None
        self.lightserve = None

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        self.event_bus.start()
        self.switch.start()

    def stop(self) -> None:
        if self.rpc_server is not None:
            self.rpc_server.stop()
            self.rpc_server = None
        if self.lightserve is not None:
            self.lightserve.close()
            self.lightserve = None
        self.switch.stop()
        self.event_bus.stop()

    def start_rpc(self) -> str:
        """Serve the real JSON-RPC stack over this node's stores on a
        loopback port; returns 'host:port'.  The light-client e2e bench
        points an HttpProvider here — the same wire a reference light
        client would use."""
        from ..rpc.core import Environment
        from ..rpc.server import RPCServer
        env = Environment(
            state_store=self.state_store,
            block_store=self.block_store,
            consensus_state=self.consensus_state,
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            p2p_switch=self.switch,
            event_bus=self.event_bus,
            genesis=self.genesis,
            app_conns=None,
            node_info=self.node_info,
            config=None)
        # serving plane wired eagerly (the lazy rpc/core.py seam would
        # also work) so fleet benches can reach node.lightserve
        # counters directly; RPCServer.stop() closes it
        from ..lightserve import LightServeSession
        self.lightserve = LightServeSession(
            self.block_store, self.state_store, self.genesis.chain_id)
        env.lightserve = self.lightserve
        self.rpc_server = RPCServer(env, "127.0.0.1:0",
                                    with_websocket=False)
        self.rpc_server.start()
        return self.rpc_server.bound_addr

    # -- convenience -------------------------------------------------------
    @property
    def addr(self) -> str:
        return f"{self.node_key.id}@{self.name}:0"

    def height(self) -> int:
        return self.block_store.height()

    def sync_target(self) -> int:
        """Highest height blocksync can COMPLETE from this node: the
        tip block's LastCommit verifies height-1, the tip itself waits
        for consensus catch-up (reference pool.IsCaughtUp semantics —
        a syncer converges one block behind the serving tip)."""
        return max(0, self.height() - 1)

    def app_hash(self) -> bytes:
        st = self.state_store.load()
        return st.app_hash if st is not None else b""

    def recorder_summary(self) -> dict:
        """Per-kind flight-recorder counts for this node (the shape
        bench.py reports per node next to its e2e rates)."""
        return self.flight_recorder.summary()

    def round_latencies(self) -> list[float]:
        """Seconds between consecutive new_height recorder events —
        the commit-to-commit round latency series for this node."""
        heights = [e["t"] for e in self.flight_recorder.events()
                   if e["kind"] == "new_height"]
        return [t1 - t0 for t0, t1 in zip(heights, heights[1:])]

    def dial(self, other: "SimNode", persistent: bool = False) -> None:
        self.switch.dial_peer(other.addr, persistent=persistent)

    def wait_for_height(self, height: int, timeout: float = 60.0) -> bool:
        """True once the block at `height` is stored AND applied.  The
        blocksync reactor saves a block before executing it, so the
        store height alone can run one block ahead of the state (and
        of app_hash())."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.block_store.height() >= height:
                st = self.state_store.load()
                if st is not None and st.last_block_height >= height:
                    return True
            time.sleep(0.005)
        return False


def _ext_commit_from(commit) -> ExtendedCommit:
    """Vote-extension-free ExtendedCommit over an existing commit's
    signatures (extensions are disabled in simnet genesis params)."""
    return ExtendedCommit(
        height=commit.height, round=commit.round,
        block_id=commit.block_id,
        extended_signatures=[
            ExtendedCommitSig(s.block_id_flag, s.validator_address,
                              s.timestamp, s.signature)
            for s in commit.signatures])


def grow_chain(node: SimNode, privs, n_blocks: int,
               txs_per_block: int = 1,
               time_step_ns: int = 1_000_000_000) -> list:
    """Extend node's chain by n_blocks through its own executor.

    Every commit signature is a real Ed25519 signature over the
    reference canonical vote sign-bytes; all signers share one
    timestamp per height so the next block's BFT-median time is
    deterministic.  Returns the new blocks."""
    state = node.state_store.load()
    by_addr = {p.pub_key().address(): p for p in privs}

    last_ext = ExtendedCommit()
    h0 = state.last_block_height
    if h0 >= state.initial_height:
        seen = node.block_store.load_seen_commit(h0)
        if seen is None:
            raise ValueError(f"no seen commit at height {h0}")
        last_ext = _ext_commit_from(seen)

    blocks = []
    for h in range(h0 + 1, h0 + n_blocks + 1):
        for t in range(txs_per_block):
            node.mempool.check_tx(f"sim{h}x{t}=v{h}".encode())
        proposer = state.validators.get_proposer().address
        block = node.block_exec.create_proposal_block(
            h, state, last_ext, proposer)
        parts = PartSet.from_data(block.to_proto())
        bid = BlockID(block.hash(), parts.header)

        ts = block.header.time.add_ns(time_step_ns)
        ext_sigs = []
        for v in state.validators.validators:
            sb = canonical.vote_sign_bytes(
                state.chain_id, PRECOMMIT_TYPE, h, 0, bid, ts)
            ext_sigs.append(ExtendedCommitSig(
                BLOCK_ID_FLAG_COMMIT, v.address, ts,
                by_addr[v.address].sign(sb)))
        last_ext = ExtendedCommit(height=h, round=0, block_id=bid,
                                  extended_signatures=ext_sigs)

        node.block_store.save_block(block, parts, last_ext.to_commit())
        state = node.block_exec.apply_block(state, bid, block)
        blocks.append(block)
    return blocks


def clone_chain(src: SimNode, dst: SimNode) -> None:
    """Seed a second serving node with src's chain: validate + apply
    every block through DST'S OWN executor and stores (the same path
    blocksync ingestion takes, minus the network)."""
    state = dst.state_store.load()
    for h in range(state.last_block_height + 1, src.height() + 1):
        block = src.block_store.load_block(h)
        commit = src.block_store.load_seen_commit(h)
        parts = PartSet.from_data(block.to_proto())
        bid = BlockID(block.hash(), parts.header)
        dst.block_store.save_block(block, parts, commit)
        state = dst.block_exec.apply_block(state, bid, block)
