"""Node: assembles every subsystem into a running validator/full node
(reference node/node.go:279 NewNode, node/setup.go).

Construction order mirrors the reference: DBs -> state from store or
genesis -> app conns -> event bus -> privval -> ABCI handshake ->
mempool/evidence/executor -> blocksync + consensus reactors -> p2p
transport/switch -> (on start) RPC.
"""

from __future__ import annotations

import logging
import os

_log = logging.getLogger(__name__)

from ..abci.client import LocalClient
from ..apps.kvstore import KVStoreApplication
from ..blocksync.reactor import BlocksyncReactor
from ..config import Config
from ..consensus.reactor import ConsensusReactor
from ..consensus.replay import (
    ErrWALMissingEndHeight, Handshaker, catchup_replay)
from ..consensus.wal import DataCorruptionError
from ..consensus.state import ConsensusConfig, ConsensusState
from ..consensus.wal import WAL
from ..evidence import EvidencePool, EvidenceReactor
from ..libs.service import BaseService
from ..mempool import CListMempool
from ..mempool.reactor import MempoolReactor
from ..p2p.key import NodeKey
from ..p2p.node_info import NodeInfo, ProtocolVersion
from ..p2p.switch import Switch
from ..p2p.transport import MultiplexTransport
from ..privval import FilePV
from ..proxy.multi_app_conn import AppConns, default_client_creator
from ..state.execution import BlockExecutor
from ..state.state import make_genesis_state
from ..state.store import StateStore
from ..store.blockstore import BlockStore
from ..store.kv import open_db
from ..types import events as ev
from ..types.genesis import GenesisDoc

# all gossip channels this node speaks
NODE_CHANNELS = bytes([0x00, 0x20, 0x21, 0x22, 0x23, 0x30, 0x38, 0x40,
                       0x60, 0x61])


def init_files(config: Config, chain_id: str = "",
               app_state=None) -> GenesisDoc:
    """`init` command (cmd/cometbft/commands/init.go): create the
    private validator, node key, and a single-validator genesis."""
    config.ensure_dirs()
    pv = FilePV.load_or_generate(config.priv_validator_key_file(),
                                 config.priv_validator_state_file())
    NodeKey.load_or_gen(config.node_key_file())

    genesis_path = config.genesis_file()
    if os.path.exists(genesis_path):
        return GenesisDoc.from_file(genesis_path)

    from ..types.genesis import GenesisValidator
    from ..types.timestamp import Timestamp
    if not chain_id:
        chain_id = "test-chain-%s" % os.urandom(3).hex()
    genesis = GenesisDoc(
        chain_id=chain_id,
        genesis_time=Timestamp.now(),
        validators=[GenesisValidator(pub_key=pv.get_pub_key(),
                                     power=10)],
        app_state=app_state)
    genesis.save_as(genesis_path)
    return genesis


class Node(BaseService):
    """node.Node."""

    def __init__(self, config: Config, app=None,
                 genesis: GenesisDoc | None = None,
                 block_sync: bool = False,
                 state_provider=None):
        """`state_provider` injects a statesync StateProvider (tests use
        in-memory light providers; production builds one from
        config.statesync.rpc_servers)."""
        super().__init__("Node")
        self.config = config
        config.ensure_dirs()
        config.validate_basic()

        # L3: databases + stores (node.go initDBs)
        backend = config.base.db_backend
        db_dir = config.db_dir()
        self.block_store = BlockStore(
            open_db(backend, os.path.join(db_dir, "blockstore.db")))
        self.state_store = StateStore(
            open_db(backend, os.path.join(db_dir, "state.db")))

        # genesis + state (node.go LoadStateFromDBOrGenesisDocProvider)
        self.genesis = genesis or GenesisDoc.from_file(
            config.genesis_file())
        state = self.state_store.load()
        if state is None:
            state = make_genesis_state(self.genesis)
            self.state_store.bootstrap(state)

        # L4: app connections (node.go createAndStartProxyAppConns)
        if app is None and config.base.abci == "kvstore":
            # the reference kvstore takes --snapshot-interval as an app
            # flag, not node config; the env var is this build's analog
            app = KVStoreApplication(
                snapshot_interval=int(os.environ.get(
                    "COMETBFT_TPU_KVSTORE_SNAPSHOT_INTERVAL", "1")))
        self.app = app
        creator = default_client_creator(config.base.abci, app=app)
        self.app_conns = AppConns(creator)
        self.app_conns.start()

        # event bus
        self.event_bus = ev.EventBus()

        # tx/block event indexers (node.go createAndStartIndexerService)
        self.tx_indexer = None
        self.block_indexer = None
        self.event_sink = None
        self.indexer_service = None
        if config.tx_index.indexer == "kv":
            from ..state.indexer import (BlockIndexer, IndexerService,
                                         TxIndexer)
            self.tx_indexer = TxIndexer(
                open_db(backend, os.path.join(db_dir, "tx_index.db")))
            self.block_indexer = BlockIndexer(
                open_db(backend, os.path.join(db_dir, "block_index.db")))
            self.indexer_service = IndexerService(
                self.tx_indexer, self.block_indexer, self.event_bus)
        elif config.tx_index.indexer == "psql":
            # relational sink (reference psql sink; SQLite here) —
            # external consumers query the schema, /tx_search is off
            from ..state.indexer import IndexerService
            from ..state.sink import SQLEventSink
            self.event_sink = SQLEventSink(
                os.path.join(db_dir, "event_sink.db"),
                self.genesis.chain_id)
            self.indexer_service = IndexerService(
                None, None, self.event_bus, event_sink=self.event_sink)

        # privval: remote signer when priv_validator_laddr is set
        # (node.go:347-353 createAndStartPrivValidatorSocketClient),
        # file-backed otherwise
        self.signer_endpoint = None
        if config.base.priv_validator_laddr:
            from ..privval.signer import (SignerClient,
                                          SignerListenerEndpoint)
            self.signer_endpoint = SignerListenerEndpoint(
                config.base.priv_validator_laddr)
            self.priv_validator = SignerClient(
                self.signer_endpoint, self.genesis.chain_id)
            if not self.signer_endpoint.wait_for_connection(30.0):
                self.signer_endpoint.close()
                raise RuntimeError(
                    "no remote signer connected to "
                    f"{config.base.priv_validator_laddr} within 30s")
        else:
            self.priv_validator = FilePV.load_or_generate(
                config.priv_validator_key_file(),
                config.priv_validator_state_file())

        # ABCI handshake: replay to sync app with store (node.go:372)
        handshaker = Handshaker(self.state_store, state,
                                self.block_store, self.genesis,
                                event_bus=self.event_bus)
        handshaker.handshake(self.app_conns)
        state = self.state_store.load() or state
        self.initial_state = state

        # statesync decision: only a node with no history state-syncs
        # (node.go:603 startStateSync gating); consensus + blocksync
        # both wait for it
        self._statesync_enabled = (config.statesync.enable and
                                   state.last_block_height == 0)
        self._state_provider = state_provider
        if self._statesync_enabled and state_provider is None:
            self._state_provider = self._build_state_provider(state)

        # mempool + evidence (node/setup.go)
        mc = config.mempool
        self.mempool = CListMempool(
            self.app_conns.mempool, height=state.last_block_height,
            size=mc.size, max_txs_bytes=mc.max_txs_bytes,
            max_tx_bytes=mc.max_tx_bytes, cache_size=mc.cache_size,
            keep_invalid_txs_in_cache=mc.keep_invalid_txs_in_cache,
            recheck=mc.recheck)
        self.evidence_pool = EvidencePool(
            open_db(backend, os.path.join(db_dir, "evidence.db")),
            self.state_store, self.block_store)

        # background pruner (node.go:1033 createPruner)
        from ..state.pruner import Pruner
        self.pruner = Pruner(
            self.state_store, self.block_store,
            tx_indexer=self.tx_indexer,
            block_indexer=self.block_indexer,
            data_companion_enabled=bool(config.rpc.privileged_laddr
                                        or config.rpc.grpc_privileged_laddr))

        # block executor
        self.block_exec = BlockExecutor(
            self.state_store, self.app_conns.consensus, self.mempool,
            evidence_pool=self.evidence_pool,
            block_store=self.block_store, event_bus=self.event_bus,
            pruner=self.pruner)

        # consensus (WAL + state machine + reactor)
        cc = config.consensus
        cs_config = ConsensusConfig(
            timeout_propose=cc.timeout_propose,
            timeout_propose_delta=cc.timeout_propose_delta,
            timeout_prevote=cc.timeout_prevote,
            timeout_prevote_delta=cc.timeout_prevote_delta,
            timeout_precommit=cc.timeout_precommit,
            timeout_precommit_delta=cc.timeout_precommit_delta,
            timeout_commit=cc.timeout_commit,
            create_empty_blocks=cc.create_empty_blocks,
            create_empty_blocks_interval=cc.create_empty_blocks_interval)
        self.wal = WAL(config.wal_file())
        self.consensus_state = ConsensusState(
            cs_config, state, self.block_exec, self.block_store,
            wal=self.wal, priv_validator=self.priv_validator,
            event_bus=self.event_bus, evidence_pool=self.evidence_pool,
            mempool=self.mempool)
        # crash recovery: WAL tail replay for the in-flight height.
        # Only the fresh-WAL case is benign; mid-log corruption gets one
        # backup-and-truncate repair, and a node that STILL can't replay
        # refuses to start rather than silently skip its locked round.
        if not block_sync:
            try:
                catchup_replay(self.consensus_state,
                               self.consensus_state.height)
            except ErrWALMissingEndHeight:
                pass  # a fresh WAL has nothing to replay
            except DataCorruptionError as e:
                _log.warning("WAL corrupt (%s); attempting repair", e)
                if not self.wal.repair():
                    raise
                # after a repair the EndHeight marker MUST be found: if
                # the truncation ate it, the node may have signed votes
                # it no longer remembers — refuse to start rather than
                # risk equivocation (reference replay.go errors here)
                catchup_replay(self.consensus_state,
                               self.consensus_state.height)
        self.consensus_reactor = ConsensusReactor(
            self.consensus_state,
            wait_sync=block_sync or self._statesync_enabled)

        # blocksync: a statesyncing node activates it AFTER the snapshot
        # restore (switch_to_blocksync), not at start
        self.blocksync_reactor = BlocksyncReactor(
            state, self.block_exec, self.block_store,
            block_sync and not self._statesync_enabled,
            consensus_reactor=self.consensus_reactor,
            peer_timeout=(config.blocksync.peer_timeout
                          if config.blocksync.peer_timeout > 0
                          else None))

        # p2p (node.go createTransport/createSwitch)
        self.node_key = NodeKey.load_or_gen(config.node_key_file())
        self.node_info = NodeInfo(
            protocol_version=ProtocolVersion(),
            node_id=self.node_key.id,
            listen_addr=config.p2p.laddr,
            network=self.genesis.chain_id,
            version="0.1.0-tpu",
            channels=NODE_CHANNELS,
            moniker=config.base.moniker,
            rpc_address=config.rpc.laddr)
        self.transport = MultiplexTransport(self.node_key,
                                            self.node_info)
        listen = config.p2p.laddr.replace("tcp://", "")
        self.switch = Switch(self.transport, listen_addr=listen)
        self.switch.max_inbound = config.p2p.max_num_inbound_peers
        self.switch.max_outbound = config.p2p.max_num_outbound_peers
        if config.p2p.emulate_latency_ms > 0:
            from ..p2p.fuzz import LatencyConnection
            delay = config.p2p.emulate_latency_ms / 1000.0
            self.switch.conn_wrap = (
                lambda conn: LatencyConnection(conn, delay))
        self.switch.add_reactor("CONSENSUS", self.consensus_reactor)
        self.switch.add_reactor("MEMPOOL",
                                MempoolReactor(self.mempool,
                                               config.mempool.broadcast))
        self.switch.add_reactor("EVIDENCE",
                                EvidenceReactor(self.evidence_pool))
        self.switch.add_reactor("BLOCKSYNC", self.blocksync_reactor)

        # statesync reactor: every node SERVES snapshots; a syncing node
        # additionally carries a Syncer (node.go:450)
        from ..statesync import StatesyncReactor
        self.statesync_reactor = StatesyncReactor(self.app_conns.snapshot)
        self.switch.add_reactor("STATESYNC", self.statesync_reactor)

        # peer exchange + address book (node.go:463-501)
        self.addr_book = None
        self.pex_reactor = None
        if config.p2p.pex:
            from ..p2p.pex import AddrBook, NetAddress, PexReactor
            self.addr_book = AddrBook(
                os.path.join(config.base.root_dir,
                             config.p2p.addr_book_file))
            try:
                self.addr_book.add_our_address(
                    NetAddress(self.node_key.id, "0.0.0.0", 0))
            except ValueError:
                pass
            self.addr_book.add_private_ids(
                [i.strip()
                 for i in config.p2p.private_peer_ids.split(",")
                 if i.strip()])
            seeds = [s.strip() for s in config.p2p.seeds.split(",")
                     if s.strip()]
            self.pex_reactor = PexReactor(self.addr_book, seeds=seeds)
            self.switch.add_reactor("PEX", self.pex_reactor)

        self.rpc_server = None
        self.privileged_rpc_server = None
        self.pprof_server = None
        self.grpc_server = None
        self.grpc_privileged_server = None

        # consensus flight recorder: always-on (recording one event is a
        # lock + ring store), dumpable via the flightrec RPC route and
        # /debug/pprof/flightrec; the CONSENSUS layer reaches it through
        # consensus_state.recorder, so per-node even in shared processes
        from ..libs.flightrec import FlightRecorder
        self.flight_recorder = FlightRecorder()
        self.consensus_state.recorder = self.flight_recorder

        # cross-node event timeline (libs/tracetl.py): same always-on
        # discipline and the same reach-through (consensus_state
        # .timeline), dumpable via the tracetl RPC route and
        # /debug/pprof/tracetl
        from ..libs import tracetl as libtracetl
        self.timeline = libtracetl.Timeline(node=self.node_key.id[:8])
        self.consensus_state.timeline = self.timeline
        self.consensus_reactor.timeline = self.timeline
        self.blocksync_reactor.timeline = self.timeline

        # device-time accounting plane (libs/devprof.py): always-on like
        # the flight recorder (an advance is a lock + float adds),
        # dumpable via the devprof RPC route and /debug/pprof/devprof
        from ..libs import devprof as libdevprof
        self.devprof_recorder = libdevprof.DevprofRecorder()
        self.consensus_state.devprof = self.devprof_recorder

        # per-consumer verify-latency ledger (libs/latledger.py):
        # always-on like devprof, dumpable via the latency RPC route
        # and /debug/pprof/latency
        from ..libs import latledger as liblatledger
        self.latledger_recorder = liblatledger.LatLedgerRecorder()
        self.consensus_state.latledger = self.latledger_recorder

        # crash-safe telemetry spool (libs/telspool.py): opt-in via
        # COMETBFT_TPU_TELSPOOL=1 (the e2e runner opts its subprocesses
        # in).  The writer periodically persists every recorder above
        # into CRC-framed segments under <home>/data/telspool so a
        # SIGKILL perturbation loses at most one flush interval; the
        # fleetobs collector harvests them plus the fleetobs RPC route
        from ..libs import telspool as libtelspool
        self.telspool_writer = None
        if libtelspool.enabled():
            import atexit
            self.telspool_writer = libtelspool.SpoolWriter(
                os.path.join(config.base.root_dir, "data", "telspool"),
                node=self.node_key.id[:8])
            self.telspool_writer.flight_recorder = self.flight_recorder
            self.telspool_writer.timeline = self.timeline
            self.telspool_writer.devprof = self.devprof_recorder
            self.telspool_writer.latledger = self.latledger_recorder
            self.consensus_state.telspool = self.telspool_writer
            atexit.register(self.telspool_writer.stop)

        # device health circuit breaker (crypto/devhealth.py): always-on
        # and process-wide — every VerifyPipeline constructed after this
        # point (and mesh.maybe_split_verify) adopts it, so quarantines
        # survive pipeline restarts; dumpable via /debug/pprof/devhealth
        from ..crypto import devhealth as libdevhealth
        self._owns_device_health = libdevhealth.registry() is None
        if self._owns_device_health:
            libdevhealth.set_registry(libdevhealth.HealthRegistry())
        self.device_health = libdevhealth.registry()

        # Prometheus metrics (node.go:868 startPrometheusServer;
        # per-package metrics.go structs)
        self.metrics_server = None
        self.statesync_metrics = None
        if config.instrumentation.prometheus:
            from ..libs import metrics as libmetrics
            from ..libs.metrics import (BlockSyncMetrics, CacheMetrics,
                                        ConsensusMetrics, DeviceMetrics,
                                        MempoolMetrics, MetricsServer,
                                        P2PMetrics, ProxyMetrics, Registry,
                                        StateMetrics, StateSyncMetrics,
                                        StoreMetrics)
            registry = Registry(config.instrumentation.namespace)
            self.metrics_registry = registry
            self.consensus_state.metrics = ConsensusMetrics(registry)
            self.mempool.metrics = MempoolMetrics(registry)
            self.switch.metrics = P2PMetrics(registry)
            self.state_metrics = StateMetrics(registry)
            self.block_exec.metrics = self.state_metrics
            self.pruner.metrics = self.state_metrics
            self.state_store.metrics = self.state_metrics
            self.blocksync_reactor.metrics = BlockSyncMetrics(registry)
            self.statesync_metrics = StateSyncMetrics(registry)
            self.statesync_metrics.syncing.set(
                1 if self._statesync_enabled else 0)
            self.app_conns.set_metrics(ProxyMetrics(registry))
            self.store_metrics = StoreMetrics(registry)
            # serialized-block cache counters (store/blockstore.py)
            self.block_store.metrics = self.store_metrics
            libmetrics.instrument_methods(
                self.state_store,
                self.state_metrics.store_access_duration_seconds,
                libmetrics.STATE_STORE_TIMED_METHODS)
            libmetrics.instrument_methods(
                self.block_store,
                self.store_metrics.block_store_access_duration_seconds,
                libmetrics.BLOCK_STORE_TIMED_METHODS)
            # the crypto layers report through the process-wide seam
            libmetrics.set_device_metrics(DeviceMetrics(registry))
            libmetrics.set_cache_metrics(CacheMetrics(registry))
            # ... and the verify-plane QoS scheduler's per-lane
            # counters (crypto/sched.py) through its own seam
            libmetrics.set_scheduler_metrics(
                libmetrics.SchedulerMetrics(registry))
            # stage spans (decode/verify-dispatch/device/apply/store):
            # the block-ingest breakdown reports through the same kind
            # of process-wide seam (libs/trace.py)
            from ..libs import trace as libtrace
            from ..libs.metrics import TraceMetrics
            libtrace.set_tracer(libtrace.StageTracer(
                TraceMetrics(registry)))
            # the votestream/RLC layers sit below node wiring and
            # report flush / fallback events through the same kind of
            # process-wide seam
            from ..libs import flightrec as libflightrec
            libflightrec.set_recorder(self.flight_recorder)
            # ... and their timeline spans through tracetl's seam
            libtracetl.set_timeline(self.timeline)
            # ... and their device busy/idle intervals through devprof's
            # seam; the compile hook attributes every XLA compilation
            # this process triggers to the cold-compile ledger
            from ..libs.metrics import DevprofMetrics
            from ..ops import compile_hook
            libmetrics.set_devprof_metrics(DevprofMetrics(registry))
            libdevprof.set_recorder(self.devprof_recorder)
            compile_hook.install(self.devprof_recorder)
            # ... and the crypto layers' request stamps through the
            # latency ledger's seam
            liblatledger.set_recorder(self.latledger_recorder)
            if self.telspool_writer is not None:
                # the spool's `metrics` records carry the exposition
                self.telspool_writer.metrics_registry = registry
            self.metrics_server = MetricsServer(
                registry, config.instrumentation.prometheus_listen_addr)

    # -- lifecycle ---------------------------------------------------------
    def on_start(self) -> None:
        self.event_bus.start()
        if self.indexer_service is not None:
            self.indexer_service.start()
        self.pruner.start()
        if self.metrics_server is not None:
            self.metrics_server.start()
        if self.telspool_writer is not None:
            self.telspool_writer.start()
        self.switch.start()
        self._start_rpc()
        peers = [a.strip()
                 for a in self.config.p2p.persistent_peers.split(",")
                 if a.strip()]
        if peers:
            self.switch.dial_peers_async(peers, persistent=True)
        if self._statesync_enabled:
            import threading
            threading.Thread(target=self._run_statesync,
                             name="statesync", daemon=True).start()

    def _build_state_provider(self, state):
        """Production path: light providers over the configured RPC
        servers (stateprovider.go:47 NewLightClientStateProvider)."""
        from ..light.client import TrustOptions
        from ..light.provider import HttpProvider
        from ..statesync import LightClientStateProvider
        cfg = self.config.statesync
        if len(cfg.rpc_servers) < 2:
            raise ValueError(
                "statesync requires at least 2 rpc_servers")
        providers = []
        for addr in cfg.rpc_servers:
            if "://" not in addr:
                addr = "http://" + addr
            providers.append(HttpProvider(self.genesis.chain_id, addr))
        opts = TrustOptions(period_ns=int(cfg.trust_period * 1e9),
                            height=cfg.trust_height,
                            hash=bytes.fromhex(cfg.trust_hash))
        return LightClientStateProvider(
            self.genesis.chain_id, state.initial_height, providers, opts)

    def _run_statesync(self) -> None:
        """Statesync bootstrap: restore a snapshot, persist the trusted
        state + seen commit, then hand off to blocksync
        (node.go:603 startStateSync -> node.go:158 BootstrapState)."""
        from ..statesync import Syncer
        from ..statesync.messages import SnapshotsRequest, wrap
        from ..statesync.reactor import SNAPSHOT_CHANNEL
        cfg = self.config.statesync
        syncer = Syncer(self.app_conns.snapshot, self.app_conns.query,
                        self._state_provider,
                        self.statesync_reactor.request_chunk,
                        chunk_fetchers=cfg.chunk_fetchers,
                        retry_timeout=cfg.chunk_request_timeout)
        self.statesync_reactor.syncer = syncer
        for peer in self.switch.peers.list():
            peer.try_send(SNAPSHOT_CHANNEL, wrap(SnapshotsRequest()))
        try:
            state, commit = syncer.sync_any(
                discovery_time=cfg.discovery_time)
        except Exception as e:
            _log.error("statesync failed: %s; falling back to blocksync",
                       e)
            self.statesync_reactor.syncer = None
            if self.statesync_metrics is not None:
                self.statesync_metrics.syncing.set(0)
            self.blocksync_reactor.switch_to_blocksync(self.initial_state)
            return
        # the reactor reverts to a pure server once sync finishes
        self.statesync_reactor.syncer = None
        if self.statesync_metrics is not None:
            self.statesync_metrics.syncing.set(0)
        # BootstrapState: persist trusted state + the commit FOR the
        # snapshot height so blocksync/consensus can verify onward
        self.state_store.bootstrap(state)
        self.block_store.save_seen_commit(state.last_block_height, commit)
        self.blocksync_reactor.switch_to_blocksync(state)

    def on_stop(self) -> None:
        from ..crypto import devhealth as libdevhealth
        if self._owns_device_health \
                and libdevhealth.registry() is self.device_health:
            libdevhealth.set_registry(None)
        if self.metrics_server is not None:
            # this node owns the process-wide device-metrics,
            # stage-tracer, and flight-recorder seams
            from ..libs import devprof as libdevprof
            from ..libs import flightrec as libflightrec
            from ..libs import latledger as liblatledger
            from ..libs import metrics as libmetrics
            from ..libs import trace as libtrace
            from ..ops import compile_hook
            libmetrics.set_device_metrics(None)
            libmetrics.set_cache_metrics(None)
            libmetrics.set_scheduler_metrics(None)
            libmetrics.set_devprof_metrics(None)
            libtrace.set_tracer(None)
            libflightrec.set_recorder(None)
            libdevprof.set_recorder(None)
            liblatledger.set_recorder(None)
            compile_hook.uninstall()
        if self.rpc_server is not None:
            self.rpc_server.stop()
        if self.privileged_rpc_server is not None:
            self.privileged_rpc_server.stop()
        if self.pprof_server is not None:
            self.pprof_server.stop()
        if self.grpc_server is not None:
            self.grpc_server.stop()
        if self.grpc_privileged_server is not None:
            self.grpc_privileged_server.stop()
        self.switch.stop()
        self.wal.close()
        self.app_conns.stop()
        self.pruner.stop()
        if self.indexer_service is not None:
            self.indexer_service.stop()
        if self.event_sink is not None:
            self.event_sink.close()
        if self.signer_endpoint is not None:
            self.signer_endpoint.close()
        if self.metrics_server is not None:
            self.metrics_server.stop()
        if self.telspool_writer is not None:
            # graceful-exit durability: the final flush happens here
            self.telspool_writer.stop()
        self.event_bus.stop()

    def _start_rpc(self) -> None:
        """Public, privileged, and pprof listeners start independently
        (node.go:819-902: each has its own gate)."""
        from ..rpc.server import RPCServer
        from ..rpc.core import Environment
        env = Environment(
            state_store=self.state_store,
            block_store=self.block_store,
            consensus_state=self.consensus_state,
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            p2p_switch=self.switch,
            event_bus=self.event_bus,
            genesis=self.genesis,
            app_conns=self.app_conns,
            node_info=self.node_info,
            config=self.config,
            tx_indexer=self.tx_indexer,
            block_indexer=self.block_indexer,
            pruner=self.pruner,
            metrics_registry=getattr(self, "metrics_registry", None))
        if self.config.rpc.laddr:
            addr = self.config.rpc.laddr.replace("tcp://", "")
            self.rpc_server = RPCServer(env, addr)
            self.rpc_server.start()
        # privileged data-companion listener (pruning service)
        if self.config.rpc.privileged_laddr:
            from ..rpc.core import PRIVILEGED_ROUTES
            self.privileged_rpc_server = RPCServer(
                env, self.config.rpc.privileged_laddr.replace("tcp://", ""),
                routes=PRIVILEGED_ROUTES, with_websocket=False)
            self.privileged_rpc_server.start()
        # pprof profiling listener (node.go:889-902)
        if self.config.rpc.pprof_laddr:
            from ..libs.pprof import PprofServer
            self.pprof_server = PprofServer(self.config.rpc.pprof_laddr)
            self.pprof_server.start()
        # native gRPC services (node.go:819-861)
        if self.config.rpc.grpc_services_laddr:
            from ..rpc.grpc_services import NodeGRPCServer
            self.grpc_server = NodeGRPCServer(
                env, self.config.rpc.grpc_services_laddr)
            self.grpc_server.start()
        if self.config.rpc.grpc_privileged_laddr:
            from ..rpc.grpc_services import PrivilegedGRPCServer
            self.grpc_privileged_server = PrivilegedGRPCServer(
                env, self.config.rpc.grpc_privileged_laddr)
            self.grpc_privileged_server.start()

    @property
    def rpc_addr(self) -> str | None:
        return self.rpc_server.bound_addr if self.rpc_server else None

    @property
    def p2p_addr(self) -> str:
        return f"{self.node_key.id}@{self.switch.bound_addr}"
