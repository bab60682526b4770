"""ChaosCluster: a simnet cluster with restartable node identities.

SimNode owns reactors and stores; what a nemesis needs on top is the
IDENTITY that survives a crash — the (state, block, evidence) MemDB
triple, the consensus WAL file, and the FilePV last-sign state.  The
cluster keeps those per node name, so ``crash(name)`` tears the live
SimNode down abruptly (buffered WAL tail lost, in-memory app lost)
and ``restart(name)`` rebuilds a fresh SimNode over the surviving
state: the app replays through the production Handshaker, consensus
replays its WAL tail through catchup_replay, and the node redials its
recorded topology — the same recovery sequence node/node.py runs.

The cluster also owns the chaos DEVICE seam: install_chaos_device()
swaps a node's blocksync verify pipeline for one whose dispatch
function the DeviceFaultController drives — honest windows judge from
the staged parse results on the host (deterministic, no XLA), armed
windows raise like a real device fault (exercising the drain path) or,
in the deliberately BROKEN 'forge' mode, skip the drain and claim
every signature valid (the self-test oracle, chaos/invariants.py).
"""

from __future__ import annotations

import os
import threading

from ..libs import lockrank

from ..consensus.replay import ErrWALMissingEndHeight, catchup_replay
from ..consensus.wal import WAL, DataCorruptionError
from ..crypto.dispatch import VerifyPipeline
from ..simnet import SimNetwork, SimNode, grow_chain
from ..simnet.node import make_sim_genesis
from ..store.kv import MemDB
from ..types import validation


class DeviceFaultController:
    """Armable fault burst on a chaos verify pipeline.

    dispatch() is the pipeline's device seam: with no faults armed it
    produces honest verdicts from the window's staged parse results
    (host safe_verify — byte-deterministic, no accelerator); an armed
    window either raises (mode='drain': the pipeline drains it and
    everything staged behind it through the host path, exactly like a
    real device error), wedges forever (mode='hang': the dispatch
    thread blocks until release(), exercising the watchdog's
    abandon-and-replace path), or — mode='forge', the deliberately
    broken injector for the oracle self-test — returns all-true
    WITHOUT verifying anything, which is precisely the bug the
    commit-validity invariant must catch.

    Arm with ``windows < 0`` for an unbounded burst (mode='kill': the
    chip never comes back — every window AND every health probe on it
    faults, so the pipeline quarantines it permanently and, once every
    chip is gone, degrades to brownout).  ``device=`` scopes the burst
    to one mesh chip by ``win.device_index``; probe windows count
    against the armed budget too, so a bounded flap burst produces ONE
    quarantine cycle — probes keep failing while the burst lasts and
    the first post-burst probe restores the chip.
    """

    MODES = ("drain", "forge", "hang", "kill")

    def __init__(self):
        self._mtx = lockrank.RankedLock("chaos.cluster")
        self._armed = 0
        self.mode = "drain"
        self.device: int | None = None
        self.faults_fired = 0
        self.windows_seen = 0
        self.probes_seen = 0
        self.first_fault_t: float | None = None
        self.last_fault_t: float | None = None
        self._release = threading.Event()

    def arm(self, windows: int, mode: str = "drain",
            device: int | None = None) -> None:
        if mode not in self.MODES:
            raise ValueError(f"unknown device-fault mode {mode!r}")
        with self._mtx:
            self._armed = int(windows)
            self.mode = mode
            self.device = int(device) if device is not None else None
            if mode == "hang":
                self._release.clear()

    def release(self) -> None:
        """Unblock every dispatch wedged in hang mode.  The cluster
        calls this BEFORE stopping a node's pipeline so thread joins
        cannot deadlock on a still-wedged dispatch."""
        self._release.set()

    @property
    def armed(self) -> int:
        with self._mtx:
            return self._armed

    def dispatch(self, win):
        import time

        hang = False
        with self._mtx:
            self.windows_seen += 1
            if getattr(win.handle, "subsystem", "") == "probe":
                self.probes_seen += 1
            mine = self.device is None or \
                getattr(win, "device_index", 0) == self.device
            if mine and self._armed != 0:
                if self._armed > 0:
                    self._armed -= 1
                self.faults_fired += 1
                now = time.monotonic()
                if self.first_fault_t is None:
                    self.first_fault_t = now
                self.last_fault_t = now
                if self.mode == "forge":
                    # BROKEN ON PURPOSE: a drain-skipping device fault
                    # resolves the window valid without verifying —
                    # the commit-validity checker MUST trip on this
                    return True, [True] * len(win.items)
                if self.mode == "hang":
                    hang = True
                else:
                    raise RuntimeError("chaos: injected device fault")
        if hang:
            # wedge OUTSIDE the mutex so the watchdog, later arms, and
            # the honest windows on other chips keep flowing; once
            # released, raise — the window was already abandoned and
            # host-resolved, the pipeline drops this stale verdict
            self._release.wait()
            raise RuntimeError("chaos: hung dispatch released")
        if win.mode == "mixed":
            return win.verifier.verify()
        from ..crypto.batch import safe_verify

        out = [safe_verify(pk, m, s) for pk, m, s in win.items]
        return all(out) and bool(out), out


class ChaosCluster:
    """Named simnet nodes + the persistent identity needed to crash
    and restart them.  Roles:

    - server(name, blocks): pre-grown deterministic chain, serves
      blocksync (grow_chain — block hashes are a pure function of the
      cluster seed);
    - syncer(name): block_sync node catching up from the servers;
    - validator(name, index): live consensus participant signing with
      genesis validator key `index`, WAL-backed when workdir is set.
    """

    def __init__(self, seed: int, n_vals: int = 4,
                 chain_id: str = "chaos-chain",
                 workdir: str | None = None):
        self.seed = seed
        self.network = SimNetwork(seed=seed)
        self.genesis, self.privs = make_sim_genesis(
            n_vals, chain_id=chain_id, seed=seed)
        self.workdir = workdir
        self.nodes: dict[str, SimNode] = {}
        self._specs: dict[str, dict] = {}
        self._edges: list[tuple[str, str, bool]] = []
        self.device_controllers: dict[str, DeviceFaultController] = {}
        # per-node HealthRegistry for chaos pipelines: scoped here (not
        # the process seam) so scenarios read quarantine/recovery facts
        # after stop_all, and so restarts reuse the same health view
        self.device_health: dict[str, object] = {}
        self._saved_deferred_threshold: int | None = None
        self._saved_tuning: dict | None = None
        self._started = False
        # process-wide flight recorder for the layers below node
        # wiring (the verify pipeline's drain/flush events report
        # through the libs/flightrec seam); installed for the run,
        # dumped into violation artifacts as the "_process" timeline
        from ..libs.flightrec import FlightRecorder
        self.process_recorder = FlightRecorder()
        self._saved_recorder = None

    def tune_blocksync(self, peer_timeout: float = 2.0,
                       status_interval: float = 0.5) -> None:
        """Shrink the pool's recovery constants so partition-heal
        recovery reflects the PROTOCOL's redo machinery, not a 10-15s
        production polling default (the tests/test_simnet.py faulted
        runs monkeypatch the same two).  Restored at stop_all."""
        from ..blocksync import pool as bpool
        from ..blocksync import reactor as breactor

        if self._saved_tuning is None:
            self._saved_tuning = {
                "peer_timeout": bpool.PEER_TIMEOUT,
                "status_interval": breactor.STATUS_UPDATE_INTERVAL}
        bpool.PEER_TIMEOUT = peer_timeout
        breactor.STATUS_UPDATE_INTERVAL = status_interval

    # -- membership --------------------------------------------------------
    def _register(self, name: str, kind: str, **extra) -> SimNode:
        if name in self._specs:
            raise ValueError(f"duplicate chaos node {name!r}")
        spec = {"kind": kind, "dbs": (MemDB(), MemDB(), MemDB()),
                "pv": None, "wal_path": None, **extra}
        self._specs[name] = spec
        node = self._spawn(name)
        self.nodes[name] = node
        return node

    def add_server(self, name: str, blocks: int,
                   txs_per_block: int = 1) -> SimNode:
        node = self._register(name, "server")
        # +1: blocksync converges one block behind the serving tip
        grow_chain(node, self.privs, blocks + 1,
                   txs_per_block=txs_per_block)
        return node

    def add_syncer(self, name: str) -> SimNode:
        return self._register(name, "syncer")

    def add_validator(self, name: str, index: int,
                      wal: bool = True) -> SimNode:
        wal_path = None
        if wal and self.workdir is not None:
            wal_path = os.path.join(self.workdir, name, "wal")
            os.makedirs(os.path.dirname(wal_path), exist_ok=True)
        return self._register(name, "validator", index=index,
                              wal_path=wal_path)

    def _spawn(self, name: str) -> SimNode:
        spec = self._specs[name]
        kind = spec["kind"]
        wal = None
        if spec.get("wal_path"):
            wal = WAL(spec["wal_path"])
        pv = spec.get("pv")
        if kind == "validator" and pv is None:
            # first boot wraps the genesis key; restarts reuse the
            # FilePV so last-sign state survives (no self-equivocation
            # during WAL catchup)
            pv = self.privs[spec["index"]]
        node = SimNode(
            name, self.genesis, self.network,
            priv_validator=pv,
            block_sync=(kind == "syncer"),
            consensus_active=(kind == "validator"),
            seed=self.seed, dbs=spec["dbs"], wal=wal)
        if kind == "validator":
            spec["pv"] = node.priv_validator
        spec["wal"] = wal
        if wal is not None and node.height() > 0:
            # crash recovery: replay the WAL tail for the in-flight
            # height before the state machine starts (node.py ordering)
            try:
                catchup_replay(node.consensus_state,
                               node.consensus_state.height)
            except ErrWALMissingEndHeight:
                pass
            except DataCorruptionError:
                if wal.repair():
                    catchup_replay(node.consensus_state,
                                   node.consensus_state.height)
                else:
                    raise
        return node

    # -- lifecycle ---------------------------------------------------------
    def start_all(self) -> None:
        from ..libs import flightrec
        self._saved_recorder = flightrec.recorder()
        flightrec.set_recorder(self.process_recorder)
        for node in self.nodes.values():
            node.start()
        self._started = True
        # edges recorded before start dial now that listeners exist; a
        # plan may partition BEFORE start (deterministic fault-at-birth
        # placement), so cross-cut dials fail here and the plan's
        # post-heal `redial` step re-attempts them
        self.redial()

    def redial(self) -> None:
        for dialer, target, persistent in self._edges:
            if dialer not in self.nodes or target not in self.nodes:
                continue
            try:
                self.nodes[dialer].dial(self.nodes[target],
                                        persistent=persistent)
            except Exception:
                pass      # partitioned or already-connected: tolerated

    def stop_all(self) -> None:
        from ..libs import flightrec
        flightrec.set_recorder(self._saved_recorder)
        # unwedge hung dispatches FIRST: pipeline stop joins its device
        # threads, and a thread parked in a hang-mode dispatch would
        # deadlock the join
        for ctl in self.device_controllers.values():
            ctl.release()
        for name, node in list(self.nodes.items()):
            try:
                node.stop()
            except Exception:
                pass
            wal = self._specs[name].get("wal")
            if wal is not None:
                try:
                    wal.close()
                except Exception:
                    pass
        for pipe in list(self.device_controllers):
            self.device_controllers.pop(pipe, None)
        self.device_health.clear()
        if self._saved_deferred_threshold is not None:
            validation.DeferredSigBatch.DEVICE_THRESHOLD = \
                self._saved_deferred_threshold
            self._saved_deferred_threshold = None
        if self._saved_tuning is not None:
            from ..blocksync import pool as bpool
            from ..blocksync import reactor as breactor
            bpool.PEER_TIMEOUT = self._saved_tuning["peer_timeout"]
            breactor.STATUS_UPDATE_INTERVAL = \
                self._saved_tuning["status_interval"]
            self._saved_tuning = None

    def dial(self, dialer: str, target: str,
             persistent: bool = True) -> None:
        """Record a topology edge; dials immediately when the cluster
        is running, else at start_all (listeners must exist first)."""
        self._edges.append((dialer, target, persistent))
        if self._started:
            self.nodes[dialer].dial(self.nodes[target],
                                    persistent=persistent)

    def connect_all(self) -> None:
        names = list(self.nodes)
        for i, a in enumerate(names):
            for b in names[i + 1:]:
                self.dial(b, a)

    # -- crash / restart ---------------------------------------------------
    def crash(self, name: str) -> None:
        """Abrupt stop: reactors die, the in-memory app evaporates,
        any BUFFERED (un-fsynced) WAL tail is lost — only what the
        stores and the WAL's synced records hold survives."""
        node = self.nodes.pop(name)
        # the controller (and its armed/fired stats) outlives the node:
        # it models the chaos HARNESS, not node state
        if name in self.device_controllers and \
                node.blocksync_reactor._pipeline is not None:
            self.device_controllers[name].release()
            node.blocksync_reactor._pipeline.stop()
            node.blocksync_reactor._pipeline = None
        node.stop()
        # deliberately NOT wal.close(): a crash never flushes
        self._specs[name]["wal"] = None

    def restart(self, name: str) -> SimNode:
        """Rebuild the node over its surviving identity and rejoin the
        recorded topology."""
        if name in self.nodes:
            raise ValueError(f"{name!r} is still running")
        node = self._spawn(name)
        self.nodes[name] = node
        spec = self._specs[name]
        if spec.get("chaos_device"):
            self._install_device(name, spec["chaos_device"])
        if self._started:
            node.start()
            for dialer, target, persistent in self._edges:
                try:
                    if dialer == name and target in self.nodes:
                        node.dial(self.nodes[target],
                                  persistent=persistent)
                    elif target == name and dialer in self.nodes:
                        self.nodes[dialer].dial(node,
                                                persistent=persistent)
                except Exception:
                    pass       # partitioned dials fail; redial on heal
        return node

    # -- chaos device seam -------------------------------------------------
    def install_chaos_device(self, name: str, depth: int = 2,
                             devices: int = 0,
                             deadline: float | None = None,
                             probe_backoff_s: float = 0.05,
                             quarantine_after: int = 3,
                             ) -> DeviceFaultController:
        """Route `name`'s blocksync verify windows through a
        controller-driven pipeline and force the deferred threshold
        low enough that windows actually take the device lane (the
        fixture idiom tests/test_simnet.py established).

        ``devices >= 2`` builds a mesh pipeline over that many fake
        chips (ints stand in for jax devices — the controller seam
        never touches them), so per-chip quarantine and round-robin
        skip become observable; ``deadline`` arms the hung-dispatch
        watchdog with a chaos-scale budget (the 600s production
        default would outlive the scenario); the probe/quarantine
        knobs shrink the health registry's recovery constants the
        same way tune_blocksync shrinks the pool's."""
        if self._saved_deferred_threshold is None:
            self._saved_deferred_threshold = \
                validation.DeferredSigBatch.DEVICE_THRESHOLD
            validation.DeferredSigBatch.DEVICE_THRESHOLD = 1
        spec = {"depth": depth, "devices": devices, "deadline": deadline,
                "probe_backoff_s": probe_backoff_s,
                "quarantine_after": quarantine_after}
        self._specs[name]["chaos_device"] = spec
        return self._install_device(name, spec)

    def _install_device(self, name: str,
                        spec) -> DeviceFaultController:
        if isinstance(spec, int):    # pre-health spec shape: bare depth
            spec = {"depth": spec, "devices": 0, "deadline": None,
                    "probe_backoff_s": 0.05, "quarantine_after": 3}
        ctl = self.device_controllers.get(name)
        if ctl is None:
            ctl = DeviceFaultController()
            self.device_controllers[name] = ctl
        health = self.device_health.get(name)
        if health is None:
            from ..crypto.devhealth import HealthRegistry
            health = HealthRegistry(
                quarantine_after=spec["quarantine_after"],
                probe_backoff_s=spec["probe_backoff_s"],
                probe_backoff_max_s=max(0.2,
                                        spec["probe_backoff_s"] * 4))
            self.device_health[name] = health
        node = self.nodes[name]
        devices = (list(range(spec["devices"]))
                   if spec["devices"] >= 2 else None)
        depth = (spec["depth"] if devices is None
                 else max(spec["depth"], 2 * len(devices)))
        pipe = VerifyPipeline(depth=depth, dispatch_fn=ctl.dispatch,
                              name=f"chaos-{name}", devices=devices,
                              health=health,
                              dispatch_deadline_s=spec["deadline"])
        pipe.start()
        reactor = node.blocksync_reactor
        if reactor._pipeline is not None:
            reactor._pipeline.stop()
        reactor._pipeline = pipe
        reactor.pipeline_depth = max(2, depth)
        return ctl

    # -- observation -------------------------------------------------------
    def node(self, name: str) -> SimNode:
        return self.nodes[name]

    def names(self, kind: str | None = None) -> list[str]:
        return [n for n, s in self._specs.items()
                if kind is None or s["kind"] == kind]

    def heights(self) -> dict[str, int]:
        return {n: node.height() for n, node in self.nodes.items()}

    def app_hashes(self) -> dict[str, str]:
        return {n: node.app_hash().hex()
                for n, node in self.nodes.items()}

    def block_hash(self, name: str, height: int) -> str | None:
        meta = self.nodes[name].block_store.load_block_meta(height)
        return meta.header.hash().hex() if meta is not None else None

    def flightrec_dumps(self) -> dict[str, dict]:
        dumps = {n: node.flight_recorder.dump()
                 for n, node in self.nodes.items()}
        dumps["_process"] = self.process_recorder.dump()
        return dumps
