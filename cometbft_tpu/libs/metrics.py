"""Prometheus-style metrics (reference: per-package metrics.go structs
generated by scripts/metricsgen, served by the node's prometheus HTTP
listener, node/node.go:868-886).

Self-contained: Counter/Gauge/Histogram with labels, a Registry, and
text exposition (Prometheus format 0.0.4) over a tiny HTTP server — no
external client dependency, same seam as the reference's
MetricsProvider (per-chain constructor injected into each subsystem).
"""

from __future__ import annotations

import socket
import threading
from . import lockrank
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


def _fmt_labels(names: tuple[str, ...], values: tuple[str, ...]) -> str:
    if not names:
        return ""
    inner = ",".join(
        '%s="%s"' % (n, str(v).replace("\\", "\\\\").replace('"', '\\"'))
        for n, v in zip(names, values))
    return "{%s}" % inner


class _Metric:
    def __init__(self, name: str, help_: str, label_names=()):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self._mtx = lockrank.RankedLock("metrics.series")
        self._values: dict[tuple[str, ...], float] = {}

    def labels(self, *values) -> "_Bound":
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name}: expected {len(self.label_names)} labels")
        return _Bound(self, tuple(str(v) for v in values))

    def _add(self, key: tuple[str, ...], delta: float) -> None:
        with self._mtx:
            self._values[key] = self._values.get(key, 0.0) + delta

    def _set(self, key: tuple[str, ...], value: float) -> None:
        with self._mtx:
            self._values[key] = value

    def collect(self) -> list[str]:
        with self._mtx:
            items = sorted(self._values.items())
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} {self.TYPE}"]
        if not items and not self.label_names:
            items = [((), 0.0)]
        for key, val in items:
            out.append(f"{self.name}{_fmt_labels(self.label_names, key)}"
                       f" {val:g}")
        return out


class _Bound:
    __slots__ = ("_metric", "_key")

    def __init__(self, metric, key):
        self._metric = metric
        self._key = key

    def inc(self, delta: float = 1.0) -> None:
        self._metric._add(self._key, delta)

    def add(self, delta: float) -> None:
        self._metric._add(self._key, delta)

    def set(self, value: float) -> None:
        self._metric._set(self._key, value)

    def observe(self, value: float) -> None:
        self._metric._observe(self._key, value)


class Counter(_Metric):
    TYPE = "counter"

    def inc(self, delta: float = 1.0) -> None:
        self._add((), delta)

    def add(self, delta: float) -> None:
        self._add((), delta)


class Gauge(_Metric):
    TYPE = "gauge"

    def set(self, value: float) -> None:
        self._set((), value)

    def inc(self, delta: float = 1.0) -> None:
        self._add((), delta)

    def add(self, delta: float) -> None:
        self._add((), delta)

    def dec(self, delta: float = 1.0) -> None:
        self._add((), -delta)


DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10)


def _lin_buckets(start: float, width: float, count: int):
    return tuple(start + width * i for i in range(count))


def _exp_buckets(start: float, factor: float, count: int):
    return tuple(start * factor ** i for i in range(count))


# The CLOSED registry of duration-histogram bucket layouts
# (scripts/check_metrics.py rule 8): every `*_seconds` / `*_ms`
# histogram registration must take `buckets=BUCKET_SCHEMES["<name>"]`
# (or omit buckets entirely — the implicit "default").  One shared
# table means same-quantity histograms stay MERGEABLE across
# subsystems and processes instead of each registration inventing its
# own boundaries; the latency ledger (libs/latledger.py) reuses
# "verify_latency" for the same reason.
BUCKET_SCHEMES: dict[str, tuple] = {
    "default": DEFAULT_BUCKETS,
    # sub-ms protocol stages up to ~26s (stage spans, step durations)
    "stage_fast": _exp_buckets(0.0001, 4, 10),
    # whole consensus rounds: 10ms up to ~76s
    "round": _exp_buckets(0.01, 2.7, 10),
    # kv store accesses: 0.2ms decades up to 2s
    "store_access": _exp_buckets(0.0002, 10, 5),
    # ABCI method timings (the reference's own layout)
    "abci_method": (.0001, .0004, .002, .009, .02, .1, .65, 2, 6, 25),
    # verify flushes: 0.5ms up to ~8s
    "flush": _exp_buckets(0.0005, 4, 8),
    # light-client serve latency: 1ms to 10s
    "serve": (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
              0.5, 1.0, 2.5, 5.0, 10.0),
    # per-request verify latency (libs/latledger.py): 0.2ms to ~3s
    # log-spaced — fine enough at the bottom for the vote path's p99,
    # wide enough at the top for brownout tails
    "verify_latency": _exp_buckets(0.0002, 2.5, 14),
}


class Histogram(_Metric):
    TYPE = "histogram"

    def __init__(self, name, help_, label_names=(),
                 buckets=DEFAULT_BUCKETS):
        super().__init__(name, help_, label_names)
        self.buckets = tuple(sorted(buckets))
        self._counts: dict[tuple[str, ...], list[int]] = {}
        self._sums: dict[tuple[str, ...], float] = {}

    def observe(self, value: float) -> None:
        self._observe((), value)

    def _observe(self, key: tuple[str, ...], value: float) -> None:
        with self._mtx:
            counts = self._counts.setdefault(
                key, [0] * (len(self.buckets) + 1))
            self._sums[key] = self._sums.get(key, 0.0) + value
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    counts[i] += 1
                    break
            else:
                counts[-1] += 1

    def collect(self) -> list[str]:
        with self._mtx:
            items = sorted(self._counts.items())
            sums = dict(self._sums)
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} histogram"]
        # exposition conformance: a label-less histogram with no
        # observations still emits its (zero) series, so scrapers never
        # see a # TYPE with no samples
        if not items and not self.label_names:
            items = [((), [0] * (len(self.buckets) + 1))]
        lnames = self.label_names + ("le",)
        for key, counts in items:
            cum = 0
            for i, ub in enumerate(self.buckets):
                cum += counts[i]
                # %g, not repr(): Prometheus renders le="1", never "1.0"
                out.append(f"{self.name}_bucket"
                           f"{_fmt_labels(lnames, key + ('%g' % ub,))} "
                           f"{cum}")
            cum += counts[-1]
            out.append(f"{self.name}_bucket"
                       f"{_fmt_labels(lnames, key + ('+Inf',))} {cum}")
            out.append(f"{self.name}_sum"
                       f"{_fmt_labels(self.label_names, key)} "
                       f"{sums.get(key, 0.0):g}")
            out.append(f"{self.name}_count"
                       f"{_fmt_labels(self.label_names, key)} {cum}")
        return out


class Registry:
    def __init__(self, namespace: str = "cometbft"):
        self.namespace = namespace
        self._mtx = lockrank.RankedLock("metrics.registry")
        self._metrics: list[_Metric] = []

    def _full(self, subsystem: str, name: str) -> str:
        return "_".join(p for p in (self.namespace, subsystem, name) if p)

    def counter(self, subsystem: str, name: str, help_: str,
                labels=()) -> Counter:
        m = Counter(self._full(subsystem, name), help_, labels)
        with self._mtx:
            self._metrics.append(m)
        return m

    def gauge(self, subsystem: str, name: str, help_: str,
              labels=()) -> Gauge:
        m = Gauge(self._full(subsystem, name), help_, labels)
        with self._mtx:
            self._metrics.append(m)
        return m

    def histogram(self, subsystem: str, name: str, help_: str,
                  labels=(), buckets=DEFAULT_BUCKETS) -> Histogram:
        m = Histogram(self._full(subsystem, name), help_, labels, buckets)
        with self._mtx:
            self._metrics.append(m)
        return m

    def expose(self) -> str:
        with self._mtx:
            metrics = list(self._metrics)
        lines: list[str] = []
        for m in metrics:
            lines.extend(m.collect())
        return "\n".join(lines) + "\n"


class MetricsServer:
    """Prometheus scrape endpoint (node/node.go startPrometheusServer)."""

    def __init__(self, registry: Registry, addr: str):
        host, _, port = addr.rpartition(":")
        reg = registry

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: N802
                pass

            def do_GET(self):  # noqa: N802
                body = reg.expose().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        # an empty host means bind-all, like the reference's
        # net.Listen(":26660")
        host = host.strip("[]")
        fam = socket.AF_INET6 if ":" in host else socket.AF_INET

        class Server(ThreadingHTTPServer):
            address_family = fam

        self._httpd = Server((host, int(port)), Handler)
        self._httpd.daemon_threads = True
        bh, bp = self._httpd.server_address[:2]
        # bound_addr must be CONNECTABLE: bind-all hosts (0.0.0.0 / ::)
        # become the matching loopback, IPv6 literals get brackets, and
        # any scope id is stripped
        if bh in ("0.0.0.0", "::", ""):
            bh = "::1" if fam == socket.AF_INET6 else "127.0.0.1"
        bh = bh.split("%", 1)[0]
        self.bound_addr = ("[%s]:%d" if ":" in bh else "%s:%d") % (bh, bp)
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="metrics-http",
            daemon=True)
        self._thread.start()

    def stop(self) -> None:
        # shutdown() deadlocks unless serve_forever() is running
        if self._thread is not None:
            self._httpd.shutdown()
        self._httpd.server_close()


# ---------------------------------------------------------------------------
# per-subsystem metric bundles (the reference's metrics.go structs)
# ---------------------------------------------------------------------------

class ConsensusMetrics:
    """internal/consensus/metrics.go."""

    def __init__(self, reg: Registry):
        self.height = reg.gauge("consensus", "height",
                                "Height of the chain.")
        self.rounds = reg.gauge("consensus", "rounds",
                                "Number of rounds at the latest height.")
        self.validators = reg.gauge("consensus", "validators",
                                    "Number of validators.")
        self.validators_power = reg.gauge(
            "consensus", "validators_power", "Total voting power.")
        self.num_txs = reg.gauge("consensus", "num_txs",
                                 "Number of transactions in the latest block.")
        self.block_size_bytes = reg.gauge(
            "consensus", "block_size_bytes", "Size of the latest block.")
        self.total_txs = reg.counter("consensus", "total_txs",
                                     "Total number of committed transactions.")
        self.block_interval_seconds = reg.histogram(
            "consensus", "block_interval_seconds",
            "Time between this and the last block.")
        # round-level observability (metrics.go MarkStep/MarkRound and
        # the prevote-delay gauges): where a long round spent its time
        self.step_duration_seconds = reg.histogram(
            "consensus", "step_duration_seconds",
            "Time spent per step, by step name.", labels=("step",),
            buckets=BUCKET_SCHEMES["stage_fast"])
        self.round_duration_seconds = reg.histogram(
            "consensus", "round_duration_seconds",
            "Time to complete one consensus round.",
            buckets=BUCKET_SCHEMES["round"])
        self.quorum_prevote_delay = reg.gauge(
            "consensus", "quorum_prevote_delay",
            "Seconds between the proposal timestamp and +2/3 prevotes "
            "for the block arriving, at the latest height.")
        self.full_prevote_delay = reg.gauge(
            "consensus", "full_prevote_delay",
            "Seconds between the proposal timestamp and ALL prevotes "
            "arriving, at the latest height.")
        self.proposal_receive_count = reg.counter(
            "consensus", "proposal_receive_count",
            "Proposals received, by validity status.",
            labels=("status",))
        self.late_votes = reg.counter(
            "consensus", "late_votes",
            "Votes received for earlier heights or rounds than the "
            "node is in, by vote type.", labels=("vote_type",))
        self.duplicate_vote_count = reg.counter(
            "consensus", "duplicate_vote_count",
            "Exact duplicate votes received (re-gossiped copies).")


class MempoolMetrics:
    """mempool/metrics.go."""

    def __init__(self, reg: Registry):
        self.size = reg.gauge("mempool", "size",
                              "Number of uncommitted transactions.")
        self.size_bytes = reg.gauge("mempool", "size_bytes",
                                    "Total size of the mempool in bytes.")
        self.tx_size_bytes = reg.histogram(
            "mempool", "tx_size_bytes", "Transaction sizes.",
            buckets=(32, 128, 512, 2048, 8192, 32768, 131072))
        self.failed_txs = reg.counter("mempool", "failed_txs",
                                      "Number of failed transactions.")
        self.recheck_times = reg.counter(
            "mempool", "recheck_times", "Number of tx rechecks.")
        self.already_received_txs = reg.counter(
            "mempool", "already_received_txs",
            "Txs received more than once.")


class P2PMetrics:
    """p2p/metrics.go: the peers gauge plus per-channel byte counters.
    The counters count framed wire bytes (length prefix + packet) at
    the MConnection seam, so both directions of a link agree."""

    def __init__(self, reg: Registry):
        self.peers = reg.gauge("p2p", "peers", "Number of peers.")
        self.message_send_bytes_total = reg.counter(
            "p2p", "message_send_bytes_total",
            "Wire bytes sent, by channel.", labels=("chID",))
        self.message_receive_bytes_total = reg.counter(
            "p2p", "message_receive_bytes_total",
            "Wire bytes received, by channel.", labels=("chID",))


class StateMetrics:
    """state/metrics.go."""

    def __init__(self, reg: Registry):
        self.block_processing_time = reg.histogram(
            "state", "block_processing_time",
            "Time spent processing FinalizeBlock, in ms.",
            buckets=_lin_buckets(1, 10, 10))
        self.consensus_param_updates = reg.counter(
            "state", "consensus_param_updates",
            "Number of consensus parameter updates returned by the "
            "application since process start.")
        self.validator_set_updates = reg.counter(
            "state", "validator_set_updates",
            "Number of validator set updates returned by the "
            "application since process start.")
        self.pruning_service_block_retain_height = reg.gauge(
            "state", "pruning_service_block_retain_height",
            "Accepted block retain height set by the data companion.")
        self.pruning_service_block_results_retain_height = reg.gauge(
            "state", "pruning_service_block_results_retain_height",
            "Accepted block results retain height set by the data "
            "companion.")
        self.pruning_service_tx_indexer_retain_height = reg.gauge(
            "state", "pruning_service_tx_indexer_retain_height",
            "Accepted tx indices retain height set by the companion.")
        self.pruning_service_block_indexer_retain_height = reg.gauge(
            "state", "pruning_service_block_indexer_retain_height",
            "Accepted block indices retain height set by the companion.")
        self.application_block_retain_height = reg.gauge(
            "state", "application_block_retain_height",
            "Accepted block retain height set by the application.")
        self.block_store_base_height = reg.gauge(
            "state", "block_store_base_height",
            "First height at which a block is available.")
        self.abci_results_base_height = reg.gauge(
            "state", "abci_results_base_height",
            "First height at which ABCI results are available.")
        self.tx_indexer_base_height = reg.gauge(
            "state", "tx_indexer_base_height",
            "First height at which tx indices are available.")
        self.block_indexer_base_height = reg.gauge(
            "state", "block_indexer_base_height",
            "First height at which block indices are available.")
        self.store_access_duration_seconds = reg.histogram(
            "state", "store_access_duration_seconds",
            "Duration of accesses to the state store, by method.",
            labels=("method",),
            buckets=BUCKET_SCHEMES["store_access"])
        # StateStore.load_validators (state/store.py), counted by the
        # store itself through its `metrics` attribute
        self.validators_loads = reg.counter(
            "state", "validators_loads_total",
            "Validator sets loaded from the state store, by how the "
            "answer was reached: stored (a full record), resumed (a "
            "pointer record caught up from a height already answered) "
            "or restarted (caught up from the full record).",
            labels=("path",))
        self.validators_catchup_rounds = reg.counter(
            "state", "validators_catchup_rounds_total",
            "Proposer-priority rounds run to catch loaded validator "
            "sets up to the height asked for.")


class StoreMetrics:
    """store/metrics.go."""

    def __init__(self, reg: Registry):
        self.block_store_access_duration_seconds = reg.histogram(
            "store", "block_store_access_duration_seconds",
            "Duration of accesses to the block store, by method.",
            labels=("method",),
            buckets=BUCKET_SCHEMES["store_access"])
        # serialized-block cache (types/part_set.SerializedBlockCache):
        # encode-once, serve-many — a gossip/blocksync peer request hits
        # the cached wire bytes instead of re-encoding + re-splitting
        self.block_cache_hits = reg.counter(
            "store", "block_cache_hits",
            "Serialized-block cache hits: a block or part request was "
            "served from cached wire bytes (no re-encode).")
        self.block_cache_misses = reg.counter(
            "store", "block_cache_misses",
            "Serialized-block cache misses: the block was decoded and "
            "re-encoded from stored parts.")
        self.block_cache_evictions = reg.counter(
            "store", "block_cache_evictions",
            "Serialized-block cache entries evicted by the LRU bound "
            "or invalidated by delete/prune.")


class BlockSyncMetrics:
    """internal/blocksync/metrics.go."""

    def __init__(self, reg: Registry):
        self.syncing = reg.gauge(
            "blocksync", "syncing",
            "Whether the node is block syncing. 1 if yes, 0 if no.")
        self.num_txs = reg.gauge(
            "blocksync", "num_txs",
            "Number of transactions in the latest block.")
        self.total_txs = reg.gauge(
            "blocksync", "total_txs", "Total number of transactions.")
        self.block_size_bytes = reg.gauge(
            "blocksync", "block_size_bytes", "Size of the latest block.")
        self.latest_block_height = reg.gauge(
            "blocksync", "latest_block_height",
            "The height of the latest block.")
        self.windows_rejected = reg.counter(
            "blocksync", "windows_rejected_total",
            "Verify windows whose verdict came out false, and blocks "
            "that failed validation at apply: each drops the suppliers "
            "of the pairs it names and fetches the pairs again.")
        self.peers_dropped = reg.counter(
            "blocksync", "peers_dropped_total",
            "Peers dropped for a block they supplied, by reason "
            "(served_invalid_block, missing_extended_commit).",
            labels=("reason",))
        self.blocks_refetched = reg.counter(
            "blocksync", "blocks_refetched_total",
            "Blocks that came back to the pool after a reject had "
            "them fetched again.")

    def record_block(self, block, size_bytes: int | None = None) -> None:
        """recordBlockMetrics (internal/blocksync/metrics.go:31).
        Callers that already hold the wire bytes pass size_bytes to
        skip a re-serialization."""
        ntxs = len(block.data.txs)
        self.num_txs.set(ntxs)
        self.total_txs.add(ntxs)
        if size_bytes is None:
            size_bytes = len(block.to_proto())
        self.block_size_bytes.set(size_bytes)
        self.latest_block_height.set(block.header.height)


class StateSyncMetrics:
    """statesync/metrics.go."""

    def __init__(self, reg: Registry):
        self.syncing = reg.gauge(
            "statesync", "syncing",
            "Whether the node is state syncing. 1 if yes, 0 if no.")


class ProxyMetrics:
    """proxy/metrics.go (subsystem abci_connection)."""

    def __init__(self, reg: Registry):
        self.method_timing_seconds = reg.histogram(
            "abci_connection", "method_timing_seconds",
            "Timing for each ABCI method, by method and call type.",
            labels=("method", "type"),
            buckets=BUCKET_SCHEMES["abci_method"])


class TraceMetrics:
    """libs/trace.py stage spans: the per-stage wall-clock breakdown
    of the block ingest pipeline (decode -> verify-dispatch -> device
    -> apply -> store).  No reference analog — the reference profiles
    with pprof; here the host-residual around each device dispatch is
    the operational question."""

    def __init__(self, reg: Registry):
        self.stage_duration_seconds = reg.histogram(
            "trace", "stage_duration_seconds",
            "Wall-clock duration of one protocol stage span, by "
            "subsystem and stage.",
            labels=("subsystem", "stage"),
            buckets=BUCKET_SCHEMES["stage_fast"])
        self.intervals_dropped = reg.counter(
            "trace", "intervals_dropped_total",
            "Span interval records evicted from the StageTracer ring "
            "(overflow was previously silent).")


class DeviceMetrics:
    """The accelerator seam — the operational signal this framework
    exists for.  No reference analog: the reference's batch verifier is
    a CPU loop; here batch shape, flush cadence, and the RLC fast-path
    hit rate decide whether the TPU is earning its keep."""

    def __init__(self, reg: Registry):
        self.batch_size = reg.histogram(
            "device", "batch_size",
            "Signatures per verify flush, by execution path.",
            labels=("path",),
            buckets=(1, 8, 32, 128, 512, 2048, 8192, 32768))
        self.flush_latency_seconds = reg.histogram(
            "device", "flush_latency_seconds",
            "Wall-clock duration of one streaming-verifier flush, by "
            "resolution path (device / host / cache / drain).",
            labels=("path",),
            buckets=BUCKET_SCHEMES["flush"])
        self.flushes = reg.counter(
            "device", "flushes",
            "Streaming-verifier flushes, by execution path "
            "(device = batched TPU dispatch, host = CPU loop).",
            labels=("path",))
        self.rlc_fallbacks = reg.counter(
            "device", "rlc_fallbacks",
            "RLC whole-batch checks that failed and fell back to the "
            "per-signature verdict kernel.")
        self.signatures_verified = reg.counter(
            "device", "signatures_verified_total",
            "Signatures whose verdict a device program produced, by "
            "program (rlc = the whole batch accepted in one equation, "
            "persig = the per-signature kernel).  Counted where "
            "pipeline windows and synchronous seam batches both pass.",
            labels=("program",))
        self.host_pack_signatures = reg.counter(
            "device", "host_pack_signatures_total",
            "Signatures packed for the RLC equation, by packer (native "
            "= the library of native/rlcpack, one call a batch outside "
            "the interpreter lock; python = the interpreter's loop: a "
            "host without the library, or a caller that brought its "
            "own hashes).  Counted where crypto/ed25519.pack_rlc "
            "returns.",
            labels=("packer",))
        self.device_hash_fallbacks = reg.counter(
            "device", "device_hash_fallbacks",
            "Pipeline windows that left the fused device-hash path "
            "(a message exceeded the static SHA-512 block bucket) and "
            "re-staged through host hashing.")
        self.a_table_cache_hits = reg.counter(
            "device", "a_table_cache_hits",
            "Device A-side table cache hits (validator set reused).")
        self.a_table_cache_misses = reg.counter(
            "device", "a_table_cache_misses",
            "Device A-side table cache misses (table built on device).")
        self.a_table_cache_first_sightings = reg.counter(
            "device", "a_table_cache_first_sightings",
            "RLC batches whose A side the cache had not seen before and "
            "left on the fused kernel (a second sighting builds the "
            "table).")
        self.a_table_cache_bytes = reg.gauge(
            "device", "a_table_cache_bytes",
            "HBM bytes resident in the A-side table cache.")
        # secp256k1 MSM engine per-key window tables
        # (crypto/secp256k1.QTableCache — the same valset reuse
        # pattern as the A-side cache, Weierstrass flavor)
        self.q_table_cache_hits = reg.counter(
            "device", "q_table_cache_hits",
            "Device secp256k1 Q-table cache hits (key set reused).")
        self.q_table_cache_misses = reg.counter(
            "device", "q_table_cache_misses",
            "Device secp256k1 Q-table cache misses (tables built).")
        self.q_table_cache_bytes = reg.gauge(
            "device", "q_table_cache_bytes",
            "HBM bytes resident in the secp256k1 Q-table cache.")
        # the overlapped verify pipeline (crypto/dispatch.py): depth
        # gauges make the host/device overlap operationally visible
        self.pipeline_inflight = reg.gauge(
            "device", "pipeline_inflight_windows",
            "Verify-pipeline windows submitted and not yet resolved.")
        self.pipeline_staged = reg.gauge(
            "device", "pipeline_staging_depth",
            "Verify-pipeline windows host-packed and waiting on the "
            "device dispatch thread.")
        self.pipeline_drains = reg.counter(
            "device", "pipeline_drains",
            "Verify-pipeline drains: a mid-flight device failure made "
            "the faulted window and everything staged behind it "
            "resolve through the host fallback path.")
        # mesh round-robin dispatch (crypto/dispatch.py devices=...,
        # crypto/mesh.py split-RLC): per-device visibility so a limping
        # chip shows up as ITS series, not an aggregate wobble
        self.mesh_dispatches = reg.counter(
            "device", "mesh_dispatches",
            "Verify programs dispatched to a mesh device (round-robin "
            "pipeline windows and split-RLC chunks), by device index.",
            labels=("device",))
        self.pipeline_device_inflight = reg.gauge(
            "device", "pipeline_device_inflight_windows",
            "Verify-pipeline windows assigned to a mesh device and not "
            "yet resolved, by device index.",
            labels=("device",))
        self.pipeline_device_drains = reg.counter(
            "device", "pipeline_device_drains",
            "Per-device pipeline drains: a fault on one mesh device "
            "made windows assigned to it resolve through the host "
            "path (other devices keep dispatching), by device index.",
            labels=("device",))
        # device health circuit breaker (crypto/devhealth.py): the
        # availability contract with the accelerator — state per chip,
        # breaker trips, probe outcomes, and watchdog-caught hangs
        self.health_state = reg.gauge(
            "device", "health_state",
            "Device health state code (0=healthy 1=suspect "
            "2=quarantined 3=probing), by device index.",
            labels=("device",))
        self.quarantines = reg.counter(
            "device", "quarantines_total",
            "Circuit-breaker trips: a device left the dispatch "
            "rotation (fault rate over threshold, or a hang), by "
            "device index.",
            labels=("device",))
        self.probes = reg.counter(
            "device", "probes_total",
            "Known-answer probe batches dispatched to quarantined "
            "devices, by device index and result (ok returns the "
            "device to rotation; fail doubles its backoff).",
            labels=("device", "result"))
        self.watchdog_timeouts = reg.counter(
            "device", "watchdog_timeouts_total",
            "Dispatches the pipeline watchdog caught past the "
            "per-window deadline: window host-resolved, wedged thread "
            "abandoned and replaced, device quarantined, by device "
            "index.",
            labels=("device",))


class ChaosMetrics:
    """Nemesis-engine observability (cometbft_tpu/chaos): what was
    injected, what the invariant oracle caught, and how fast the
    cluster recovered.  scripts/check_metrics.py lints this bundle
    like every other; the engine drives every field."""

    def __init__(self, reg: Registry):
        self.faults_injected = reg.counter(
            "chaos", "faults_injected",
            "Nemesis fault injections, by injector kind "
            "(partition, crash, device_fault, ...).",
            labels=("kind",))
        self.invariant_violations = reg.counter(
            "chaos", "invariant_violations",
            "Invariant-checker violations, by invariant "
            "(agreement, commit_validity, ...).",
            labels=("invariant",))
        self.recovery_seconds = reg.gauge(
            "chaos", "recovery_seconds",
            "Seconds from the most recent partition heal to the first "
            "new commit anywhere in the cluster.")
        self.faulted_blocks_per_sec = reg.gauge(
            "chaos", "faulted_blocks_per_sec",
            "Blocks/s the fault-target node sustained across a "
            "device-fault burst (drain + host fallback included).")


class CacheMetrics:
    """The signature-verdict cache (crypto/sigcache.py): whether the
    process is re-proving facts it already knows.  The hits/misses
    split carries a `consumer` label (consensus / blocksync / light /
    evidence / validation / crypto) so the redundancy shows up per
    product path, not as one aggregate."""

    def __init__(self, reg: Registry):
        self.hits = reg.counter(
            "sigcache", "hits_total",
            "Signature-verdict cache hits (verdict served without "
            "re-verification), by consumer.", labels=("consumer",))
        self.negative_hits = reg.counter(
            "sigcache", "negative_hits_total",
            "Cache hits whose stored verdict is INVALID — a known-bad "
            "triple rejected without re-verification, by consumer.",
            labels=("consumer",))
        self.misses = reg.counter(
            "sigcache", "misses_total",
            "Signature-verdict cache misses (triple verified and "
            "inserted), by consumer.", labels=("consumer",))
        self.insertions = reg.counter(
            "sigcache", "insertions_total",
            "Verdicts inserted at a resolution seam, by consumer.",
            labels=("consumer",))
        self.evictions = reg.counter(
            "sigcache", "evictions_total",
            "LRU evictions (the bounded cache displaced its "
            "least-recently-used verdict).")
        self.entries = reg.gauge(
            "sigcache", "entries",
            "Verdicts currently resident in the cache.")
        self.votestream_coalesced = reg.counter(
            "sigcache", "votestream_coalesced_total",
            "Duplicate in-flight triples coalesced onto one "
            "StreamingVerifier batch slot (two peers flooding the "
            "same vote share one future).")


class LightServeMetrics:
    """The coalescing light-client serving plane
    (cometbft_tpu/lightserve/): how many sync requests one node is
    absorbing, how much verify work the coalescer merged away, and the
    serve-latency distribution the fleet bench gates as
    light_serve_p99_ms.  Driven by LightServeSession / the request
    coalescer; scripts/check_metrics.py lints the bundle like every
    other."""

    def __init__(self, reg: Registry):
        self.requests_total = reg.counter(
            "lightserve", "requests_total",
            "Light-client sync requests accepted (light_sync route "
            "or in-process session.sync).")
        self.coalesced_heights_total = reg.counter(
            "lightserve", "coalesced_heights_total",
            "Header-verify heights that attached to an already "
            "in-flight future instead of enqueueing their own verify "
            "(the cross-request dedupe).")
        self.headers_served_total = reg.counter(
            "lightserve", "headers_served_total",
            "Verified light blocks served to clients (one per path "
            "height per request).")
        self.verify_windows_total = reg.counter(
            "lightserve", "verify_windows_total",
            "Merged verify windows the serving plane flushed through "
            "the VerifyPipeline.")
        self.verify_sigs_total = reg.counter(
            "lightserve", "verify_sigs_total",
            "Commit signatures submitted for verification by the "
            "serving plane (after sigcache partition the pipeline "
            "dispatches the misses).")
        self.prefetched_headers_total = reg.counter(
            "lightserve", "prefetched_headers_total",
            "Light-block payloads the trust-path planner encoded "
            "ahead of demand into the serialized payload cache.")
        self.inflight_heights = reg.gauge(
            "lightserve", "inflight_heights",
            "Heights currently holding an unresolved shared verify "
            "future in the coalescer.")
        self.serve_seconds = reg.histogram(
            "lightserve", "serve_seconds",
            "Per-request serve latency (accept to last header "
            "handed back), seconds.",
            buckets=BUCKET_SCHEMES["serve"])


class DevprofMetrics:
    """The device-time accounting plane (libs/devprof.py): per-chip
    busy/idle partition (idle attributed by cause — staging /
    backpressure / no_work / drain), live occupancy, and the XLA
    cold-compile ledger.  Driven incrementally from
    DevprofRecorder.advance / compile_event."""

    def __init__(self, reg: Registry):
        self.busy_seconds = reg.counter(
            "devprof", "busy_seconds_total",
            "Seconds a verify-pipeline device spent dispatching "
            "windows, by device index.", labels=("device",))
        self.idle_seconds = reg.counter(
            "devprof", "idle_seconds_total",
            "Seconds a verify-pipeline device sat idle, by device "
            "index and attributed cause (staging / backpressure / "
            "no_work / drain).  busy + idle sums to the accounted "
            "wall-clock per device, exactly.",
            labels=("device", "cause"))
        self.occupancy = reg.gauge(
            "devprof", "occupancy_ratio",
            "Busy fraction of each device's accounted wall-clock "
            "(busy_seconds / wall), by device index.",
            labels=("device",))
        self.compile_seconds = reg.counter(
            "devprof", "compile_seconds_total",
            "Seconds spent in XLA compilation (all phases: trace, "
            "lower, backend compile) — the cold-compile cost kept "
            "separate from warm occupancy.")
        self.compile_count = reg.counter(
            "devprof", "compile_count",
            "XLA backend compilations, by dispatch kind "
            "(ops/compile_hook.py dispatch_scope label; 'other' for "
            "compiles outside any scope).", labels=("kind",))


class SchedulerMetrics:
    """The verify-plane QoS scheduler (crypto/sched.py): per-lane
    dispatch accounting on the shared VerifyPipeline.  Lanes are
    consumer labels from the closed registry (crypto/sigcache.LANES);
    preemptions count dispatches that overtook earlier-submitted
    lower-priority windows, held_seconds accumulates how long the
    overtaken windows waited (the same wait the latency ledger books
    as queue_wait), and lane_deficit exposes the deficit round-robin
    counters that keep equal-priority lanes fair."""

    def __init__(self, reg: Registry):
        self.dispatched_windows = reg.counter(
            "sched", "dispatched_windows",
            "Verify windows dispatched, by lane (consumer label).",
            labels=("lane",))
        self.dispatched_sigs = reg.counter(
            "sched", "dispatched_sigs",
            "Signatures dispatched, by lane.", labels=("lane",))
        self.preemptions = reg.counter(
            "sched", "preemptions_total",
            "Dispatches where this lane's window overtook at least "
            "one earlier-submitted lower-priority window.",
            labels=("lane",))
        self.held_seconds = reg.counter(
            "sched", "held_seconds_total",
            "Seconds windows of this lane spent overtaken (held "
            "staged while higher lanes dispatched ahead of them).",
            labels=("lane",))
        self.lane_deficit = reg.gauge(
            "sched", "lane_deficit_sigs",
            "Deficit round-robin balance, in signatures, by lane "
            "(equal-priority fairness state).", labels=("lane",))


def instrument_methods(obj, hist: Histogram, methods) -> None:
    """Wrap named bound methods of obj with duration observation
    labeled by method name — the reference's metricsgen store wrappers
    (StoreAccessDurationSeconds{method=...}) without a wrapper class
    per store.  Instance-attribute shadowing means references to the
    OBJECT taken before instrumentation still hit the timed path."""
    import functools
    import time as _time

    def make(fn, name):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            t0 = _time.monotonic()
            try:
                return fn(*args, **kwargs)
            finally:
                hist.labels(name).observe(_time.monotonic() - t0)
        return wrapped

    for name in methods:
        fn = getattr(obj, name, None)
        if fn is not None and callable(fn):
            setattr(obj, name, make(fn, name))


STATE_STORE_TIMED_METHODS = (
    "load", "save", "bootstrap", "load_validators",
    "load_consensus_params", "save_finalize_block_response",
    "load_finalize_block_response", "prune_states",
    "prune_abci_responses")

BLOCK_STORE_TIMED_METHODS = (
    "save_block", "save_seen_commit", "save_extended_commit",
    "load_block_meta", "load_block_meta_by_hash", "load_block",
    "load_block_bytes", "load_block_by_hash", "load_block_part",
    "load_block_commit",
    "load_seen_commit", "load_extended_commit", "prune_blocks")


# Process-wide device-metrics hook: the crypto layers (votestream,
# batch dispatch, the A-table cache) sit below any node wiring, so
# they report through this seam when a node has installed metrics.
_device_metrics: DeviceMetrics | None = None


def set_device_metrics(dm: DeviceMetrics | None) -> None:
    global _device_metrics
    _device_metrics = dm


def device_metrics() -> DeviceMetrics | None:
    return _device_metrics


# Same seam for the signature-verdict cache (crypto/sigcache.py): the
# cache is process-wide and sits below node wiring.
_cache_metrics: CacheMetrics | None = None


def set_cache_metrics(cm: CacheMetrics | None) -> None:
    global _cache_metrics
    _cache_metrics = cm


def cache_metrics() -> CacheMetrics | None:
    return _cache_metrics


# ... and for the light-client serving plane (lightserve/): sessions
# are constructed lazily by RPC environments, below any node wiring.
_lightserve_metrics: LightServeMetrics | None = None


def set_lightserve_metrics(lm: LightServeMetrics | None) -> None:
    global _lightserve_metrics
    _lightserve_metrics = lm


def lightserve_metrics() -> LightServeMetrics | None:
    return _lightserve_metrics


# ... and for the device-time accounting plane (libs/devprof.py): the
# pipeline's dispatch threads drive it from below node wiring.
_devprof_metrics: DevprofMetrics | None = None


def set_devprof_metrics(dm: DevprofMetrics | None) -> None:
    global _devprof_metrics
    _devprof_metrics = dm


def devprof_metrics() -> DevprofMetrics | None:
    return _devprof_metrics


# ... and for the verify-plane QoS scheduler (crypto/sched.py): the
# pipeline's dispatch threads drive it from below node wiring.
_scheduler_metrics: SchedulerMetrics | None = None


def set_scheduler_metrics(sm: SchedulerMetrics | None) -> None:
    global _scheduler_metrics
    _scheduler_metrics = sm


def scheduler_metrics() -> SchedulerMetrics | None:
    return _scheduler_metrics
