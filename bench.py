"""Benchmark: Ed25519 verify throughput on one TPU chip.

Metric of record (BASELINE.json): sig-verifies/sec/chip, Ed25519 batch.
Baseline: the reference's Go CPU batch verifier (curve25519-voi behind
crypto/ed25519 BatchVerifier, /root/reference/crypto/ed25519/ed25519.go:208,
bench harness crypto/ed25519/bench_test.go:31-67). The reference publishes
no absolute number; Go single verify is ~70-100 µs/op on server x86 and
voi's batch path roughly halves per-sig cost at batch >= 64, so we take
25,000 sigs/s (40 µs/sig) as the CPU baseline.

Primary metric: the RLC whole-batch equation (ops/ed25519.rlc_verify_kernel)
on a 4095-signature batch — the honest-batch hot path used by
types.VerifyCommit* via crypto/batch.py.  The `extra` field carries the
secondary metrics of record:
  - per_sig_kernel_sigs_per_sec: the per-signature-verdict kernel
    (the fallback/localization path)
  - light_client_headers_per_sec: 150-validator commit verifications
    (BASELINE's 10k-headers x 150-validators sync config), RLC-verified
    with dispatches pipelined the way a syncing light client overlaps
    header verification.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "sigs/sec/chip", "vs_baseline": N,
   "extra": {...}}
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

GO_CPU_BASELINE_SIGS_PER_SEC = 25_000.0


def _make_sigs(n, n_keys=None, msg_len=128):
    """n signatures over n_keys DISTINCT keys (default: all distinct —
    a commit has one signature per validator)."""
    from cometbft_tpu.crypto import ed25519_ref as ref

    if n_keys is None:
        n_keys = n
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey)
        from cryptography.hazmat.primitives.serialization import (
            Encoding, PublicFormat)

        def keygen(seed):
            k = Ed25519PrivateKey.from_private_bytes(seed)
            return seed, k.public_key().public_bytes(
                Encoding.Raw, PublicFormat.Raw)

        def sign(seed, msg):
            return Ed25519PrivateKey.from_private_bytes(seed).sign(msg)
    except ImportError:           # pragma: no cover
        keygen, sign = ref.keygen, ref.sign

    keys = [keygen(bytes([(i & 0xFF), ((i >> 8) & 0xFF), (i >> 16) & 0xFF]
                         + [7] * 29))
            for i in range(n_keys)]
    pks, msgs, sigs = [], [], []
    for i in range(n):
        seed, pub = keys[i % n_keys]
        msg = i.to_bytes(8, "little") * (msg_len // 8)
        pks.append(pub)
        msgs.append(msg)
        sigs.append(sign(seed, msg))
    return pks, msgs, sigs


def bench_rlc(batch: int, iters: int, n_keys=None,
              use_cache: bool = False, passes: int = 1) -> float:
    """Pipelined RLC dispatches; one readback syncs the chain.

    use_cache=False for the headline: distinct one-shot batches get no
    honest benefit from the A-table cache.  use_cache=True measures the
    repeated-valset workload (the light-client/blocksync shape).

    passes>1 repeats the TIMED section (fixtures and compile reused)
    and returns the best pass; the whole spread is kept in
    bench_rlc.last_pass_rates."""
    import jax
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519 as dev

    pks, msgs, sigs = _make_sigs(batch, n_keys=n_keys)
    packed = [jax.device_put(x) for x in ed.pack_rlc(pks, msgs, sigs)]
    if use_cache:
        assert ed.rlc_verify(packed, use_cache=True), \
            "benchmark batch failed RLC verification"
        a_tab, a_ok = ed._A_TABLE_CACHE.get(np.asarray(packed[0]))

        def dispatch():
            return dev.rlc_verify_device_cached_a(a_tab, a_ok,
                                                  *packed[1:])
    else:
        assert bool(np.asarray(dev.rlc_verify_device(*packed))), \
            "benchmark batch failed RLC verification"

        def dispatch():
            return dev.rlc_verify_device(*packed)

    rates = []
    for _ in range(max(1, passes)):
        t0 = time.perf_counter()
        outs = [dispatch() for _ in range(iters)]
        assert np.asarray(outs[-1])
        rates.append(batch / ((time.perf_counter() - t0) / iters))
    # expose the whole spread: max alone hides the run-to-run swing
    bench_rlc.last_pass_rates = [round(r, 1) for r in rates]
    return max(rates)


def bench_per_sig(batch: int, iters: int) -> float:
    import jax
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519 as dev

    pks, msgs, sigs = _make_sigs(batch)
    a, r, s, h, valid = ed.pack_batch(pks, msgs, sigs,
                                      dev.bucket_size(batch))
    args = [jax.device_put(x) for x in (a, r, s, h)]
    verdict = np.asarray(dev.verify_batch_device(*args))
    assert verdict[:batch].all(), "benchmark batch failed to verify"
    t0 = time.perf_counter()
    outs = [dev.verify_batch_device(*args) for _ in range(iters)]
    np.asarray(outs[-1])
    dt = (time.perf_counter() - t0) / iters
    return batch / dt


def bench_device_hash(batch: int, iters: int, n_keys=None) -> float:
    """Fused hash-to-scalar RLC dispatches: SHA-512(R||A||M), the
    per-pubkey zh aggregation and the A-side signed-window recode all
    run on device (ops/ed25519.rlc_verify_hash_kernel); the host ships
    raw padded message blocks.  The host-hash device arm on the SAME
    fixture rides .last_detail for the A/B delta — note the fused rate
    folds in the hashing the host arm leaves behind in host_pack
    spans."""
    import jax
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519 as dev

    pks, msgs, sigs = _make_sigs(batch, n_keys=n_keys)
    packed = [jax.device_put(np.asarray(x))
              for x in ed.pack_rlc_device_hash(pks, msgs, sigs)]
    assert bool(np.asarray(dev.rlc_verify_hash_device(*packed))), \
        "benchmark batch failed fused RLC verification"
    t0 = time.perf_counter()
    outs = [dev.rlc_verify_hash_device(*packed) for _ in range(iters)]
    assert np.asarray(outs[-1])
    rate = batch / ((time.perf_counter() - t0) / iters)

    host_packed = [jax.device_put(x)
                   for x in ed.pack_rlc(pks, msgs, sigs)]
    assert bool(np.asarray(dev.rlc_verify_device(*host_packed)))
    t0 = time.perf_counter()
    outs = [dev.rlc_verify_device(*host_packed) for _ in range(iters)]
    assert np.asarray(outs[-1])
    host_rate = batch / ((time.perf_counter() - t0) / iters)
    bench_device_hash.last_detail = {
        "fused_sigs_per_sec": round(rate, 1),
        "host_hash_device_sigs_per_sec": round(host_rate, 1)}
    return rate


def bench_commit_splice(n_vals: int = 200, iters: int = 50) -> float:
    """Columnar vote sign-bytes assembly for one commit, ms/commit
    (LOWER is better): one numpy splice per timestamp-length group vs
    the per-signature canonical encode the columnar path replaced.
    Byte parity is asserted before timing; the per-sig baseline rides
    .last_detail."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, PartSetHeader)
    from cometbft_tpu.types.timestamp import Timestamp

    bid = BlockID(b"\xab" * 32, PartSetHeader(3, b"\xcd" * 32))
    sigs = [CommitSig(BLOCK_ID_FLAG_COMMIT, bytes([i % 256]) * 20,
                      Timestamp(1_700_000_000 + i, (i * 7919) % 10 ** 9),
                      b"\x00" * 64)
            for i in range(n_vals)]
    commit = Commit(height=1234, round=1, block_id=bid, signatures=sigs)
    chain_id = "bench-chain"
    cols = commit.vote_sign_bytes_all(chain_id)
    per_sig = [canonical.vote_sign_bytes(chain_id, 2, 1234, 1, bid,
                                         s.timestamp) for s in sigs]
    assert cols == per_sig, "columnar splice broke sign-bytes parity"

    t0 = time.perf_counter()
    for _ in range(iters):
        commit._sb_all = None          # defeat the memo: time the splice
        commit.vote_sign_bytes_all(chain_id)
    columnar_ms = (time.perf_counter() - t0) / iters * 1e3
    t0 = time.perf_counter()
    for _ in range(iters):
        [canonical.vote_sign_bytes(chain_id, 2, 1234, 1, bid,
                                   s.timestamp) for s in sigs]
    per_sig_ms = (time.perf_counter() - t0) / iters * 1e3
    bench_commit_splice.last_detail = {
        "columnar_ms": round(columnar_ms, 3),
        "per_sig_ms": round(per_sig_ms, 3),
        "n_vals": n_vals}
    return columnar_ms


def bench_light_headers(n_validators: int, n_dispatches: int,
                        headers_per_dispatch: int) -> float:
    """Headers/sec for light-client sync: the syncing client batches
    headers_per_dispatch commits (same validator set — pack_rlc
    aggregates the repeated pubkeys host-side) into one RLC program,
    pipelining dispatches like a real sync pipeline."""
    import jax
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519 as dev

    pks, msgs, sigs = _make_sigs(n_validators * headers_per_dispatch,
                                 n_keys=n_validators, msg_len=120)
    packed = [jax.device_put(x) for x in ed.pack_rlc(pks, msgs, sigs)]
    # the A-table cache is the honest configuration here: a syncing
    # light client re-verifies the SAME validator set every header
    assert ed.rlc_verify(packed, use_cache=True)
    a_tab, a_ok = ed._A_TABLE_CACHE.get(np.asarray(packed[0]))
    t0 = time.perf_counter()
    outs = [dev.rlc_verify_device_cached_a(a_tab, a_ok, *packed[1:])
            for _ in range(n_dispatches)]
    assert np.asarray(outs[-1])
    dt = time.perf_counter() - t0
    return n_dispatches * headers_per_dispatch / dt


def bench_blocksync(n_vals: int, blocks_per_dispatch: int,
                    dispatches: int) -> float:
    """Blocks/sec for blocksync replay (BASELINE '100k blocks x
    10k-validator set', reference internal/blocksync/reactor.go:546):
    each block costs one VerifyCommitLight = ~2/3 of the validator set
    signing; consecutive blocks share the validator set, so batching
    blocks_per_dispatch commits into one RLC dispatch amortizes the
    whole A-side MSM across blocks."""
    import jax
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519 as dev

    sigs_per_block = (2 * n_vals) // 3 + 1
    pks, msgs, sigs = _make_sigs(sigs_per_block * blocks_per_dispatch,
                                 n_keys=n_vals, msg_len=120)
    packed = [jax.device_put(x) for x in ed.pack_rlc(pks, msgs, sigs)]
    # consecutive blocks share the validator set: cached A tables
    assert ed.rlc_verify(packed, use_cache=True)
    a_tab, a_ok = ed._A_TABLE_CACHE.get(np.asarray(packed[0]))
    t0 = time.perf_counter()
    outs = [dev.rlc_verify_device_cached_a(a_tab, a_ok, *packed[1:])
            for _ in range(dispatches)]
    assert np.asarray(outs[-1])
    dt = time.perf_counter() - t0
    return dispatches * blocks_per_dispatch / dt


def bench_secp(batch: int, iters: int) -> float:
    """secp256k1 ECDSA verifies/sec on device (the reference cannot
    batch this key type at all; crypto/batch/batch.go)."""
    import jax
    from cometbft_tpu.crypto import secp256k1 as sk
    from cometbft_tpu.ops import secp256k1 as dev

    privs = [sk.PrivKey.generate(bytes([i & 0xFF, i >> 8] + [11] * 30))
             for i in range(min(batch, 128))]
    pks, msgs, sigs = [], [], []
    for i in range(batch):
        p = privs[i % len(privs)]
        m = i.to_bytes(8, "little") * 8
        pks.append(p.pub_key().bytes())
        msgs.append(m)
        sigs.append(p.sign(m))
    packed = sk.pack_batch(pks, msgs, sigs, batch)
    args = [jax.device_put(x) for x in packed[:-1]]
    assert np.asarray(dev.verify_batch_device(*args)).all()
    t0 = time.perf_counter()
    outs = [dev.verify_batch_device(*args) for _ in range(iters)]
    np.asarray(outs[-1])
    dt = (time.perf_counter() - t0) / iters
    return batch / dt


def bench_secp_msm(batch: int, iters: int) -> float:
    """secp256k1 ECDSA verifies/sec through the unified MSM engine
    (ops/msm.py shared-table multi-product) on the SAME fixture and
    measurement discipline as bench_secp — both time only the device
    dispatch (pack outside the loop), so the pair is the clean A/B of
    the ladder -> MSM swap (~4224 vs ~1250 field-muls/signature)."""
    import jax
    from cometbft_tpu.crypto import secp256k1 as sk
    from cometbft_tpu.ops import secp256k1 as dev

    privs = [sk.PrivKey.generate(bytes([i & 0xFF, i >> 8] + [11] * 30))
             for i in range(min(batch, 128))]
    pks, msgs, sigs = [], [], []
    for i in range(batch):
        p = privs[i % len(privs)]
        m = i.to_bytes(8, "little") * 8
        pks.append(p.pub_key().bytes())
        msgs.append(m)
        sigs.append(p.sign(m))
    pk = sk.pack_msm_batch(pks, msgs, sigs, batch)
    qtab, q_corr = sk.q_table_cache().get(pk["key_id"], pk["keys_x"],
                                          pk["keys_y"])
    args = jax.device_put((qtab, q_corr, pk["gid"], pk["g_rows"],
                           pk["g_neg"], pk["q_rows"], pk["q_neg"],
                           pk["r_limbs"], pk["rn_limbs"],
                           pk["rn_valid"], pk["s_pt"]))
    assert np.asarray(dev.verify_batch_msm_device(*args)).all()
    t0 = time.perf_counter()
    outs = [dev.verify_batch_msm_device(*args) for _ in range(iters)]
    np.asarray(outs[-1])
    dt = (time.perf_counter() - t0) / iters
    return batch / dt


def bench_mixed_ladder(n_ed: int = 9000, n_secp: int = 1000) -> float:
    """bench_mixed with the secp MSM engine forced off — the ladder
    arm of the same-fixture mixed-commit A/B (the reading itself is
    not gated; perf_gate SKIPs it as a comparison arm)."""
    old = os.environ.get("COMETBFT_TPU_SECP_MSM")
    os.environ["COMETBFT_TPU_SECP_MSM"] = "0"
    try:
        return bench_mixed(n_ed, n_secp)
    finally:
        if old is None:
            os.environ.pop("COMETBFT_TPU_SECP_MSM", None)
        else:
            os.environ["COMETBFT_TPU_SECP_MSM"] = old


def bench_mixed(n_ed: int = 9000, n_secp: int = 1000) -> float:
    """Mixed-keytype commit verify: one 10k-power
    commit whose validator set mixes ed25519 and secp256k1 keys, routed
    through crypto/batch.MixedBatchVerifier — the per-type sub-batches
    dispatch concurrently (ed25519 RLC + secp MSM-engine kernels are
    independent device programs; COMETBFT_TPU_SECP_MSM=0 reverts the
    secp side to the Straus ladder, see bench_mixed_ladder).  The
    reference refuses mixed batches outright (types/validation.go:18);
    this is the measured rate for accepting them."""
    from cometbft_tpu.crypto import batch as cb
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.crypto import secp256k1 as sk

    ed_keys = [ref.keygen(bytes([i + 1]) * 32) for i in range(64)]
    sk_keys = [sk.PrivKey.generate(bytes([i & 0xFF, i >> 8] + [7] * 30))
               for i in range(64)]
    items = []
    for i in range(n_ed):
        seed, pub = ed_keys[i % len(ed_keys)]
        msg = b"mixed-commit-" + i.to_bytes(8, "little") * 4
        items.append((ed.PubKey(pub), msg, ref.sign(seed, msg)))
    for i in range(n_secp):
        p = sk_keys[i % len(sk_keys)]
        msg = b"mixed-commit-" + (n_ed + i).to_bytes(8, "little") * 4
        items.append((p.pub_key(), msg, p.sign(msg)))

    def run_once() -> float:
        v = cb.MixedBatchVerifier()
        for pk, msg, sig in items:
            v.add(pk, msg, sig)
        t0 = time.perf_counter()
        ok, verdicts = v.verify()
        dt = time.perf_counter() - t0
        assert ok and all(verdicts), "mixed commit verify failed"
        return dt

    run_once()                       # warm both kernels
    dt = min(run_once() for _ in range(2))
    return (n_ed + n_secp) / dt


def bench_blocksync_e2e() -> dict:
    """Reactor-level end-to-end: blocks through
    the REAL blocksync/reactor.py -> DeferredSigBatch device verify ->
    blockstore over the simnet in-memory transport, not a dispatch
    loop over pre-packed arrays.  Sizes via SIMNET_BENCH_BLOCKS /
    SIMNET_BENCH_VALS (defaults 96 x 64).  Pinned to pipeline_depth=1
    (the strictly serial ingest loop) so it stays the A/B base arm for
    the pipelined extra below."""
    from cometbft_tpu.simnet import bench as simbench
    return simbench.bench_blocksync_e2e(pipeline_depth=1)


def bench_blocksync_pipelined() -> dict:
    """The overlapped arm of the same e2e on the same seed: the
    reactor's depth-K verify pipeline (crypto/dispatch.py) collects
    and host-packs window N+1 while window N's dispatch is on device.
    Depth via SIMNET_BENCH_PIPELINE_DEPTH (default 3: collect + device
    + apply all concurrently distinct windows); the result carries
    overlap_efficiency (sum-of-stages / wall-clock) and the measured
    device-span-overlaps-collect seconds."""
    from cometbft_tpu.simnet import bench as simbench
    depth = int(os.environ.get("SIMNET_BENCH_PIPELINE_DEPTH", "3"))
    return simbench.bench_blocksync_e2e(pipeline_depth=max(2, depth))


def bench_light_e2e() -> dict:
    """Headers through light/client.py windowed sequential sync
    against a simnet node's real JSON-RPC server (HttpProvider over
    HTTP loopback).  Sizes via SIMNET_LIGHT_HEADERS /
    SIMNET_LIGHT_VALS (defaults 128 x 32)."""
    from cometbft_tpu.simnet import bench as simbench
    return simbench.bench_light_e2e()


def bench_lightserve() -> dict:
    """Coalescing serving-plane fleet A/B (lightserve/): one node's
    LightServeSession serving a seeded synthetic fleet of light
    clients, coalescing OFF then ON on the same seed.  Asserts
    bit-identical served payload digests across arms and a strict
    verify-dispatch reduction in the ON arm; reports the ON arm's
    clients/s and p99 serve latency plus the coalesce ratio.  Sizes
    via SIMNET_LIGHT_FLEET_CLIENTS / _BLOCKS / _VALS / _WORKERS
    (defaults 10000 x 48 x 4 x 32)."""
    from cometbft_tpu.simnet import bench as simbench
    return simbench.bench_lightserve_fleet()


def bench_consensus_e2e() -> dict:
    """Live rounds through the real consensus reactor over simnet:
    blocks committed per wall second, with the per-stage consensus
    breakdown (propose/prevote/precommit/commit + the vote-verify
    dispatch/device spans) and round-latency percentiles.  Sizes via
    SIMNET_CONSENSUS_BLOCKS / SIMNET_CONSENSUS_VALS (defaults
    12 x 4)."""
    from cometbft_tpu.simnet import bench as simbench
    return simbench.bench_consensus_e2e()


def bench_e2e_fleet() -> dict:
    """Fleet telemetry plane e2e (cometbft_tpu/fleetobs/): a real
    multi-process testnet with a SIGKILL perturbation, then the
    collector harvests every node's crash-safe spool + live fleetobs
    RPC dump and merges them onto one clock axis.  Reports the share
    of committed heights carrying cross-process flow edges, the solved
    clock-offset spread, and the fleet critical-path device share.
    Sizes via E2E_FLEET_VALS / E2E_FLEET_BLOCKS (defaults 3 x 4)."""
    import tempfile

    from cometbft_tpu.e2e import Manifest, Testnet
    from cometbft_tpu.fleetobs import report

    vals = max(2, int(os.environ.get("E2E_FLEET_VALS", "3")))
    blocks = int(os.environ.get("E2E_FLEET_BLOCKS", "4"))
    lines = ["load_tx_rate = 10", "run_blocks = %d" % blocks]
    for i in range(vals):
        lines.append("[node.validator%d]" % i)
    lines.append('perturb = ["kill"]')     # the last validator dies
    manifest = Manifest.parse("\n".join(lines) + "\n")
    with tempfile.TemporaryDirectory(prefix="fleetbench-") as home:
        net = Testnet(manifest, os.path.join(home, "net"),
                      chain_id="bench-fleet")
        net.setup()
        net.start()
        try:
            net.wait_for_height(blocks, timeout=180)
            net.run_perturbations()
            tip = max(n.height() for n in net.nodes if n.running())
            net.wait_for_height(tip + 2, timeout=180, nodes=net.nodes)
            time.sleep(1.5)        # > one spool flush post-restart
            capture = net.collect_telemetry()
        finally:
            net.stop()
    fleet = report.fleet_report(capture)
    cov = fleet["coverage"]
    merged = fleet["merged"]
    out = {
        "e2e_fleet_height_coverage": cov["height_coverage"],
        "e2e_fleet_clock_offset_spread_ms":
            merged["clock_offset_spread_ms"],
        "e2e_fleet_critical_path_device_share":
            fleet["critical_path"]["summary"]["device_share"],
        "detail": {
            "nodes": sorted(capture["nodes"]),
            "union_heights": cov["union_heights"],
            "common_heights": cov["common_heights"],
            "cross_flow_edges": cov["cross_flow_edges"],
            "offset_methods": sorted(
                {v["method"] for v in merged["offsets"].values()}),
            "occupancy": fleet["occupancy"]["fleet"],
        },
    }
    bench_e2e_fleet.last = out
    return out


bench_e2e_fleet.last = None


def bench_commit_reverify(n_sigs: int | None = None,
                          iters: int | None = None) -> float:
    """Warm-cache commit re-verify rate: what the H+1 LastCommit
    re-validation costs once the process-wide signature-verdict cache
    (crypto/sigcache.py) holds every verdict.  The first pass is the
    first-seen verify (populates the cache); the timed passes measure
    partition() over the same triples — pure SHA-256 keying + striped
    LRU hits, no device dispatch, no curve math.  Sizes via
    SIGCACHE_BENCH_SIGS / SIGCACHE_BENCH_ITERS (defaults 1024 x 50)."""
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.crypto.batch import safe_verify
    from cometbft_tpu.crypto.ed25519 import PrivKey

    n_sigs = n_sigs if n_sigs is not None else int(
        os.environ.get("SIGCACHE_BENCH_SIGS", "1024"))
    iters = iters if iters is not None else int(
        os.environ.get("SIGCACHE_BENCH_ITERS", "50"))
    prev = sigcache._enabled_override
    sigcache.set_enabled(True)
    sigcache.reset()
    try:
        items = []
        for i in range(n_sigs):
            priv = PrivKey.generate(i.to_bytes(2, "little") + b"\x07" * 30)
            msg = b"commit-reverify" + i.to_bytes(4, "little")
            items.append((priv.pub_key(), msg, priv.sign(msg)))
        assert all(safe_verify(pk, m, s) for pk, m, s in items)
        t0 = time.perf_counter()
        for _ in range(iters):
            verdicts, miss_idx = sigcache.partition(items, label="bench")
            assert not miss_idx and all(verdicts)
        dt = time.perf_counter() - t0
        return n_sigs * iters / dt
    finally:
        sigcache.set_enabled(prev)
        sigcache.reset()


def bench_chaos() -> dict:
    """Recovery metrics from the chaos nemesis engine (docs/CHAOS.md):
    seeded deterministic fault scenarios over simnet — a partition/heal
    cycle (time-to-first-commit after heal), a device-fault burst
    through the verify pipeline's drain path (blocks/s under faults),
    and a flapping-chip quarantine/probe cycle (seconds from
    quarantine entry to the probe that restores the chip).
    A scenario that violates an invariant raises instead of reporting:
    numbers measured on a broken cluster are worse than no numbers.
    Sizes via CHAOS_BENCH_BLOCKS / seed via CHAOS_BENCH_SEED."""
    from cometbft_tpu.chaos import scenarios as chaos_scenarios
    return chaos_scenarios.bench_chaos(
        seed=int(os.environ.get("CHAOS_BENCH_SEED", "29")),
        blocks=int(os.environ.get("CHAOS_BENCH_BLOCKS", "24")))


def _device_or_exit() -> dict:
    """First touch of JAX.  Every number this file prints is a device
    number: with no TPU there is nothing to measure, and no older
    capture stands in for one."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(json.dumps({"ok": False, "device": device,
                          "error": "no TPU: bench.py does not run "
                                   "off the chip"}), flush=True)
        sys.exit(1)
    return device


def _pick(d, *keys) -> dict:
    """The numeric members of d under keys (d may be None)."""
    return {k: d[k] for k in keys
            if isinstance(d, dict) and isinstance(d.get(k), (int, float))}


def main() -> None:
    """One process on one chip: fail without a TPU, run the bench_*
    functions above, print ONE JSON line stamped with the device."""
    device = _device_or_exit()
    from cometbft_tpu.chaos import scenarios as chaos_scen
    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.simnet import bench as simbench

    compile_hook.ensure_compile_cache()
    batch = int(os.environ.get("BENCH_BATCH", "32767"))
    iters = int(os.environ.get("BENCH_ITERS", "8"))
    passes = int(os.environ.get("BENCH_HEADLINE_PASSES", "3"))
    rlc = bench_rlc(batch, iters, passes=passes)   # distinct keys
    extra = {
        "rlc_batch": batch,
        "rlc_keys": "distinct (one per signature)",
        "headline_passes": passes,
        "headline_pass_rates": bench_rlc.last_pass_rates,
        "config": {},
    }

    def run_extra(key, fn, config=None, more=None):
        """extra[key] = fn(); on success also its config note and
        more() — companion readings of the same run.  An extra that
        raises is recorded as such and never loses the others."""
        try:
            extra[key] = fn()
        except Exception as e:
            extra[key] = f"error: {e!r}"[:120]
            return
        if config:
            extra["config"][key] = config
        if more:
            extra.update(more())

    run_extra("per_sig_kernel_sigs_per_sec",
              lambda: round(bench_per_sig(min(batch + 1, 4096), iters), 1))
    run_extra("rlc_cached_a_sigs_per_sec",
              lambda: round(bench_rlc(batch, iters, use_cache=True,
                                      passes=passes), 1),
              "same batch shape, A-side decompression+tables cached "
              "(repeated-valset workload)",
              lambda: {"rlc_cached_a_pass_rates":
                       bench_rlc.last_pass_rates})
    run_extra("device_hash_sigs_per_sec",
              lambda: round(bench_device_hash(batch, iters), 1),
              f"fused SHA-512 + zh aggregation + A-recode on device, "
              f"batch {batch}; host-hash device arm in "
              f"device_hash_detail",
              lambda: {"device_hash_detail":
                       bench_device_hash.last_detail})
    run_extra("commit_splice_ms",
              lambda: round(bench_commit_splice(), 3),
              "columnar vote sign-bytes splice, 200-sig commit, "
              "ms/commit (numpy only, no device)",
              lambda: {"commit_splice_detail":
                       bench_commit_splice.last_detail})
    run_extra("light_client_headers_per_sec",
              lambda: round(bench_light_headers(150, 8, 384), 1),
              "150 validators/commit, 384 commits/RLC dispatch, "
              "pipelined (a dispatch loop, not light/client.py)")
    run_extra("secp256k1_sigs_per_sec",
              lambda: round(bench_secp(4096, 6), 1),
              "batch 4096, per-signature Straus kernel")
    run_extra("secp256k1_msm_sigs_per_sec",
              lambda: round(bench_secp_msm(4096, 6), 1),
              "batch 4096, unified MSM engine (ops/msm.py): same "
              "fixture as secp256k1_sigs_per_sec")
    run_extra("blocksync_blocks_per_sec",
              lambda: round(bench_blocksync(10_000, 48, 4), 2),
              "10k validators, 6667+1 sigs/commit, 48 blocks/dispatch "
              "(a dispatch loop, not the reactor)")

    # -- reactor-level e2e over the simnet: measured THROUGH the
    # protocol stack (blocksync/reactor.py -> blockstore,
    # light/client.py -> real JSON-RPC) rather than beside it
    run_extra("blocksync_e2e_blocks_per_sec",
              lambda: bench_blocksync_e2e()["blocks_per_sec"],
              "simnet e2e: real blocks through the blocksync reactor "
              "into the store (defaults 96 blocks x 64 validators; "
              "SIMNET_BENCH_* overrides)",
              lambda: {"blocksync_e2e_detail": simbench.last_blocksync})
    run_extra("blocksync_pipelined_blocks_per_sec",
              lambda: bench_blocksync_pipelined()["blocks_per_sec"],
              "simnet e2e, overlapped verify pipeline (depth via "
              "SIMNET_BENCH_PIPELINE_DEPTH, default 3); same "
              "blocks/validators/seed as the serial arm",
              lambda: {"blocksync_pipelined_detail":
                       simbench.last_blocksync,
                       "pipeline_overlap_efficiency":
                       simbench.last_blocksync.get("overlap_efficiency")})
    run_extra("light_e2e_headers_per_sec",
              lambda: bench_light_e2e()["headers_per_sec"],
              "simnet e2e: light/client.py sequential sync against a "
              "simnet node's real JSON-RPC server (defaults 128 "
              "headers x 32 validators; SIMNET_LIGHT_* overrides)",
              lambda: {"light_e2e_detail": simbench.last_light})
    run_extra("light_clients_served_per_sec",
              lambda: bench_lightserve()["light_clients_served_per_sec"],
              "lightserve coalescing fleet A/B (docs/LIGHTSERVE.md), "
              "host verify path (SIMNET_LIGHT_FLEET_* overrides, "
              "defaults 10000 clients x 48 blocks x 4 vals)",
              lambda: {**_pick(simbench.last_lightserve,
                               "light_serve_p99_ms"),
                       "light_serve_detail": {
                           k: simbench.last_lightserve.get(k) for k in (
                               "coalesce_ratio", "clients_per_sec_off",
                               "clients_per_sec_on", "p99_ms_off",
                               "p99_ms_on", "verify_windows_off",
                               "verify_windows_on", "verify_sigs_off",
                               "verify_sigs_on", "clients", "blocks",
                               "validators")}})
    run_extra("vote_verify_p99_ms",
              lambda: round(simbench.bench_verify_contention()
                            ["vote_verify_p99_ms"], 3),
              "contention A/B on one shared pipeline: consensus votes "
              "solo vs beside blocksync bulk windows + lightserve "
              "bursts; verdict cache off; QoS scheduler on and off "
              "over the same seeds (SIMNET_CONTENTION_* overrides)",
              lambda: {**_pick(simbench.last_contention,
                               "bulk_verify_p99_ms",
                               "bulk_verify_throughput_ratio",
                               "vote_verify_p99_ms_sched_off"),
                       "verify_latency_detail": {
                           k: simbench.last_contention.get(k) for k in (
                               "vote_verify_p99_ms_solo",
                               "vote_verify_p50_ms",
                               "vote_p99_contention_ratio",
                               "bulk_verify_sigs_per_s", "votes",
                               "bulk_windows", "bulk_window_size",
                               "light_requests", "seed", "depth", "solo",
                               "contended", "contended_sched_off")}})
    run_extra("consensus_e2e_blocks_per_sec",
              lambda: bench_consensus_e2e(
                  attach_timeline=True)["blocks_per_sec"],
              "simnet e2e: live multi-validator rounds through the "
              "real consensus reactor (defaults 12 blocks x 4 "
              "validators; SIMNET_CONSENSUS_* overrides)",
              lambda: {**_pick(simbench.last_consensus,
                               "critical_path_device_share",
                               "verdict_cache_hit_rate",
                               "device_occupancy_fraction",
                               "host_bound_fraction",
                               "compile_seconds_total"),
                       "consensus_e2e_detail": simbench.last_consensus})
    run_extra("commit_reverify_sigs_per_sec",
              lambda: round(bench_commit_reverify(), 1),
              "signature-verdict cache warm re-verify (host only; "
              "SIGCACHE_BENCH_SIGS x SIGCACHE_BENCH_ITERS, defaults "
              "1024 x 50)")
    run_extra("chaos_recovery_seconds",
              lambda: bench_chaos()["chaos_recovery_seconds"],
              "nemesis engine over simnet (docs/CHAOS.md): seconds "
              "from heal to first new commit (CHAOS_BENCH_SEED / "
              "CHAOS_BENCH_BLOCKS overrides)",
              lambda: {**_pick(chaos_scen.last_chaos,
                               "chaos_faulted_blocks_per_sec",
                               "chaos_flap_recovery_seconds"),
                       "chaos_detail": {
                           k: chaos_scen.last_chaos.get(k) for k in (
                               "partition_heal", "device_fault_drain",
                               "device_flap_quarantine")}})
    # the fleet's node processes are children pinned to the CPU
    # (e2e/runner.py): this process keeps the chip
    run_extra("e2e_fleet_height_coverage",
              lambda: bench_e2e_fleet()["e2e_fleet_height_coverage"],
              "fleet telemetry e2e (docs/OBSERVABILITY.md): real "
              "process testnet + kill perturbation (E2E_FLEET_VALS x "
              "E2E_FLEET_BLOCKS, defaults 3 x 4)",
              lambda: {**_pick(bench_e2e_fleet.last,
                               "e2e_fleet_clock_offset_spread_ms",
                               "e2e_fleet_critical_path_device_share"),
                       "e2e_fleet_detail":
                       bench_e2e_fleet.last["detail"]})
    run_extra("mixed_commit_sigs_per_sec",
              lambda: round(bench_mixed(9000, 1000), 1),
              "10k-power mixed commit: 9000 ed25519 + 1000 secp256k1 "
              "through MixedBatchVerifier, secp side on the unified "
              "MSM engine")
    run_extra("mixed_commit_sigs_per_sec_ladder",
              lambda: round(bench_mixed_ladder(9000, 1000), 1),
              "mixed_commit_sigs_per_sec fixture with "
              "COMETBFT_TPU_SECP_MSM=0 (secp Straus ladder arm)")

    print(json.dumps({
        "metric": "ed25519_batch_verify_throughput",
        "value": round(rlc, 1),
        "unit": "sigs/sec/chip",
        "vs_baseline": round(rlc / GO_CPU_BASELINE_SIGS_PER_SEC, 3),
        "device": device,
        "extra": extra,
    }), flush=True)


if __name__ == "__main__":
    main()
