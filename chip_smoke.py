#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the verify plane still runs
on the attached TPU.

One process, one chip.  A node catches up by blocksync and a light
client syncs headers over the real JSON-RPC server, both verifying
commits through DeferredSigBatch -> VerifyPipeline -> crypto/batch ->
ops/ed25519, at the validator counts this system's users run:

  vals175  175 validators (the reference QA report): blocksync of
           --blocks-175 blocks, then a light client over --headers-175
           headers of the same chain, then one tampered commit.
  vals10k  10,000 validators (BASELINE.json's north-star shape):
           blocksync of --blocks-10k blocks.

Depth is what is cut, never width, and it is cut by the clock: every
device program costs one to three minutes of tracing and compiling in a
cold process, and the whole run has 1,200 s.  The defaults trigger ten
programs; --blocks-10k 8 (three more) has passed on the chip in 1,302 s.

Every phase prints one JSON line; the last line of stdout is
{"ok": ..., "device": {...}}.  The script FAILS unless the device did
the work: it reads the pipeline's own counters, the device metrics and
the flight recorder, and refuses a run in which a window drained to
the host, resolved from the verdict cache, or fell back from the RLC
kernel.  There is no flag that relaxes the device check: on anything
but a TPU main() fails at its first step.  Sizes are flags so the
phases can be rehearsed on a CPU by calling them directly
(tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import shutil
import subprocess
import sys
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def check(cond: bool, what: str, failures: list) -> None:
    if not cond:
        failures.append(what)


def finish(rec: dict, failures: list) -> dict:
    """Print a phase's line; raise if any of its checks failed."""
    rec["ok"] = not failures
    if failures:
        rec["failed_checks"] = failures
    emit(rec)
    if failures:
        raise SmokeFailure(f"{rec['phase']}: " + "; ".join(failures))
    return rec


# -- device ------------------------------------------------------------------

def check_device() -> dict:
    """First touch of JAX.  Fails on anything but a TPU."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    emit({"phase": "device", "devices": [str(d) for d in devs],
          "jax": jax.__version__, **info})
    if info["platform"] != "tpu":
        raise SmokeFailure(
            f"no TPU: jax.devices()[0].platform == {info['platform']!r}")
    return info


# -- native libraries -----------------------------------------------------------

def build_native() -> dict:
    """Build native/protowire and native/bls12381 from the tracked
    sources.  Fails if the toolchain is there and a build is not."""
    from cometbft_tpu.crypto import bls12381
    from cometbft_tpu.libs import native_codec

    failures: list = []
    toolchain = bool(shutil.which("make") and shutil.which("g++"))
    built = {}
    for name in ("protowire", "bls12381") if toolchain else ():
        p = subprocess.run(
            ["make", "-B", "-C", os.path.join(REPO, "native", name)],
            capture_output=True, text=True)
        built[name] = p.returncode == 0
        check(p.returncode == 0,
              f"native/{name} build failed: {p.stderr[-400:]}", failures)
    rec = {"phase": "native", "toolchain": toolchain, "built": built,
           "native_codec_enabled": native_codec.enabled(),
           "bls12381_enabled": bls12381.enabled()}
    if toolchain:
        check(rec["native_codec_enabled"],
              "native commit codec not enabled after build", failures)
        check(rec["bls12381_enabled"],
              "bls12381 not enabled after build", failures)
    return finish(rec, failures)


# -- instruments ------------------------------------------------------------------

@dataclasses.dataclass
class Instruments:
    device_metrics: object
    recorder: object
    devprof: object

    def counter(self, metric, *labels) -> float:
        with metric._mtx:
            return metric._values.get(tuple(str(v) for v in labels), 0.0)

    def events_since(self, seq: int) -> list[dict]:
        return [e for e in self.recorder.events() if e["seq"] >= seq]


def install_instruments() -> Instruments:
    """Install what the counters need BEFORE any node starts: with no
    DeviceMetrics / FlightRecorder installed, rlc_fallbacks and the
    EV_* events are dropped and every check on them passes vacuously."""
    from cometbft_tpu.libs import devprof, flightrec
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.ops import compile_hook

    dm = libmetrics.DeviceMetrics(libmetrics.Registry())
    libmetrics.set_device_metrics(dm)
    rec = flightrec.FlightRecorder(capacity=1 << 17)
    flightrec.set_recorder(rec)
    dp = devprof.DevprofRecorder()
    devprof.set_recorder(dp)
    compile_hook.install(dp)
    return Instruments(dm, rec, dp)


def uninstall_instruments() -> None:
    from cometbft_tpu.libs import devprof, flightrec
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.ops import compile_hook

    libmetrics.set_device_metrics(None)
    flightrec.set_recorder(None)
    devprof.set_recorder(None)
    compile_hook.uninstall()


# -- compile ahead, in parallel -------------------------------------------------

def expected_programs(n_vals: int, window_blocks: int, n_windows: int,
                      tamper: bool, light: bool = False) -> list[tuple]:
    """The device programs a blocksync of full windows triggers at this
    validator count, as (kind, K, N) — widths from ops/ed25519.pad_width
    and bucket_size, never from a guess.

    Windows hold verify_commit_light's signatures (it stops past 2/3 of
    the power); apply-time validate_block checks the FULL LastCommit,
    whose first 2/3 the window already put in the verdict cache, so the
    remainder goes through the batch seam as a batch of its own.  The
    first sighting of a validator set takes the fused kernel, later
    ones build the A tables once and take the cached-A kernel
    (crypto/ed25519.ATableCache.get_if_worthwhile)."""
    from cometbft_tpu.crypto.ed25519 import ATableCache
    from cometbft_tpu.ops import ed25519 as dev

    signers = n_vals * 2 // 3 + 1        # equal powers: first past 2/3
    rest = n_vals - signers
    out = []

    def batch(n_keys, n_sigs, sightings):
        if sightings < 1:
            return
        k, n = dev.pad_width(n_keys + 1), dev.pad_width(n_sigs)
        out.append(("ed25519_rlc", k, n))
        if sightings > 1 and k >= ATableCache.MIN_K:
            out.append(("ed25519_a_tables", k))
            out.append(("ed25519_rlc_cached", k, n))

    batch(signers, signers * window_blocks, n_windows)
    # the first block's LastCommit is empty
    batch(rest, rest, window_blocks * n_windows - 1)
    if light:
        # the light client checks its trust root's commit through the
        # batch seam; its windows repeat the blocksync window's shape
        batch(signers, signers, 2)
        out.remove(("ed25519_rlc", *out[-1][1:]))
    if tamper:
        batch(n_vals, n_vals, 1)
        out.append(("ed25519_persig", dev.bucket_size(n_vals)))
    return out


def prewarm(programs: list[tuple], workers: int) -> dict:
    """Compile the expected programs ahead of the phases, overlapped.
    A whole RLC program takes a minute or more to trace and up to three
    to compile, and a node builds them one by one as shapes first
    appear; done that way the cold build alone outlasts the smoke's
    time limit.  Tracing holds the GIL, so it stays serial; XLA
    releases it while it compiles, so each program compiles in a
    thread while the next is traced.  Nothing here verifies anything:
    the phases below still dispatch through the reactors, and a shape
    this list missed compiles there and shows in that phase's compile
    ledger."""
    import jax
    import jax.numpy as jnp

    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    S = jax.ShapeDtypeStruct
    u32, i32, b = jnp.uint32, jnp.int32, jnp.bool_

    def lower(prog):
        kind, *dims = prog
        if kind == "ed25519_rlc":
            k, n = dims
            return dev._rlc_jitted.lower(
                S((8, k), u32), S((8, n), u32), S((52, k), i32),
                S((52, k), b), S((26, n), i32), S((26, n), b))
        if kind == "ed25519_a_tables":
            return dev._a_tables_jitted.lower(S((8, dims[0]), u32))
        if kind == "ed25519_rlc_cached":
            k, n = dims
            return dev._rlc_cached_jitted.lower(
                S((17, 4, 20, k), i32), S((), b), S((8, n), u32),
                S((52, k), i32), S((52, k), b), S((26, n), i32),
                S((26, n), b))
        if kind == "ed25519_persig":
            n = dims[0]
            return dev._jitted.lower(S((8, n), u32), S((8, n), u32),
                                     S((16, n), u32), S((16, n), u32))
        raise ValueError(prog)

    def compile_(prog, low, trace_s):
        t0 = time.perf_counter()
        with compile_hook.compile_scope(prog[0], prog[1:]):
            low.compile()
        return {"program": list(prog), "trace_lower_s": round(trace_s, 2),
                "compile_s": round(time.perf_counter() - t0, 2)}

    # the XLA-path programs (a width no Pallas block divides) compile
    # longest: lower them first so that overlaps the most tracing
    programs = sorted(dict.fromkeys(programs),
                      key=lambda p: dev._pallas_blk(p[1]) is not None)
    t0 = time.perf_counter()
    # each compile holds gigabytes of host memory while it runs
    with ThreadPoolExecutor(max(1, min(workers, 6)),
                            thread_name_prefix="prewarm") as ex:
        futs = []
        for prog in programs:
            # tracing and lowering hold the GIL: one at a time, here.
            # Six threads tracing at once took 20 times as long each.
            t1 = time.perf_counter()
            low = lower(prog)
            futs.append(ex.submit(compile_, prog, low,
                                  time.perf_counter() - t1))
        done = [f.result() for f in futs]
    rec = {"phase": "prewarm", "programs": done,
           "wall_s": round(time.perf_counter() - t0, 2),
           "serial_s": round(sum(d["trace_lower_s"] + d["compile_s"]
                                 for d in done), 2)}
    emit(rec)
    return rec


# -- the chain fixture --------------------------------------------------------------

class _CachingSigner:
    """A validator key for grow_chain that records each signature it
    makes as verified.  The source node that grows the chain checks
    every LastCommit through the batch seam; those are the fixture's
    own signatures, made a line earlier, and verifying them on the
    device would add three program shapes per size to the cold compile
    bill for nothing the smoke tests.  The verdict cache is reset
    before the syncing node starts, so nothing recorded here reaches
    the path under test."""

    def __init__(self, priv):
        self._priv = priv
        self._pub = priv.pub_key()

    def pub_key(self):
        return self._pub

    def sign(self, msg: bytes) -> bytes:
        from cometbft_tpu.crypto import sigcache

        sig = self._priv.sign(msg)
        sigcache.insert(self._pub, msg, sig, True)
        return sig


@dataclasses.dataclass
class Chain:
    name: str
    n_vals: int
    n_blocks: int          # heights a syncer can complete
    seed: int
    net: object
    genesis: object
    src: object
    grow_s: float


def build_chain(name: str, n_vals: int, n_blocks: int, seed: int) -> Chain:
    """Genesis + a source node holding n_blocks + 1 real blocks (a
    syncer converges one block behind the serving tip).  The whole
    chain exists before anything dials, so verify windows fill."""
    from cometbft_tpu.simnet import (
        SimNetwork, SimNode, grow_chain, make_sim_genesis,
    )

    t0 = time.perf_counter()
    net = SimNetwork(seed=seed)
    genesis, privs = make_sim_genesis(n_vals=n_vals, seed=seed)
    src = SimNode(f"{name}-src", genesis, net, seed=seed)
    grow_chain(src, [_CachingSigner(p) for p in privs], n_blocks + 1,
               txs_per_block=2)
    chain = Chain(name, n_vals, n_blocks, seed, net, genesis, src,
                  round(time.perf_counter() - t0, 2))
    emit({"phase": "chain", "size": name, "validators": n_vals,
          "blocks": n_blocks + 1, "seconds": chain.grow_s,
          "depth": f"cut to {n_blocks} synced blocks; width is the "
                   f"source's"})
    return chain


# -- what a phase reads back ----------------------------------------------------------

def _phase_start(inst: Instruments) -> dict:
    from cometbft_tpu.libs import trace as libtrace

    tr = libtrace.StageTracer()
    prev = libtrace.tracer()
    libtrace.set_tracer(tr)
    snap = inst.devprof.snapshot()
    return {"tracer": tr, "prev_tracer": prev,
            "seq": inst.recorder.recorded,
            "rlc_fallbacks": inst.counter(
                inst.device_metrics.rlc_fallbacks),
            "compiles": snap["compile"]["count"],
            "compile_s": snap["compile"]["seconds_total"],
            "programs": {(p["kind"], tuple(p["shape"] or ())):
                         p["dispatches"] for p in snap["programs"]},
            "t0": time.perf_counter()}


def _phase_end(inst: Instruments, start: dict,
               subsystem: str) -> tuple[dict, list]:
    """Wall seconds, stage seconds, compile and flight-recorder deltas
    since _phase_start, and the subsystem's verify-flush events."""
    from cometbft_tpu.libs import flightrec
    from cometbft_tpu.libs import trace as libtrace

    wall = time.perf_counter() - start["t0"]
    libtrace.set_tracer(start["prev_tracer"])
    evs = inst.events_since(start["seq"])
    snap = inst.devprof.snapshot()
    flushes = [e for e in evs if e["kind"] == flightrec.EV_VERIFY_FLUSH
               and e.get("subsystem") == subsystem]
    paths: dict = {}
    for e in flushes:
        paths[e["path"]] = paths.get(e["path"], 0) + 1
    ran = {}
    for p in snap["programs"]:
        key = (p["kind"], tuple(p["shape"] or ()))
        d = p["dispatches"] - start["programs"].get(key, 0)
        if d:
            ran[f"{p['kind']}{list(key[1])}"] = d
    rec = {
        "wall_s": round(wall, 3),
        "stages": {k: v for k, v in start["tracer"].snapshot().items()
                   if k.startswith(subsystem + ".")},
        "compiles": snap["compile"]["count"] - start["compiles"],
        "compile_s": round(snap["compile"]["seconds_total"]
                           - start["compile_s"], 2),
        "programs_dispatched": ran,
        "window_paths": paths,
        "window_batches": sorted({e["batch"] for e in flushes}),
        "rlc_fallbacks": int(inst.counter(
            inst.device_metrics.rlc_fallbacks) - start["rlc_fallbacks"]),
        "ev_device_fallback": sum(
            e["kind"] == flightrec.EV_DEVICE_FALLBACK for e in evs),
        "ev_pipeline_drain": sum(
            e["kind"] == flightrec.EV_PIPELINE_DRAIN for e in evs),
        "ev_rlc_fallback": sum(
            e["kind"] == flightrec.EV_RLC_FALLBACK for e in evs),
    }
    return rec, flushes


def _check_honest(rec: dict, flushes: list, failures: list,
                  min_device_windows: int) -> None:
    """The device did the work: no drain, no fault, no RLC fallback, no
    window answered by the verdict cache, every window at or over the
    deferred threshold verified on the device."""
    from cometbft_tpu.types.validation import DeferredSigBatch

    paths = rec["window_paths"]
    check(paths.get("device", 0) >= min_device_windows,
          f"device windows {paths.get('device', 0)} < "
          f"{min_device_windows}", failures)
    for bad in ("cache", "drain", "error"):
        check(not paths.get(bad), f"{paths.get(bad)} window(s) resolved "
              f"path={bad}", failures)
    thr = DeferredSigBatch.DEVICE_THRESHOLD
    off = [e["batch"] for e in flushes
           if e["batch"] >= thr and e["path"] != "device"]
    check(not off, f"windows of >= {thr} signatures off the device: "
          f"{off}", failures)
    for key in ("rlc_fallbacks", "ev_device_fallback",
                "ev_pipeline_drain", "ev_rlc_fallback"):
        check(rec[key] == 0, f"{key} == {rec[key]}", failures)


# -- blocksync ------------------------------------------------------------------------

def _hold_windows_until_full(pool, tip: int) -> None:
    """Blocks reach a syncing node's pool one by one and the reactor
    verifies whatever run of them has arrived when it looks, so the
    sizes of its verify windows follow the timing of the run — and
    each size is a device program of its own, minutes to compile.  To
    run the same programs every time, the smoke lets the reactor see a
    window only once it is as full as it will get: VERIFY_WINDOW blocks
    and the one after, or every block up to the source's tip.  What the
    reactor then does with the window is untouched."""
    peek = pool.peek_window

    def peek_full(max_blocks, offset=0):
        window, after = peek(max_blocks, offset)
        if window and window[-1][0].header.height != tip and not (
                len(window) == max_blocks and after is not None):
            return [], None
        return window, after

    pool.peek_window = peek_full


def phase_blocksync(chain: Chain, inst: Instruments,
                    min_device_windows: int = 1,
                    timeout: float = 900.0) -> dict:
    """A fresh node syncs the chain through the real BlocksyncReactor
    at its default PIPELINE_DEPTH; the app hash must equal the
    source's and the pipeline's own counters must show the device."""
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.simnet import SimNode

    src = chain.src
    # the source node put every commit triple in the process-wide
    # verdict cache while it grew the chain: without this the syncing
    # node's windows resolve path == "cache" at submit
    sigcache.reset()
    syncer = SimNode(f"{chain.name}-sync", chain.genesis, chain.net,
                     block_sync=True, seed=chain.seed)
    _hold_windows_until_full(syncer.blocksync_reactor.pool, src.height())
    target = src.sync_target()
    failures: list = []
    pipe_stats: dict = {}
    start = _phase_start(inst)
    try:
        src.start()
        syncer.start()
        syncer.dial(src)
        synced = syncer.wait_for_height(target, timeout=timeout)
        # read BEFORE stop(): on_stop nulls the reactor's pipeline
        pipe = syncer.blocksync_reactor._pipeline
        if pipe is not None:
            pipe_stats = {
                "depth": pipe.depth,
                "submitted": pipe.submitted,
                "device_windows": pipe.device_windows,
                "host_windows": pipe.host_windows,
                "drained_windows": pipe.drained_windows,
                "faults": pipe.faults,
                "quarantines": pipe.health.quarantines("0"),
            }
    finally:
        rec, flushes = _phase_end(inst, start, "blocksync")
        syncer.stop()
        src.stop()
    want = src.block_store.load_block(target + 1).header.app_hash
    rec = {"phase": "blocksync", "size": chain.name,
           "validators": chain.n_vals, "blocks": target,
           "signatures_in_windows": sum(e["batch"] for e in flushes),
           "app_hash": syncer.app_hash().hex(),
           "app_hash_equal": syncer.app_hash() == want,
           "pipeline": pipe_stats, **rec}
    check(synced, f"stalled at {syncer.height()}/{target}", failures)
    check(rec["app_hash_equal"], "app hash differs from the source's",
          failures)
    check(bool(pipe_stats), "the reactor never built its pipeline",
          failures)
    if pipe_stats:
        check(pipe_stats["device_windows"] >= min_device_windows,
              f"device_windows {pipe_stats['device_windows']} < "
              f"{min_device_windows}", failures)
        for key in ("drained_windows", "faults", "quarantines"):
            check(pipe_stats[key] == 0, f"{key} == {pipe_stats[key]}",
                  failures)
    _check_honest(rec, flushes, failures, min_device_windows)
    return finish(rec, failures)


# -- light client -------------------------------------------------------------------------

def phase_light(chain: Chain, inst: Instruments, n_headers: int,
                window: int, min_device_windows: int = 1) -> dict:
    """A light client syncs n_headers of the chain sequentially over
    the source's real JSON-RPC server (HttpProvider), as
    simnet/bench.bench_light_e2e wires it."""
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.light.client import SEQUENTIAL, Client, TrustOptions
    from cometbft_tpu.light.provider import HttpProvider

    src = chain.src
    sigcache.reset()
    failures: list = []
    target = min(1 + n_headers, src.height() - 1)
    start = _phase_start(inst)
    try:
        rpc_addr = src.start_rpc()
        provider = HttpProvider(chain.genesis.chain_id,
                                f"http://{rpc_addr}")
        root = src.block_store.load_block_meta(1)
        opts = TrustOptions(
            period_ns=100 * 365 * 24 * 3600 * 1_000_000_000,
            height=1, hash=root.header.hash())
        client = Client(chain.genesis.chain_id, opts, provider,
                        verification_mode=SEQUENTIAL,
                        sequential_batch_size=window)
        lb = client.verify_light_block_at_height(target)
    finally:
        rec, flushes = _phase_end(inst, start, "light")
        src.stop()
    want = src.block_store.load_block_meta(target).header.hash()
    rec = {"phase": "light", "size": chain.name,
           "validators": chain.n_vals, "headers": target - 1,
           "window_headers": window,
           "signatures_in_windows": sum(e["batch"] for e in flushes),
           "header_hash_equal": lb.signed_header.header.hash() == want,
           **rec}
    check(lb.height == target, f"stopped at {lb.height}/{target}",
          failures)
    check(rec["header_hash_equal"],
          "verified header differs from the source's", failures)
    _check_honest(rec, flushes, failures, min_device_windows)
    return finish(rec, failures)


# -- the plain reference ---------------------------------------------------------------------

def _ref_init() -> None:
    # pool workers verify in pure Python and never need the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)


def _ref_verify_chunk(triples: list) -> list:
    from cometbft_tpu.crypto import ed25519_ref as ref

    out = []
    for pk, msg, sig in triples:
        try:
            out.append(bool(ref.verify(pk, msg, sig)))
        except ValueError:
            out.append(False)
    return out


def phase_reference(chain: Chain, workers: int) -> dict:
    """Every signature of every commit in the synced range, verified
    one by one on the host with crypto/ed25519_ref (not safe_verify,
    which reads the verdict cache): the reference must accept exactly
    what the system accepted — all of them."""
    import multiprocessing

    src = chain.src
    chain_id = chain.genesis.chain_id
    t0 = time.perf_counter()
    triples = []
    for h in range(1, chain.n_blocks + 1):
        commit = src.block_store.load_block_commit(h)
        vals = src.state_store.load_validators(h)
        sbs = commit.vote_sign_bytes_all(chain_id)
        for i, cs in enumerate(commit.signatures):
            triples.append((vals.validators[i].pub_key.bytes(), sbs[i],
                            cs.signature))
    workers = max(1, workers)
    step = max(64, -(-len(triples) // (workers * 4)))
    chunks = [triples[i:i + step] for i in range(0, len(triples), step)]
    if workers == 1:
        verdicts = [v for c in chunks for v in _ref_verify_chunk(c)]
    else:
        with ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn"),
                initializer=_ref_init) as ex:
            verdicts = [v for vs in ex.map(_ref_verify_chunk, chunks)
                        for v in vs]
    failures: list = []
    rejected = verdicts.count(False)
    check(len(verdicts) == len(triples) and rejected == 0,
          f"reference rejects {rejected} of {len(triples)} signatures "
          f"the system accepted", failures)
    return finish({"phase": "reference", "size": chain.name,
                   "commits": chain.n_blocks,
                   "signatures": len(triples), "rejected": rejected,
                   "workers": workers,
                   "wall_s": round(time.perf_counter() - t0, 2)},
                  failures)


# -- one tampered commit ----------------------------------------------------------------------

def phase_tamper(chain: Chain, inst: Instruments) -> dict:
    """Flip one byte of one signature of a stored commit (both picked
    from the seed) and verify the commit with types/validation
    .verify_commit: the device path must raise the same `wrong
    signature (#i)` text the CPU batch verifier produces, after exactly
    one RLC fallback, with the per-signature kernel localising it."""
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.types import validation

    src = chain.src
    rng = random.Random(chain.seed)
    h = rng.randrange(1, chain.n_blocks + 1)
    idx = rng.randrange(chain.n_vals)
    byte = rng.randrange(32)            # the R half of the signature
    commit = src.block_store.load_block_commit(h)
    vals = src.state_store.load_validators(h)
    sigs = list(commit.signatures)
    sig = bytearray(sigs[idx].signature)
    sig[byte] ^= 0x01
    sigs[idx] = dataclasses.replace(sigs[idx], signature=bytes(sig))
    bad = dataclasses.replace(commit, signatures=sigs, _hash=None,
                              _proto=None)

    def verdict() -> str:
        sigcache.reset()
        try:
            validation.verify_commit(chain.genesis.chain_id, vals,
                                     bad.block_id, h, bad)
        except validation.ErrInvalidSignature as e:
            return str(e)
        return "accepted"

    # the expectation: create_batch_verifier(provider="cpu")'s verdicts
    prev = os.environ.get("COMETBFT_TPU_PROVIDER")
    os.environ["COMETBFT_TPU_PROVIDER"] = "cpu"
    try:
        want = verdict()
    finally:
        if prev is None:
            del os.environ["COMETBFT_TPU_PROVIDER"]
        else:
            os.environ["COMETBFT_TPU_PROVIDER"] = prev
    start = _phase_start(inst)
    got = verdict()
    rec, _ = _phase_end(inst, start, "none")
    persig = sum(n for prog, n in rec["programs_dispatched"].items()
                 if prog.startswith("ed25519_persig"))
    sigcache.reset()
    failures: list = []
    check(want.startswith(f"wrong signature (#{idx})"),
          f"the CPU verifier says {want[:40]!r}, not #{idx}", failures)
    check(got == want, f"device path says {got[:40]!r}, the CPU "
          f"verifier {want[:40]!r}", failures)
    check(rec["rlc_fallbacks"] == 1 and rec["ev_rlc_fallback"] == 1,
          f"RLC fallbacks {rec['rlc_fallbacks']} (events "
          f"{rec['ev_rlc_fallback']}), want exactly 1", failures)
    check(persig == 1, f"per-signature kernel dispatched {persig} "
          f"times, want 1", failures)
    check(rec["ev_device_fallback"] == 0, "device fallback recorded",
          failures)
    return finish({"phase": "tamper", "size": chain.name, "height": h,
                   "index": idx, "byte": byte, "error": got[:32],
                   "persig_dispatches": persig, **rec}, failures)


# -- which programs ran ------------------------------------------------------------------------

def phase_programs(inst: Instruments, pallas_from: int) -> dict:
    """Every distinct device program of the run: dispatches, compile
    seconds from the compile_hook ledger, and which kernel each MSM
    side lowered to.  From A width `pallas_from` up (the 10k shapes)
    both sides must be Pallas."""
    import jax

    from cometbft_tpu.ops import ed25519 as dev

    snap = inst.devprof.snapshot()
    compile_s: dict = {}
    for e in snap["compile"]["entries"]:
        key = (e["kind"], tuple(e["shape"] or ()))
        compile_s[key] = compile_s.get(key, 0.0) + e["seconds"]
    failures: list = []
    programs = []
    for p in snap["programs"]:
        shape = tuple(p["shape"] or ())
        row = {"kind": p["kind"], "shape": list(shape),
               "dispatches": p["dispatches"],
               "backend_compile_s": round(
                   compile_s.get((p["kind"], shape), 0.0), 2)}
        if p["kind"] in ("ed25519_rlc", "ed25519_rlc_cached"):
            plan = dev.rlc_kernel_plan(*shape)
            row["a_side"] = plan["a"]["msm"]
            row["r_side"] = plan["r"]["msm"]
            row["fold"] = plan["fold"]
            row["blk"] = [plan["a"]["blk"], plan["r"]["blk"]]
            if shape[0] >= pallas_from:
                check(plan["a"]["msm"] == plan["r"]["msm"] == "pallas"
                      and plan["fold"] == "pallas",
                      f"{p['kind']}{list(shape)} is not Pallas on both "
                      f"sides: {plan}", failures)
        programs.append(row)
    mem = jax.devices()[0].memory_stats() or {}
    rec = {"phase": "programs", "distinct_programs": len(programs),
           "programs": programs,
           "compiles": snap["compile"]["count"],
           "compile_seconds_total": snap["compile"]["seconds_total"],
           "compile_by_kind": snap["compile"]["by_kind"],
           "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
           "bytes_limit": mem.get("bytes_limit")}
    return finish(rec, failures)


# -- main ---------------------------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--vals-175", type=int, default=175)
    ap.add_argument("--blocks-175", type=int, default=96)
    ap.add_argument("--headers-175", type=int, default=64)
    ap.add_argument("--vals-10k", type=int, default=10_000)
    ap.add_argument("--blocks-10k", type=int, default=1,
                    help="1 fits a cold run into 1200 s: one window, "
                         "one program; 8 adds the apply-time batch "
                         "and three more programs")
    return ap.parse_args(argv)


def run(args) -> None:
    from cometbft_tpu.blocksync.reactor import VERIFY_WINDOW
    from cometbft_tpu.ops import compile_hook

    t_run = time.perf_counter()
    emit({"phase": "compile_cache",
          "dir": compile_hook.ensure_compile_cache(),
          "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))})
    build_native()
    inst = install_instruments()
    # a full VERIFY_WINDOW of downloaded blocks verifies as its largest
    # power of two (blocksync/reactor.py quantises the window)
    full = 1 << (VERIFY_WINDOW.bit_length() - 1)

    def windows(n_blocks):
        w = min(full, 1 << (n_blocks.bit_length() - 1))
        return w, max(1, n_blocks // w)

    w175, n175 = windows(args.blocks_175)
    w10k, n10k = windows(args.blocks_10k)
    # compile threads / host-reference processes
    workers = max(1, (os.cpu_count() or 2) - 1)
    try:
        with ThreadPoolExecutor(1, thread_name_prefix="prewarm") as bg:
            warm = bg.submit(
                prewarm,
                expected_programs(args.vals_175, w175, n175, True, True)
                + expected_programs(args.vals_10k, w10k, n10k, False),
                workers)
            # growing the chains is host work: it overlaps the compiles
            c175 = build_chain("vals175", args.vals_175, args.blocks_175,
                               args.seed)
            c10k = build_chain("vals10k", args.vals_10k, args.blocks_10k,
                               args.seed + 1)
            warm.result()
        phase_blocksync(c175, inst, min_device_windows=min(2, n175))
        phase_reference(c175, workers)
        phase_tamper(c175, inst)
        # light windows as deep as a blocksync window: the same program
        phase_light(c175, inst, args.headers_175, window=full)
        phase_blocksync(c10k, inst, min_device_windows=1)
        phase_reference(c10k, workers)
        phase_programs(inst, pallas_from=4096)
        emit({"phase": "total",
              "wall_s": round(time.perf_counter() - t_run, 1)})
    finally:
        uninstall_instruments()


def main(argv=None) -> int:
    args = parse_args(argv)
    device = None
    try:
        device = check_device()
        sys.path.insert(0, REPO)
        run(args)
    except BaseException as e:                  # noqa: BLE001
        emit({"phase": "failure", "error": f"{type(e).__name__}: {e}"})
        print(json.dumps({"ok": False, "device": device}), flush=True)
        if not isinstance(e, Exception):
            raise
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
