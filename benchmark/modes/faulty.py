"""Mode `faulty`: fresh nodes catch up on the fixture chain from ten peers
of which some forge, one node at a time, through the real
BlocksyncReactor and BlockPool at their default pipeline depth.

A pass is one fresh syncing SimNode (stores, verdict cache and A-table
cache new) dialled to the configuration's `peers` peers, from its
construction to the block below the source's tip being stored and
applied.  The peers are fixture_faulty's: real SimNodes that serve the
source's chain, of which one a verify window, drawn from the seed,
forges one signature in the LastCommit of the first block it is asked
for whose lower neighbour lies in the same window
(`forged_signatures_per_verify_window` of the traffic mix: 0 gives a
pass of honest peers).  Every window of a pass therefore rejects once:
the RLC batch fails, the per-signature program judges the batch, the
reactor drops both suppliers of the pair the verdicts name, fetches the
two blocks again and verifies the window a second time.  A thread of the
mode keeps the node at its count of peers (the stand-in for PEX): a
dropped peer's slot is filled by a fresh connection within
milliseconds.  The window rule, the rate and the closed loop are mode
`catchup`'s, and so is everything this file does not override.

What decides `correct` is reference_faulty's and reference's: see
check().  The mode calls no verifier and runs no dispatch loop; it reads
the program through the substrate the program writes (libs/trace spans,
libs/metrics counters, the flight recorder).
"""

from __future__ import annotations

import os
import threading
import time

from benchmark import (
    fixture, fixture_faulty, harness, programs, programs_faulty,
    reference_faulty)

catchup = harness.load_module("modes", "catchup")

# spans whose fields the comparison reads (the benchmark's tracer keeps
# names and times only)
EPISODES = {("blocksync", "reject"), ("blocksync", "refetch"),
            ("verify", "localize"), ("verify", "persig_pack"),
            ("verify", "persig_dispatch"), ("verify", "persig_readback")}
REFILL_EVERY_S = 0.002


def _needs() -> None:
    """What this mode reads of the program that a tree before PR 31
    lacks.  Checked at once, before anything is built: the driver tries
    a new cell on the parent, and the parent must fail soon and cleanly
    (it would otherwise run the cell with nobody counting its rejects)."""
    import inspect

    from cometbft_tpu.blocksync.pool import BlockPool
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.libs import trace as libtrace

    missing = []
    if not hasattr(libtrace, "close"):
        missing.append("libs/trace.close() (the blocksync.reject and "
                       "blocksync.refetch spans)")
    if not hasattr(libmetrics.BlockSyncMetrics(libmetrics.Registry()),
                   "windows_rejected"):
        missing.append("BlockSyncMetrics.windows_rejected / peers_dropped "
                       "/ blocks_refetched")
    if "seed" not in inspect.signature(BlockPool.__init__).parameters:
        missing.append("BlockPool(seed=): a refetch's jitter from a "
                       "generator the pool owns")
    if missing:
        raise harness.BenchmarkError(
            "mode faulty needs of the program: " + "; ".join(missing))


class Session(catchup.Session):
    def __init__(self, run, inst, cache_dir: str, log):
        super().__init__(run, inst, cache_dir, log)
        self.peers = None
        self.persig = None
        self.episodes: list = []        # (name, start, end, fields)
        self.flushes: list = []

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        _needs()
        run = self.run
        wb = self.window_blocks()
        rec = programs.ensure(
            programs.expected_programs(self.n_vals, wb),
            programs.store_dir(self.cache_dir, run.device["kind"],
                               run.workload),
            workers=max(1, (os.cpu_count() or 2) - 1), log=self.log)
        self.dispatchers = rec.pop("dispatchers")
        per = programs_faulty.ensure(
            programs_faulty.buckets(self.n_vals, wb),
            os.path.join(programs.store_dir(
                self.cache_dir, run.device["kind"], run.workload),
                "persig"), log=self.log)
        self.persig = per["dispatcher"]
        if self.persig is not None:
            self.dispatchers[programs_faulty.KIND] = self.persig
        run.setup["programs_s"] = rec["load_s"] + rec["build_wall_s"] \
            + per["load_s"] + per["trace_lower_s"] \
            + per["backend_compile_s"]
        run.setup["trace_lower_s"] = rec["trace_lower_s"] \
            + per["trace_lower_s"]
        run.setup["backend_compile_s"] = rec["backend_compile_s"] \
            + per["backend_compile_s"]
        self._keep_episode_fields()
        self.chain = fixture.build_chain(self.cfg, run.seed)
        self.peers = fixture_faulty.Peers(self.chain, self.cfg,
                                          self.traffic, wb)
        run.setup["fixture_s"] = self.chain.grow_s + self.peers.build_s
        t0 = time.perf_counter()
        # a whole pass, every window of it rejecting: the per-signature
        # program has run before the window opens
        warm = self.one_pass(float(self.traffic.get(
            "warmup_timeout_s", self.traffic["pass_timeout_s"])))
        run.setup["warmup_s"] = time.perf_counter() - t0
        learned = programs.stop_learning(self.dispatchers)
        run.setup["trace_lower_s"] += learned["trace_lower_s"]
        run.setup["backend_compile_s"] += learned["backend_compile_s"]
        if learned["learned"]:
            self.log({"phase": "programs", **learned})
        self.release(warm)
        self.log({"phase": "warmup", **self._loggable(warm)})
        if warm["stored"] < warm["target"]:
            raise RuntimeError(f"the warm-up pass stalled at "
                               f"{warm['stored']}/{warm['target']}")

    def _keep_episode_fields(self) -> None:
        record = self.inst.tracer.record

        def recording(subsystem, stage, seconds, end=None, fields=None):
            if (subsystem, stage) in EPISODES:
                t1 = end if end is not None else time.perf_counter()
                self.episodes.append((f"{subsystem}.{stage}", t1 - seconds,
                                      t1, dict(fields or {})))
            record(subsystem, stage, seconds, end=end, fields=fields)

        self.inst.tracer.record = recording

    @staticmethod
    def _loggable(p: dict) -> dict:
        return {k: v for k, v in p.items()
                if k not in ("hashes", "account", "stored_sigs")}

    # -- one pass -----------------------------------------------------------------
    def one_pass(self, timeout: float | None = None) -> dict:
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.crypto import sigcache
        from cometbft_tpu.libs import metrics as libmetrics
        from cometbft_tpu.simnet import SimNode

        chain = self.chain
        target = chain.n_blocks
        sigcache.reset()
        ed._A_TABLE_CACHE.clear()
        self._n += 1
        account = self.peers.begin_pass(self._n)
        t0 = time.perf_counter()
        node = SimNode(f"sync{self._n}", chain.genesis, chain.net,
                       block_sync=True, seed=chain.seed & 0x7FFFFFFF,
                       app=fixture.make_app(self.cfg))
        meter = libmetrics.BlockSyncMetrics(libmetrics.Registry())
        node.blocksync_reactor.metrics = meter
        catchup._hold_windows_until_full(node.blocksync_reactor.pool,
                                         chain.src.height())
        pipe_stats: dict = {}
        done = threading.Event()
        refilled = [0]
        pex_errors: list = []

        def keep_peers():
            while not done.wait(REFILL_EVERY_S):
                try:
                    refilled[0] += self.peers.refill(node)
                except Exception as e:      # noqa: BLE001 - a dial failed
                    pex_errors.append(f"{type(e).__name__}: {e}"[:200])

        pex = threading.Thread(target=keep_peers, name="faulty-pex",
                               daemon=True)
        self.peers.watch_drops(node)
        node.start()
        try:
            self.peers.connect(node)
            pex.start()
            node.wait_for_height(target, timeout=timeout or float(
                self.traffic["pass_timeout_s"]))
            pipe = node.blocksync_reactor._pipeline
            if pipe is not None:
                pipe_stats = {"device_windows": pipe.device_windows,
                              "host_windows": pipe.host_windows,
                              "drained_windows": pipe.drained_windows,
                              "faults": pipe.faults}
        finally:
            done.set()
            if pex.is_alive():
                pex.join(timeout=10.0)
            self.peers.end_pass(node)
            node.stop()
            for t in threading.enumerate():
                if t.name == "blocksync-pool":
                    t.join(timeout=10.0)
        t1 = time.perf_counter()

        def count(metric, *labels):
            with metric._mtx:
                return metric._values.get(tuple(labels), 0.0)

        return {"node": node, "target": target, "seconds": t1 - t0,
                "t0": t0, "t1": t1, "pipeline": pipe_stats,
                "account": account, "refilled": refilled[0],
                "pex_errors": pex_errors[:5],
                "windows_rejected": count(meter.windows_rejected),
                "peers_dropped": count(meter.peers_dropped,
                                       "served_invalid_block"),
                "blocks_refetched": count(meter.blocks_refetched)}

    def release(self, p: dict) -> None:
        """Beside what catch-up keeps: what the node's stores hold at
        the place of every forged signature."""
        node = p["node"]
        stored = []
        for f in p["account"].forgeries:
            here = []
            for c in (node.block_store.load_block_commit(f.commit_height),
                      node.block_store.load_seen_commit(f.commit_height)):
                if c is not None:
                    here.append(c.signatures[f.index].signature)
            b = node.block_store.load_block(f.block_height)
            if b is not None and b.last_commit is not None:
                here.append(b.last_commit.signatures[f.index].signature)
            stored.append(here)
        p["stored_sigs"] = stored
        super().release(p)

    # -- the window -----------------------------------------------------------------
    def profile_pass(self, profile) -> None:
        """One more pass like the window's, a slice of it under the
        profiler with one whole reject inside.  The node asks for blocks
        64 ahead of what it has applied, so the fixture's account of
        what was asked for says how far the pass is: the profiler starts
        `profile_lead_blocks` before the first window is applied (the
        second window's dispatch, reject and second verification
        follow) and stops once that reject is over, no sooner than
        `profile_seconds` and no later than `profile_max_seconds` after
        it started."""
        from cometbft_tpu.blocksync.pool import MAX_PENDING_REQUESTS

        slice_s = float(self.traffic.get("profile_seconds", 0.5))
        max_s = float(self.traffic.get("profile_max_seconds", 2.0))
        lead = int(self.traffic.get("profile_lead_blocks", 4))
        asked = self.window_blocks() - lead + MAX_PENDING_REQUESTS
        number = self._n + 1
        box: dict = {}
        th = threading.Thread(
            target=lambda: box.update(p=self.one_pass()),
            name="profiled-pass", daemon=True)
        th.start()

        def wait_for(cond, until):
            while th.is_alive() and time.perf_counter() < until \
                    and not cond():
                time.sleep(0.002)

        def far_enough():
            acct = self.peers.account
            return acct.number == number and any(
                h >= asked for _, _, h in acct.served[-64:])

        wait_for(far_enough, time.perf_counter()
                 + float(self.traffic["pass_timeout_s"]))
        n_rejects = sum(1 for e in self.episodes
                        if e[0] == "blocksync.reject")
        profile.start()
        th.join(timeout=slice_s)
        wait_for(lambda: sum(1 for e in self.episodes
                             if e[0] == "blocksync.reject") > n_rejects,
                 profile.t0 + max_s)
        profile.stop()
        th.join()
        self.release(box["p"])
        self.log({"phase": "profile_pass",
                  "slice_s": round(profile.t1 - profile.t0, 3),
                  "lead_blocks": lead,
                  "stop_s": round(time.perf_counter() - profile.t1, 2),
                  "pass": self._loggable(box["p"])})

    def window(self, seconds: float) -> None:
        inst = self.inst
        dm = inst.device_metrics

        def verified(program):
            m = dm.signatures_verified
            with m._mtx:
                return m._values.get((program,), 0.0)

        v0 = {k: verified(k) for k in ("rlc", "persig")}
        n0 = len(self.passes)
        super().window(seconds)
        run = self.run
        passes = self.passes[n0:]
        t0, t1 = passes[0]["t0"], passes[-1]["t1"]
        c = run.counters
        c["signatures_verified_rlc"] = verified("rlc") - v0["rlc"]
        c["signatures_verified_persig"] = verified("persig") - v0["persig"]
        for k in ("windows_rejected", "peers_dropped", "blocks_refetched",
                  "refilled"):
            c[k] = sum(p[k] for p in passes)
        c["forged_handed_out"] = sum(len(p["account"].forgeries)
                                     for p in passes)
        eps = [e for e in self.episodes if t0 <= e[2] <= t1]
        c["episodes"] = {}
        for name, a, b, fields in eps:
            rec = c["episodes"].setdefault(
                name, {"count": 0, "seconds": 0.0})
            rec["count"] += 1
            rec["seconds"] += b - a
        loc = [e for e in eps if e[0] == "verify.localize"]
        self.log({"phase": "rejects", "counters": {
            k: c[k] for k in ("rlc_fallbacks", "windows_rejected",
                              "peers_dropped", "blocks_refetched",
                              "refilled", "forged_handed_out",
                              "signatures_verified_rlc",
                              "signatures_verified_persig")},
            "episodes_ms_each": {
                k: round(v["seconds"] * 1000.0 / v["count"], 3)
                for k, v in c["episodes"].items()},
            "localize": [{"ms": round((b - a) * 1000.0, 3), **f}
                         for _, a, b, f in loc[:12]],
            "rejects": [{"ms": round((b - a) * 1000.0, 3), **f}
                        for n, a, b, f in eps
                        if n == "blocksync.reject"][:12],
            "spans_ms_per_block": {
                k: round(v["seconds"] * 1000.0 / max(1, run.units), 4)
                for k, v in sorted(run.spans.items())}})

    # -- what decides `correct`, `attempted`, `failed` ------------------------------------
    def _pass_record(self, p: dict) -> dict:
        acct = p["account"]
        return {
            "forgeries": [{"number": f.number, "peer": f.peer, "at": f.at,
                           "block_height": f.block_height,
                           "commit_height": f.commit_height}
                          for f in acct.forgeries],
            "served": list(acct.served),
            "rejects": [{"start": a, "end": b, "height": f.get("height")}
                        for n, a, b, f in self.episodes
                        if n == "blocksync.reject"
                        and p["t0"] <= b <= p["t1"]],
            "dialled": list(acct.dialled),
            "dropped": list(acct.dropped),
            "connected_at_end": list(acct.connected_at_end)}

    def _forgery_records(self, p: dict) -> list:
        src = self.chain.src.block_store
        out = []
        for f, stored in zip(p["account"].forgeries, p["stored_sigs"]):
            c = src.load_block(f.block_height).last_commit
            cs = c.signatures[f.index]
            out.append({
                "height": c.height, "round": c.round,
                "block_hash": c.block_id.hash,
                "parts_total": c.block_id.part_set_header.total,
                "parts_hash": c.block_id.part_set_header.hash,
                "index": f.index, "seconds": cs.timestamp.seconds,
                "nanos": cs.timestamp.nanos, "forged": f.forged,
                "stored": stored})
        return out

    def check(self) -> tuple[dict, int, int]:
        """Catch-up's comparison over the stored chain, the sampled
        commits and the device path, and beside it what became of every
        forgery."""
        from cometbft_tpu.libs import flightrec

        chain, run = self.chain, self.run
        c = run.counters
        # an RLC fallback is this configuration's own traffic: it is
        # held to its count below, and is no departure from the device
        # path as catch-up's check would have it
        expected = {k: c[k] for k in ("rlc_fallbacks",
                                      flightrec.EV_RLC_FALLBACK)}
        c.update(dict.fromkeys(expected, 0))
        try:
            compared, attempted, failed = super().check()
        finally:
            c.update(expected)
        powers = [int(self.cfg["power"])] * self.n_vals
        counts = {"forged_ref_accepted": 0, "forged_stored": 0,
                  "forged_not_rejected": 0, "rejects_misnamed": 0,
                  "peers_dropped_wrongly": 0, "forgers_kept": 0,
                  "suppliers_kept": 0, "blocks_refetched_beyond_pair": 0}
        for p in self.passes:
            for got in (reference_faulty.check_forgeries(
                            chain.genesis.chain_id, chain.pubkeys, powers,
                            self._forgery_records(p)),
                        reference_faulty.check_pass(self._pass_record(p))):
                for k, v in got.items():
                    counts[k] += v
        for k, v in reference_faulty.check_counters({
                "rejects_wanted": self.peers.per_window and sum(
                    self.peers.windows for _ in self.passes),
                "rlc_fallbacks": c["rlc_fallbacks"],
                "windows_rejected": c["windows_rejected"],
                "blocks_refetched": c["blocks_refetched"],
                "persig_signatures": c["signatures_verified_persig"],
                # the forged block's own pair fails on its block id
                # and is left out of the batch the reject judges
                "window_signatures": self.signers
                * (self.window_blocks() - 1),
                }).items():
            counts[k] = counts.get(k, 0) + v
        failed += counts["forged_stored"] + counts["forged_not_rejected"]
        compared.update({k: {"value": v, "limit": 0}
                         for k, v in counts.items()})
        handed = [f for p in self.passes for f in p["account"].forgeries]
        self.log({"phase": "forgeries", "handed_out": len(handed),
                  "by_the_peer_drawn": sum(f.designated for f in handed)})
        return compared, attempted, min(failed, attempted)

    def close(self) -> None:
        """Stop what setup() started (the source was never started:
        nobody dials it here)."""
        if self.peers is not None:
            self.peers.close()
        programs_faulty.uninstall()
        programs.uninstall()
