"""Mode `catchup`: fresh nodes catch up on the fixture chain, one at a
time, through the real BlocksyncReactor at its default pipeline depth.

A pass is one fresh syncing SimNode dialled to the source, from its
construction to the block below the source's tip being stored and
applied.  Before each pass the process-wide verdict cache is reset; the
A-table cache is left as a long sync would have it.  The window runs
passes until --seconds have gone and ENDS AT THE FIRST PASS BOUNDARY
after that mark; the rate is all blocks of those passes over the true
elapsed time.  Closed loop: a catching-up node is its own only client.

phase_blocksync, _hold_windows_until_full and the honest-run checks are
copies of chip_smoke.py's (PR 22).
"""

from __future__ import annotations

import os
import threading
import time

from benchmark import fixture, programs, reference


def _hold_windows_until_full(pool, tip: int) -> None:
    """Blocks reach a syncing node's pool one by one and the reactor
    verifies whatever run of them has arrived when it looks, so the
    sizes of its verify windows follow the timing of the run - and each
    size is a device program of its own, minutes to trace.  To run the
    same programs every time, the reactor is shown a window only once it
    is as full as it will get: the full count and the block after, or
    every block up to the source's tip.  What the reactor then does with
    the window is untouched."""
    peek = pool.peek_window

    def peek_full(max_blocks, offset=0):
        window, after = peek(max_blocks, offset)
        if window and window[-1][0].header.height != tip and not (
                len(window) == max_blocks and after is not None):
            return [], None
        return window, after

    pool.peek_window = peek_full


class Session:
    def __init__(self, run, inst, cache_dir: str, log):
        self.run, self.inst, self.log = run, inst, log
        self.cache_dir = cache_dir
        self.cfg, self.traffic = run.config, run.traffic
        self.n_vals = int(self.cfg["validators"])
        self.signers = self.n_vals * 2 // 3 + 1
        self.chain = None
        self.passes: list = []
        self.sample = None
        self._n = 0

    # -- set-up -------------------------------------------------------------
    def window_blocks(self) -> int:
        """A full reactor window verifies as its largest power of two,
        and no deeper than the chain."""
        from cometbft_tpu.blocksync.reactor import VERIFY_WINDOW

        full = 1 << (VERIFY_WINDOW.bit_length() - 1)
        depth = int(self.cfg["chain_blocks"])
        return min(full, 1 << (depth.bit_length() - 1))

    def setup(self) -> None:
        run = self.run
        progs = programs.expected_programs(self.n_vals,
                                           self.window_blocks())
        for kind, *dims in progs:
            if len(dims) == 2:
                from cometbft_tpu.ops import ed25519 as dev

                self.log({"phase": "plan", "program": [kind, *dims],
                          "plan": dev.rlc_kernel_plan(*dims)})
        rec = programs.ensure(
            progs, programs.store_dir(self.cache_dir, run.device["kind"],
                                      run.workload),
            workers=max(1, (os.cpu_count() or 2) - 1), log=self.log)
        self.dispatchers = rec.pop("dispatchers")
        run.setup["programs_s"] = rec["load_s"] + rec["build_wall_s"]
        run.setup["trace_lower_s"] = rec["trace_lower_s"]
        run.setup["backend_compile_s"] = rec["backend_compile_s"]
        self.chain = fixture.build_chain(self.cfg, run.seed)
        run.setup["fixture_s"] = self.chain.grow_s
        self.chain.src.start()
        t0 = time.perf_counter()
        # a whole pass: a node stopped mid-chain leaves windows in flight
        # that would verify on into the measured window.  It may have to
        # learn a program the hint did not name, hence its own time-out
        warm = self.one_pass(float(self.traffic.get(
            "warmup_timeout_s", self.traffic["pass_timeout_s"])))
        run.setup["warmup_s"] = time.perf_counter() - t0
        learned = programs.stop_learning(self.dispatchers)
        run.setup["trace_lower_s"] += learned["trace_lower_s"]
        run.setup["backend_compile_s"] += learned["backend_compile_s"]
        if learned["learned"]:
            self.log({"phase": "programs", **learned})
        self.release(warm)
        self.log({"phase": "warmup", **{k: v for k, v in warm.items()
                                        if k != "hashes"}})
        if warm["stored"] < warm["target"]:
            raise RuntimeError(f"the warm-up pass stalled at "
                               f"{warm['stored']}/{warm['target']}")

    # -- one pass -----------------------------------------------------------------
    def one_pass(self, timeout: float | None = None) -> dict:
        from cometbft_tpu.crypto import sigcache
        from cometbft_tpu.simnet import SimNode

        chain = self.chain
        target = chain.n_blocks
        # the source node put every commit triple in the process-wide
        # verdict cache while it grew the chain, and so did the pass
        # before: without this the windows resolve path == "cache"
        sigcache.reset()
        self._n += 1
        t0 = time.perf_counter()
        node = SimNode(f"sync{self._n}", chain.genesis, chain.net,
                       block_sync=True, seed=chain.seed & 0x7FFFFFFF,
                       app=fixture.make_app(self.cfg))
        _hold_windows_until_full(node.blocksync_reactor.pool,
                                 chain.src.height())
        pipe_stats: dict = {}
        node.start()
        try:
            node.dial(chain.src)
            node.wait_for_height(target, timeout=timeout or float(
                self.traffic["pass_timeout_s"]))
            # read BEFORE stop(): on_stop drops the reactor's pipeline
            pipe = node.blocksync_reactor._pipeline
            if pipe is not None:
                pipe_stats = {"device_windows": pipe.device_windows,
                              "host_windows": pipe.host_windows,
                              "drained_windows": pipe.drained_windows,
                              "faults": pipe.faults}
        finally:
            node.stop()
            # stop() does not join the pool routine: a node still
            # applying would verify into the next pass's verdict cache
            for t in threading.enumerate():
                if t.name == "blocksync-pool":
                    t.join(timeout=10.0)
        return {"node": node, "target": target,
                "seconds": time.perf_counter() - t0, "pipeline": pipe_stats}

    def release(self, p: dict) -> None:
        """Keep of a finished pass only what the comparison reads.
        Outside the window: loading a 10,000-validator state is slow."""
        node = p.pop("node")
        st = node.state_store.load()
        p["stored"] = min(node.block_store.height(),
                          st.last_block_height if st else 0)
        top = min(p["stored"], p["target"])
        p["hashes"] = {h: node.block_store.load_block_meta(h).block_id.hash
                       for h in range(1, top + 1)}
        p["app_hash"] = st.app_hash if st else b""
        p["applied"] = st.last_block_height if st else 0
        self.sample = node              # the last pass is the sample

    # -- the window -----------------------------------------------------------------
    def profile_pass(self, profile) -> None:
        """One more pass like the window's, its first seconds under the
        profiler.  A slice is short: one dispatch of an XLA-path program
        is a quarter of a million device events, and the profiler takes
        a second to hand over thirty thousand."""
        slice_s = float(self.traffic.get("profile_seconds", 1.0))
        delay_s = float(self.traffic.get("profile_delay_seconds", 0.0))
        box: dict = {}
        th = threading.Thread(
            target=lambda: box.update(p=self.one_pass()),
            name="profiled-pass", daemon=True)
        th.start()
        th.join(timeout=delay_s)        # past the pass's own start-up
        profile.start()
        th.join(timeout=slice_s)
        profile.stop()
        th.join()
        self.release(box["p"])
        self.log({"phase": "profile_pass", "slice_s": slice_s,
                  "delay_s": delay_s,
                  "stop_s": round(time.perf_counter() - profile.t1, 2),
                  "pass": {k: v for k, v in box["p"].items()
                           if k != "hashes"}})

    def window(self, seconds: float) -> None:
        run, inst = self.run, self.inst
        seq0 = inst.recorder.recorded
        disp0 = inst.dispatches()
        comp0 = inst.compiles()
        fb0 = inst.rlc_fallbacks()
        miss0 = sum(d.misses for d in self.dispatchers.values())
        t0 = time.perf_counter()
        while True:
            p = self.one_pass()
            self.passes.append(p)
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        run.window_s = t1 - t0
        for p in self.passes:
            self.release(p)
        run.units = sum(min(p["stored"], p["target"]) for p in self.passes)
        run.spans = inst.tracer.totals(t0, t1)
        evs = inst.events_since(seq0)
        from cometbft_tpu.libs import flightrec

        flushes = [e for e in evs if e["kind"] == flightrec.EV_VERIFY_FLUSH
                   and e.get("subsystem") == "blocksync"]
        self.flushes = flushes
        disp1 = inst.dispatches()
        comp1 = inst.compiles()
        run.counters = {
            "dispatches": {f"{k[0]}{list(k[1])}": n - disp0.get(k, 0)
                           for k, n in disp1.items()
                           if n - disp0.get(k, 0)},
            "compile_s_in_window": comp1[1] - comp0[1],
            "dispatcher_misses": sum(
                d.misses for d in self.dispatchers.values()) - miss0,
            "rlc_fallbacks": inst.rlc_fallbacks() - fb0,
            "rlc_dispatches_by_width": {},
            "windows": {},
            "passes": len(self.passes),
            "pass_seconds": [round(p["seconds"], 4) for p in self.passes],
        }
        c = run.counters
        for k, n in c["dispatches"].items():
            if k.startswith("ed25519_rlc"):
                width = int(k.split("[")[1].rstrip("]").split(",")[-1])
                by = c["rlc_dispatches_by_width"]
                by[width] = by.get(width, 0) + n
        # backend compiles the ledger counted, and no fewer than the
        # shapes that were not built ahead or any compile phase it timed
        c["compiles_in_window"] = max(
            comp1[0] - comp0[0], c["dispatcher_misses"],
            1 if c["compile_s_in_window"] > 0 else 0)
        for e in flushes:
            w = run.counters["windows"]
            w[e["path"]] = w.get(e["path"], 0) + 1
        for kind in (flightrec.EV_DEVICE_FALLBACK,
                     flightrec.EV_PIPELINE_DRAIN,
                     flightrec.EV_RLC_FALLBACK):
            run.counters[kind] = sum(e["kind"] == kind for e in evs)
        # signatures the device verified in the window: the windows'
        # and, for every block but each pass's first, the remainder
        rest = self.n_vals - self.signers
        signatures = sum(e["batch"] for e in flushes
                         if e["path"] == "device") + rest * sum(
            max(0, min(p["stored"], p["target"]) - 1) for p in self.passes)
        self.log({"phase": "window", "seconds": round(run.window_s, 4),
                  "blocks": run.units, "signatures": signatures,
                  "counters": run.counters,
                  "spans": {k: round(v["seconds"], 4)
                            for k, v in run.spans.items()}})

    def slice_work(self, t0: float, t1: float) -> dict:
        """What the device dispatches of the traced slice [t0, t1]
        carried, by the cell's own sizes: a window's width carries the
        window's signatures, the remainder's width the remainder's; and
        the device time of each class of dispatch (work.match_dispatches
        sets the trace's program runs beside the host's calls)."""
        from benchmark import work
        from cometbft_tpu.ops import ed25519 as dev

        rest = self.n_vals - self.signers
        per_window = self.signers * self.window_blocks()
        by_width = {dev.pad_width(rest): rest,
                    dev.pad_width(per_window): per_window}
        calls = []
        for kind, d in self.dispatchers.items():
            for t, sig in d.calls:
                # N is the widest last dimension among the arguments
                calls.append((t, kind, max(
                    (shape[-1] for shape, _ in sig if shape), default=0)))
        n = sum(by_width.get(w, 0) for t, kind, w in calls
                if t0 <= t <= t1 and kind != "ed25519_a_tables")
        matched = work.match_dispatches(
            self.run.profile.get("programs") or [], calls)
        classes = {} if matched is None else {
            w: {**matched.get(w, {"seconds": 0.0, "count": 0}),
                "sigs": sigs} for w, sigs in by_width.items()}
        c = self.chain.src.block_store.load_block_commit(1)
        sb = reference.vote_sign_bytes(
            self.chain.genesis.chain_id, c.height, c.round,
            c.block_id.hash, c.block_id.part_set_header.total,
            c.block_id.part_set_header.hash,
            c.signatures[0].timestamp.seconds,
            c.signatures[0].timestamp.nanos)
        self.log({"phase": "slice", "signatures": n, "classes": classes})
        return {"signatures": n, "sign_bytes_len": len(sb),
                "classes": classes}

    # -- what decides `correct`, `attempted`, `failed` ------------------------------------
    def dispatches_short(self) -> dict:
        """{padded width N: dispatches, blocks and signatures short} of
        the window's ed25519_rlc* dispatches against what its passes
        call for: a pass that stored s blocks verified them in windows
        of window_blocks() and then of the largest power of two that is
        left (the reactor's own quantising), each one dispatch of the
        signers' signatures, and checked the remainder of every
        LastCommit but the first block's (which has none) in one
        dispatch a block.  A batch verified on the host leaves no event
        behind, only a dispatch fewer; padding hides nothing here."""
        from cometbft_tpu.ops import ed25519 as dev

        wb = self.window_blocks()
        rest = self.n_vals - self.signers
        want: dict = {}

        def call_for(n_sigs, blocks, count):
            if n_sigs and count:
                w = want.setdefault(dev.pad_width(n_sigs),
                                    {"n": 0, "sigs": n_sigs,
                                     "blocks": blocks})
                w["n"] += count
        for p in self.passes:
            stored = left = min(p["stored"], p["target"])
            while left:
                w = min(wb, 1 << (left.bit_length() - 1))
                call_for(self.signers * w, w, 1)
                left -= w
            call_for(rest, 1, max(0, stored - 1))
        got = self.run.counters["rlc_dispatches_by_width"]
        out = {}
        for width, w in want.items():
            lack = max(0, w["n"] - got.get(width, 0))
            if lack:
                out[width] = {"dispatches": lack,
                              "blocks": lack * w["blocks"],
                              "sigs": lack * w["sigs"]}
        return out

    def check(self) -> tuple[dict, int, int]:
        from cometbft_tpu.crypto import sigcache
        from cometbft_tpu.libs import flightrec
        chain, run = self.chain, self.run
        src = chain.src
        src_hashes = {h: src.block_store.load_block_meta(h).block_id.hash
                      for h in range(1, chain.n_blocks + 1)}
        totals = {"blocks_missing": 0, "blocks_hash_differs": 0,
                  "app_hash_differs": 0}
        for p in self.passes:
            want = {"hashes": {h: src_hashes[h]
                               for h in range(1, p["target"] + 1)},
                    "app_hash": src.block_store.load_block(
                        p["applied"] + 1).header.app_hash}
            got = reference.check_stored(want, p)
            for k in totals:
                totals[k] += got[k]
        # the sample: every commit the last pass's node stored
        node = self.sample
        top = min(self.passes[-1]["stored"], self.passes[-1]["target"])
        commits = []
        for h in range(1, top + 1):
            c = node.block_store.load_block_commit(h) \
                or node.block_store.load_seen_commit(h)
            if c is None:
                continue
            commits.append({
                "height": c.height, "round": c.round,
                "block_hash": c.block_id.hash,
                "parts_total": c.block_id.part_set_header.total,
                "parts_hash": c.block_id.part_set_header.hash,
                "sigs": [(s.block_id_flag, s.timestamp.seconds,
                          s.timestamp.nanos, s.signature)
                         for s in c.signatures]})
        powers = [int(self.cfg["power"])] * self.n_vals

        def system_verdict(pk, msg, sig):
            return sigcache.cache().lookup(sigcache.key(pk, msg, sig))

        sigs = reference.check_commits(
            chain.genesis.chain_id, chain.pubkeys, powers, commits,
            system_verdict, full_below=top)
        sigs["commits_missing"] = top - len(commits)
        # a run that quietly left the chip fails: every window resolves
        # on the device path, and the window's ed25519_rlc* dispatches
        # number what its passes call for
        off = 0
        for e in self.flushes:
            if e["path"] != "device":
                off += max(1, -(-e["batch"] // self.signers))
        for kind in (flightrec.EV_DEVICE_FALLBACK,
                     flightrec.EV_PIPELINE_DRAIN,
                     flightrec.EV_RLC_FALLBACK):
            off += run.counters[kind]
        off += int(run.counters["rlc_fallbacks"])
        if not any(e["path"] == "device" for e in self.flushes):
            off = max(off, run.units or 1)
        short = self.dispatches_short()
        run.counters["dispatches_short"] = {
            str(n): v["dispatches"] for n, v in short.items()}
        # a block's window and its remainder are two dispatches of one
        # block: the widths' blocks are not added up
        off = max([off] + [v["blocks"] for v in short.values()])
        sigs["sigs_off_device"] = sum(v["sigs"] for v in short.values())
        attempted = sum(p["target"] for p in self.passes)
        failed = totals["blocks_missing"] + totals["blocks_hash_differs"] \
            + off
        compared = {}
        for k, v in {**totals, **{k: v for k, v in sigs.items()
                                  if k != "sigs_checked"},
                     "blocks_off_device": off}.items():
            compared[k] = {"value": v, "limit": 0}
        self.log({"phase": "compared", "sigs_checked": sigs["sigs_checked"],
                  "commits": len(commits), "passes": len(self.passes)})
        return compared, attempted, min(failed, attempted)

    def end_to_end(self, name: str):
        run = self.run
        if name == "setup_s":
            return run.setup["total_s"]
        if name == self.traffic["rate_metric"]:
            return run.units / run.window_s
        return None

    def close(self) -> None:
        """Stop what setup() started."""
        if self.chain is not None:
            self.chain.src.stop()
        programs.uninstall()
