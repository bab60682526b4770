"""Mode `light`: fresh light clients sync the fixture chain sequentially,
one at a time, through light.Client.verify_light_block_at_height (the
pipelined windows of _verify_sequential_pipelined at the client's
default pipeline depth).

A pass is one fresh Client with a fresh MemoryStore, from its
construction (which fetches and checks the trust root) to the target
height being verified and stored.  Before each pass the process-wide
verdict cache is reset and the A-table cache is cleared: a light client
is a process that has just started, and a chain whose set changes at
every height never shows it one A side twice.  The window runs passes
until --seconds have gone and ENDS AT THE FIRST PASS BOUNDARY after that
mark; the rate is all light blocks verified and stored in those passes
over the true elapsed time.  Closed loop: one client at a time.

Everything about a cell comes from its files.  The configuration says
what the chain is (fixture_light.build) and, where it states them, the
trusting period, the clock and the store's size; the traffic mix says
which provider serves the client (`memory`: light.provider
.MemoryProvider over light blocks read from the source's stores; `http`:
the source node's real JSON-RPC server behind HttpProvider), how many
times the primary is also a witness, the window
(`sequential_batch_size`; absent: the client's default), the trust
height and the profiler slice.  The target is the height below the
source's tip.
"""

from __future__ import annotations

import os
import threading
import time

from benchmark import (
    fixture_light, harness, programs, programs_light, reference,
    reference_light)


def _recording_store():
    """A MemoryStore that notes, at every save, how many verdicts the
    process had computed since the pass began (the verdict cache is new
    each pass and its insertion count is one attribute read): a header
    saved before its window's verdict shows as a save that saw fewer
    verdicts than its window and those before it hold signatures."""
    from cometbft_tpu.crypto import sigcache
    from cometbft_tpu.light.store import MemoryStore

    class RecordingStore(MemoryStore):
        def __init__(self):
            super().__init__()
            self.saves: list = []       # (height, verdicts computed)

        def save_light_block(self, lb) -> None:
            self.saves.append((lb.height, sigcache.cache().insertions))
            super().save_light_block(lb)

    return RecordingStore()


def _raw_header(h) -> dict:
    """A stored header as the plain fields the reference hashes."""
    return {"version_block": h.version.block, "version_app": h.version.app,
            "chain_id": h.chain_id, "height": h.height,
            "time_seconds": h.time.seconds, "time_nanos": h.time.nanos,
            "last_block_hash": h.last_block_id.hash,
            "last_parts_total": h.last_block_id.part_set_header.total,
            "last_parts_hash": h.last_block_id.part_set_header.hash,
            "last_commit_hash": h.last_commit_hash,
            "data_hash": h.data_hash,
            "validators_hash": h.validators_hash,
            "next_validators_hash": h.next_validators_hash,
            "consensus_hash": h.consensus_hash, "app_hash": h.app_hash,
            "last_results_hash": h.last_results_hash,
            "evidence_hash": h.evidence_hash,
            "proposer_address": h.proposer_address}


def _raw_commit(c) -> dict:
    return {"height": c.height, "round": c.round,
            "block_hash": c.block_id.hash,
            "parts_total": c.block_id.part_set_header.total,
            "parts_hash": c.block_id.part_set_header.hash,
            "sigs": [(s.block_id_flag, s.timestamp.seconds,
                      s.timestamp.nanos, s.signature)
                     for s in c.signatures]}


def _counter(metric, *labels) -> float:
    with metric._mtx:
        return metric._values.get(tuple(labels), 0.0)


class Session:
    def __init__(self, run, inst, cache_dir: str, log):
        self.run, self.inst, self.log = run, inst, log
        self.cache_dir = cache_dir
        self.cfg, self.traffic = run.config, run.traffic
        self.n_vals = int(self.cfg["validators"])
        self.signers = self.n_vals * 2 // 3 + 1     # equal powers
        self.chain = None
        self.provider = None
        self.passes: list = []
        self.sample = None
        self.flushes: list = []
        self.dispatchers: dict = {}

    def _setting(self, key: str, default=None):
        """The traffic mix's word, else the configuration's."""
        return self.traffic.get(key, self.cfg.get(key, default))

    # -- set-up -------------------------------------------------------------
    def window_headers(self) -> int:
        import inspect

        from cometbft_tpu.light.client import Client

        bs = self.traffic.get("sequential_batch_size")
        if bs is None:
            bs = inspect.signature(Client.__init__).parameters[
                "sequential_batch_size"].default
        return int(bs)

    def plan(self) -> list:
        """[(first height, last height)] of a pass's verify windows."""
        bs = self.window_headers()
        out, h = [], self.trust_height + 1
        while h <= self.target:
            end = min(h + bs - 1, self.target)
            out.append((h, end))
            h = end + 1
        return out

    def window_widths(self) -> dict:
        """{padded width N: (signatures, dispatches)} a pass's windows
        call for: one dispatch a window, of the signatures
        verify_commit_light counts in its headers."""
        from cometbft_tpu.ops import ed25519 as dev

        out: dict = {}
        for first, last in self.plan():
            n = self.signers * (last - first + 1)
            sigs, count = out.get(dev.pad_width(n), (0, 0))
            out[dev.pad_width(n)] = (sigs + n, count + 1)
        return out

    def setup(self) -> None:
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.light.provider import HttpProvider, MemoryProvider
        from cometbft_tpu.ops import ed25519 as dev

        # at once, before anything is built: the driver tries a new cell
        # on the parent, and the parent must fail soon and cleanly
        if not hasattr(ed.ATableCache, "clear"):
            raise harness.BenchmarkError(
                "mode light needs crypto/ed25519.ATableCache.clear(): a "
                "pass must begin with no A table and no sighting, as a "
                "client that has just started, and this program cannot "
                "clear them")
        run = self.run
        self.chain = chain = fixture_light.build(self.cfg, run.seed)
        self.trust_height = int(self._setting("trust_height", 1))
        # a source serves the commit of every height below its tip
        self.target = chain.n_blocks
        t0 = time.perf_counter()
        blocks = fixture_light.light_blocks(chain, self.target)
        run.setup["fixture_s"] = chain.grow_s + time.perf_counter() - t0
        progs = programs_light.expected_programs(
            {h: [v.pub_key.bytes() for v in
                 lb.validator_set.validators[:self.signers]]
             for h, lb in blocks.items()},
            [(self.trust_height, self.trust_height)] + self.plan())
        for kind, *dims in progs:
            if len(dims) == 2:
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
        chain_id = chain.genesis.chain_id
        kind = self.traffic["provider"]
        if kind == "memory":
            self.provider = MemoryProvider(chain_id, blocks)
        elif kind == "http":
            self.provider = HttpProvider(
                chain_id, f"http://{chain.src.start_rpc()}")
        else:
            raise harness.BenchmarkError(f"no provider {kind!r}")
        self.root_hash = chain.src.block_store.load_block_meta(
            self.trust_height).header.hash()
        t0 = time.perf_counter()
        warm = self.one_pass()
        run.setup["warmup_s"] = time.perf_counter() - t0
        learned = programs.stop_learning(self.dispatchers)
        run.setup["trace_lower_s"] += learned["trace_lower_s"]
        run.setup["backend_compile_s"] += learned["backend_compile_s"]
        if learned["learned"]:
            self.log({"phase": "programs", **learned})
        self.release(warm)
        self.log({"phase": "warmup", **{k: v for k, v in warm.items()
                                        if k not in ("hashes", "saves")}})
        if warm["stored"] < warm["headers"]:
            raise RuntimeError(
                f"the warm-up pass stored {warm['stored']} of "
                f"{warm['headers']} headers: {warm['error']}")

    # -- one pass -----------------------------------------------------------------
    def now(self):
        """The client's clock: `now_offset_s` after the genesis time
        where the cell states one, else the wall clock."""
        from cometbft_tpu.types.timestamp import Timestamp

        offset = self._setting("now_offset_s")
        if offset is None:
            return Timestamp.now()
        return self.chain.genesis.genesis_time.add_ns(
            int(offset) * 1_000_000_000)

    def one_pass(self) -> dict:
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.crypto import sigcache
        from cometbft_tpu.light.client import (
            DEFAULT_PRUNING_SIZE, SEQUENTIAL, Client, TrustOptions)

        # the source put every commit triple in the process-wide verdict
        # cache while it grew the chain, and so did the pass before:
        # without this the windows resolve path == "cache"
        sigcache.reset()
        ed._A_TABLE_CACHE.clear()
        store = _recording_store()
        error = None
        t0 = time.perf_counter()
        try:
            client = Client(
                self.chain.genesis.chain_id,
                TrustOptions(
                    period_ns=int(self._setting("trusting_period_s"))
                    * 1_000_000_000,
                    height=self.trust_height, hash=self.root_hash),
                self.provider,
                witnesses=[self.provider] * int(
                    self.traffic.get("witnesses", 0)),
                trusted_store=store, verification_mode=SEQUENTIAL,
                pruning_size=int(self._setting("pruning_size",
                                               DEFAULT_PRUNING_SIZE)),
                sequential_batch_size=self.window_headers(),
                now_fn=self.now)
            client.verify_light_block_at_height(self.target)
        except Exception as e:              # noqa: BLE001 - a failed pass
            error = f"{type(e).__name__}: {e}"[:300]
        return {"store": store, "headers": self.target - self.trust_height,
                "seconds": time.perf_counter() - t0, "error": error}

    def release(self, p: dict) -> None:
        """Keep of a finished pass only what the comparison reads; the
        last pass's store is the sample.  Outside the window."""
        store = p.pop("store")
        p["hashes"] = {}
        for h in range(self.trust_height + 1, self.target + 1):
            lb = store.light_block(h)
            if lb is not None:
                p["hashes"][h] = reference_light.header_hash(
                    _raw_header(lb.header))
        p["stored"] = len(p["hashes"])
        p["saves"] = store.saves
        self.sample = store

    # -- the window -----------------------------------------------------------------
    def profile_pass(self, profile) -> None:
        """One more pass like the window's under the profiler (Python
        tracing off), from profile_delay_seconds into it for
        profile_seconds or to the pass's end: every program here is the
        Pallas kernels, a few dozen device events a dispatch, so a whole
        pass fits a slice."""
        slice_s = float(self.traffic.get("profile_seconds", 1.0))
        delay_s = float(self.traffic.get("profile_delay_seconds", 0.0))
        box: dict = {}
        th = threading.Thread(
            target=lambda: box.update(p=self.one_pass()),
            name="profiled-pass", daemon=True)
        th.start()
        th.join(timeout=delay_s)
        profile.start()
        th.join(timeout=slice_s)
        profile.stop()
        th.join()
        self.release(box["p"])
        self.log({"phase": "profile_pass", "slice_s": slice_s,
                  "delay_s": delay_s,
                  "stop_s": round(time.perf_counter() - profile.t1, 2),
                  "pass": {k: v for k, v in box["p"].items()
                           if k not in ("hashes", "saves")}})

    def _a_table(self) -> dict:
        dm = self.inst.device_metrics
        return {name: _counter(getattr(dm, "a_table_cache_" + name))
                for name in ("hits", "misses", "first_sightings")}

    def window(self, seconds: float) -> None:
        from cometbft_tpu.libs import flightrec

        run, inst = self.run, self.inst
        dm = inst.device_metrics
        seq0 = inst.recorder.recorded
        disp0 = inst.dispatches()
        comp0 = inst.compiles()
        fb0 = inst.rlc_fallbacks()
        tab0 = self._a_table()
        sig0 = _counter(dm.signatures_verified, "rlc")
        miss0 = sum(d.misses for d in self.dispatchers.values())
        t0 = time.perf_counter()
        while True:
            self.passes.append(self.one_pass())
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        run.window_s = t1 - t0
        for p in self.passes:
            self.release(p)
        run.units = sum(p["stored"] for p in self.passes)
        run.spans = inst.tracer.totals(t0, t1)
        evs = inst.events_since(seq0)
        self.flushes = [e for e in evs
                        if e["kind"] == flightrec.EV_VERIFY_FLUSH
                        and e.get("subsystem") == "light"]
        disp1 = inst.dispatches()
        comp1 = inst.compiles()
        c = run.counters = {
            "dispatches": {f"{k[0]}{list(k[1])}": n - disp0.get(k, 0)
                           for k, n in disp1.items()
                           if n - disp0.get(k, 0)},
            "compile_s_in_window": comp1[1] - comp0[1],
            "dispatcher_misses": sum(
                d.misses for d in self.dispatchers.values()) - miss0,
            "rlc_fallbacks": inst.rlc_fallbacks() - fb0,
            "rlc_dispatches_by_width": {},
            "windows": {},
            "a_table": {k: v - tab0[k] for k, v in self._a_table().items()},
            "signatures_verified_rlc":
                _counter(dm.signatures_verified, "rlc") - sig0,
            "passes": len(self.passes),
            "pass_seconds": [round(p["seconds"], 4) for p in self.passes],
        }
        for k, n in c["dispatches"].items():
            if k.startswith("ed25519_rlc"):
                width = int(k.split("[")[1].rstrip("]").split(",")[-1])
                by = c["rlc_dispatches_by_width"]
                by[width] = by.get(width, 0) + n
        c["compiles_in_window"] = max(
            comp1[0] - comp0[0], c["dispatcher_misses"],
            1 if c["compile_s_in_window"] > 0 else 0)
        for e in self.flushes:
            c["windows"][e["path"]] = c["windows"].get(e["path"], 0) + 1
        for kind in (flightrec.EV_DEVICE_FALLBACK,
                     flightrec.EV_PIPELINE_DRAIN,
                     flightrec.EV_RLC_FALLBACK):
            c[kind] = sum(e["kind"] == kind for e in evs)
        self.log({"phase": "window", "seconds": round(run.window_s, 4),
                  "headers": run.units,
                  "signatures": sum(e["batch"] for e in self.flushes
                                    if e["path"] == "device"),
                  "counters": c,
                  "spans": {k: round(v["seconds"], 4)
                            for k, v in run.spans.items()}})

    def slice_work(self, t0: float, t1: float) -> dict:
        """What the device dispatches of the traced slice [t0, t1]
        carried, by the cell's own sizes: a width carries the mean of
        the signatures its windows hold (the root's width the root's),
        and the device time of each class of dispatch
        (work.match_dispatches)."""
        from benchmark import work
        from cometbft_tpu.ops import ed25519 as dev

        by_width = {w: sigs / count
                    for w, (sigs, count) in self.window_widths().items()}
        by_width.setdefault(dev.pad_width(self.signers), self.signers)
        calls = []
        for kind, d in self.dispatchers.items():
            for t, sig in d.calls:
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
    def off_device(self) -> tuple[int, int]:
        """(signatures, headers) of the window's passes that were not
        verified on the device path, by the larger of two accounts: the
        windows that resolved on another path, with fallbacks and
        drains; and the dispatches the passes call for (one a verify
        window, at its padded width) that were not made - a window
        verified on the host may leave no event behind, only a dispatch
        fewer.  The two see the same window, so they are not added."""
        from cometbft_tpu.libs import flightrec

        c = self.run.counters
        sigs = sum(e["batch"] for e in self.flushes
                   if e["path"] != "device")
        headers = -(-sigs // self.signers)
        for kind in (flightrec.EV_DEVICE_FALLBACK,
                     flightrec.EV_PIPELINE_DRAIN,
                     flightrec.EV_RLC_FALLBACK):
            headers += c[kind]
        headers += int(c["rlc_fallbacks"])
        if not any(e["path"] == "device" for e in self.flushes):
            headers = max(headers, self.run.units or 1)
        short, lack_sigs = {}, 0
        got = c["rlc_dispatches_by_width"]
        for width, (n_sigs, count) in self.window_widths().items():
            lack = max(0, count * len(self.passes) - got.get(width, 0))
            if lack:
                short[str(width)] = lack
                lack_sigs += lack * n_sigs // count
        c["dispatches_short"] = short
        return (max(sigs, lack_sigs),
                max(headers, lack_sigs // self.signers))

    def stored_before_verdict(self) -> int:
        """Headers, over every pass, saved when the process had computed
        fewer verdicts in that pass than the header's window and the
        windows before it hold signatures (counted from the save of the
        trust root, whose own commit the client checks first)."""
        need, total = {}, 0
        for first, last in self.plan():
            total += self.signers * (last - first + 1)
            for h in range(first, last + 1):
                need[h] = total
        early = 0
        for p in self.passes:
            base = next((seen for h, seen in p["saves"]
                         if h == self.trust_height), 0)
            early += sum(1 for h, seen in p["saves"]
                         if seen - base < need.get(h, 0))
        return early

    def check(self) -> tuple[dict, int, int]:
        from cometbft_tpu.crypto import sigcache
        chain = self.chain
        src = chain.src
        src_hashes = {h: src.block_store.load_block_meta(h).block_id.hash
                      for h in range(self.trust_height + 1,
                                     self.target + 1)}
        totals = {"headers_missing": 0, "header_hash_differs": 0}
        for p in self.passes:
            got = reference_light.check_stored(src_hashes, p["hashes"])
            for k in totals:
                totals[k] += got[k]
        # the sample: the last pass's store, the root and every header
        store = self.sample
        blocks = []
        for h in range(self.trust_height, self.target + 1):
            lb = store.light_block(h)
            if lb is not None:
                blocks.append({"header": _raw_header(lb.header),
                               "commit": _raw_commit(
                                   lb.signed_header.commit)})

        def system_verdict(pk, msg, sig):
            return sigcache.cache().lookup(sigcache.key(pk, msg, sig))

        period = int(self._setting("trusting_period_s")) * 10 ** 9
        now = self.now()
        got = reference_light.check_chain(
            chain.genesis.chain_id, blocks, chain.keys_at,
            lambda h: [chain.power] * chain.n_vals, period,
            now.seconds * 10 ** 9 + now.nanos, 10 * 10 ** 9,
            system_verdict)
        sigs_off, headers_off = self.off_device()
        attempted = sum(p["headers"] for p in self.passes)
        failed = totals["headers_missing"] \
            + totals["header_hash_differs"] + headers_off
        compared = {}
        for k, v in {**totals,
                     **{k: v for k, v in got.items()
                        if k not in ("sigs_checked", "headers_checked")},
                     "stored_before_verdict": self.stored_before_verdict(),
                     "sigs_off_device": sigs_off,
                     "headers_off_device": headers_off}.items():
            compared[k] = {"value": v, "limit": 0}
        self.log({"phase": "compared", "sigs_checked": got["sigs_checked"],
                  "headers_checked": got["headers_checked"],
                  "passes": len(self.passes),
                  "errors": [p["error"] for p in self.passes
                             if p["error"]]})
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
            server = getattr(self.chain.src, "rpc_server", None)
            if server is not None:
                server.stop()
        programs.uninstall()
