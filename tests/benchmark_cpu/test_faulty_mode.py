"""The benchmark's `faulty` mode, its forging fixture and its reference,
driven on the CPU at 7 validators, four peers and windows of four.

As in test_benchmark_harness.py the device lane is a host judge; here it
answers a window as the device path does - one verdict where every
signature verifies, an RLC fallback and per-signature verdicts where one
does not - and keeps the device path's counters.  What is pinned: a
whole run reads `correct` true when sound and false when the reject path
is broken underneath in each of the ways the configuration's guarantees
name; the mode refuses a program that lacks what it reads; the five
per-layer metrics of the reject read 0 in an honest cell and a number
here; reference_faulty imports nothing of the program.
"""

import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    fixture_faulty, harness, programs, programs_faulty, reference_faulty)

from cometbft_tpu.blocksync import pool as bpool  # noqa: E402
from cometbft_tpu.blocksync import reactor as breactor  # noqa: E402
from cometbft_tpu.crypto import batch as cb  # noqa: E402
from cometbft_tpu.crypto import dispatch  # noqa: E402
from cometbft_tpu.crypto import ed25519 as ed  # noqa: E402
from cometbft_tpu.libs import flightrec  # noqa: E402
from cometbft_tpu.libs import metrics as libmetrics  # noqa: E402
from cometbft_tpu.libs import trace as libtrace  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402

TINY = {"name": "tiny7", "validators": 7, "power": 10, "tx_bytes": 64,
        "txs_per_block": 3, "chain_blocks": 16, "chain_id": "tiny-chain",
        "peers": 4}
SIGNERS = 5
WINDOW = 4
WINDOWS = 4                 # of a pass
SEED = 2 ** 31 + 9
NEW_METRICS = ("rlc_rejects_per_block.sync", "persig_sigs_per_block.sync",
               "localize_ms_per_block.sync", "reject_ms_per_block.sync",
               "refetch_ms_per_block.sync")


def _judge(triples):
    verdicts = [ed.PubKey(bytes(pk)).verify_signature(m, s)
                for pk, m, s in triples]
    return all(verdicts) and bool(verdicts), verdicts


def host_judge_window(self, win, device=None):
    """Stands in for VerifyPipeline._device_dispatch, with the device
    path's accounting: a dispatch of the window's program and, where the
    batch holds a bad signature, the fallback, the localisation's spans
    and the per-signature program's count."""
    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    with compile_hook.dispatch_scope(
            "ed25519_rlc_cached", (8, dev.pad_width(len(win.items)))):
        pass
    ok, verdicts = _judge((dispatch._pk_bytes(pk), m, s)
                          for pk, m, s in win.items)
    if ok:
        cb._count_verified("rlc", len(verdicts))
        return ok, verdicts
    libmetrics.device_metrics().rlc_fallbacks.inc()
    flightrec.record(flightrec.EV_RLC_FALLBACK, batch=len(verdicts))
    with libtrace.span("verify", "localize", batch=len(verdicts)):
        with libtrace.span("verify", "host_pack", packer="python"):
            pass
        cb._count_verified("persig", len(verdicts))
    return ok, verdicts


def host_judge_batch(self):
    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    n = dev.pad_width(len(self._items))
    with compile_hook.dispatch_scope("ed25519_rlc", (n, n)):
        pass
    return _judge(self._items)


@pytest.fixture
def stub_device(monkeypatch):
    # a refetched window's new signatures (the pair's one and the five
    # of the pair that had been left out) are too few for a batch of
    # their own, as the product's 118 are under its 128
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD", 7)
    monkeypatch.setattr(cb, "DEVICE_THRESHOLD", 2)
    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        host_judge_window)
    monkeypatch.setattr(cb.TpuEd25519BatchVerifier, "_verify_items",
                        host_judge_batch)
    monkeypatch.setattr(programs, "expected_programs", lambda n, w: [])
    monkeypatch.setattr(programs_faulty, "buckets", lambda n, w: [])
    # windows of four, and requests two windows ahead as the product's
    # 64 are of its 32: a window's blocks are asked for while the
    # window two below it is being applied
    monkeypatch.setattr(breactor, "VERIFY_WINDOW", WINDOW)
    monkeypatch.setattr(bpool, "MAX_PENDING_REQUESTS", 2 * WINDOW)
    monkeypatch.setattr(bpool, "RETRY_JITTER", 0.004)


@pytest.fixture
def cells(tmp_path, monkeypatch):
    """A manifest in a temporary directory: the tiny configuration under
    the real `faulty` mix, its honest twin under the real `catchup` mix,
    and the real metrics (conftest.py gives the twin the lists that
    qa175.catchup has)."""
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    (tmp_path / "tiny7.json").write_text(json.dumps(TINY))
    manifest = dict(real)
    manifest["configs"] = [{"name": "tiny7", "source": "test",
                            "file": "tiny7.json", "reduced": [],
                            "why": "test"}]
    manifest["workloads"] = [
        {"name": "tiny7.faulty", "config": "tiny7", "traffic": "faulty",
         "chips": 1, "why": "test"},
        {"name": "tiny7.catchup", "config": "tiny7", "traffic": "catchup",
         "chips": 1, "why": "test"}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    load = harness.load_json

    def short(p):
        d = load(p)
        if os.path.dirname(p).endswith("traffic"):
            d["pass_timeout_s"] = 4
            d["warmup_timeout_s"] = 20
        return d

    monkeypatch.setattr(harness, "load_json", short)
    return str(path)


def _run(manifest, workload="tiny7.faulty", trace=False):
    rc, result = harness.run_cell(workload, SEED, 0.2, trace,
                                  time.perf_counter(), check_chip=False,
                                  manifest_path=manifest)
    assert rc == 0
    return result


def _past_warmup(n_warm, broken, sound):
    """`broken` from call n_warm + 1 on, `sound` before."""
    state = {"n": 0}

    def fn(*a, **kw):
        state["n"] += 1
        return (broken if state["n"] > n_warm else sound)(*a, **kw)

    return fn


COMPARED = {
    "blocks_missing", "blocks_hash_differs", "app_hash_differs",
    "sigs_ref_rejected", "sigs_not_verified", "sigs_verdict_differs",
    "commits_short", "commits_missing", "sigs_off_device",
    "blocks_off_device", "forged_ref_accepted", "forged_stored",
    "forged_not_rejected", "rejects_misnamed", "peers_dropped_wrongly",
    "forgers_kept", "suppliers_kept", "blocks_refetched_beyond_pair",
    "rejects_short", "rejects_beyond", "persig_sigs_short"}


# -- a sound run ------------------------------------------------------------------------

def test_a_sound_run_is_correct(stub_device, cells):
    result = _run(cells)
    assert {k: v for k, v in result["compared"].items()
            if v["value"]} == {}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 16 and result["attempted"] % 16 == 0
    assert set(result["metrics"]) == {"sync_blocks_per_s", "setup_s"}
    assert result["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert set(result["compared"]) == COMPARED
    assert all(v == {"value": 0, "limit": 0}
               for v in result["compared"].values())


def test_a_traced_run_reports_the_rejects_metrics(stub_device, cells,
                                                  monkeypatch):
    monkeypatch.setattr(harness.Profile, "start", lambda self: setattr(
        self, "t0", time.perf_counter()))
    monkeypatch.setattr(harness.Profile, "stop", lambda self: setattr(
        self, "t1", time.perf_counter()))
    monkeypatch.setattr(harness, "read_profile", lambda *a: None)
    result = _run(cells, trace=True)
    got = result["metrics"]
    assert result["correct"] is True
    for name in NEW_METRICS + (
            "dispatches_per_block.sync", "seam_pack_ms_per_block.sync",
            "fixture_s.setup", "warmup_s.setup", "programs_s.setup"):
        assert name in got, name
    assert got["compiles_in_window.sync"]["value"] == 0
    # one reject a window of four blocks, each judging one by one the
    # window's signatures less those of the pair the forged block's own
    # part-set hash puts out of the batch
    assert got["rlc_rejects_per_block.sync"]["value"] == 1 / WINDOW
    assert got["persig_sigs_per_block.sync"]["value"] == \
        SIGNERS * (WINDOW - 1) / WINDOW
    assert got["reject_ms_per_block.sync"]["value"] > \
        got["refetch_ms_per_block.sync"]["value"] > 0
    # a window a dispatch and its second verification, a remainder a
    # block but the first
    assert got["dispatches_per_block.sync"]["value"] == pytest.approx(
        (2 * WINDOWS + 15) / 16)
    # the metrics that read a span of qa175.catchup's list are not this
    # cell's to report
    for name in ("apply_ms_per_block.sync", "decode_ms_per_block.sync"):
        assert name not in got, name


def test_the_rejects_metrics_read_zero_in_an_honest_cell(
        stub_device, cells, monkeypatch):
    monkeypatch.setattr(harness.Profile, "start", lambda self: setattr(
        self, "t0", 0.0))
    monkeypatch.setattr(harness.Profile, "stop", lambda self: setattr(
        self, "t1", 0.0))
    monkeypatch.setattr(harness, "read_profile", lambda *a: None)
    result = _run(cells, workload="tiny7.catchup", trace=True)
    assert result["correct"] is True
    for name in NEW_METRICS:
        assert result["metrics"][name]["value"] == 0, name


def test_no_forgery_in_the_traffic_gives_a_pass_of_honest_peers(
        stub_device, cells, monkeypatch):
    load = harness.load_json

    def honest(p):
        d = load(p)
        if p.endswith(os.path.join("traffic", "faulty.json")):
            d["forged_signatures_per_verify_window"] = 0
        return d

    monkeypatch.setattr(harness, "load_json", honest)
    result = _run(cells)
    assert result["correct"] is True
    assert all(v["value"] == 0 for v in result["compared"].values())


def test_readers_return_nothing_where_rejects_left_no_trace():
    from benchmark import readers

    run = harness.Run("w", {}, {}, 1, 1.0, {})
    for name in NEW_METRICS:
        assert readers.read_metric(name, run) is None
    # rejects counted, and a program that opens no such span or keeps
    # no such count (the parent of the PR that brought them)
    run.units, run.counters = 32, {"rlc_fallbacks": 1.0}
    assert readers.read_metric("rlc_rejects_per_block.sync", run) == 1 / 32
    for name in NEW_METRICS[1:]:
        assert readers.read_metric(name, run) is None
    run.counters = {"rlc_fallbacks": 0.0}
    for name in NEW_METRICS:
        assert readers.read_metric(name, run) == 0


# -- the reject path broken underneath: `correct` comes out false -----------------------

def test_fault_a_verdict_that_lies_true(stub_device, cells, monkeypatch):
    # the forged signature is waved through: the block below the forged
    # one is stored with the forged commit as its seen commit
    def lying(self, win, device=None):
        host_judge_window(self, win, device)
        return True, [True] * len(win.items)

    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        _past_warmup(2 * WINDOWS, lying, host_judge_window))
    result = _run(cells)
    c = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    assert c["forged_stored"]["value"] >= 1
    assert c["forged_not_rejected"]["value"] >= 1
    assert c["forged_ref_accepted"]["value"] == 0


def test_fault_a_localisation_that_names_the_neighbour(stub_device, cells,
                                                       monkeypatch):
    # the per-signature verdicts come back shifted by one commit: the
    # reject names a height above the forged commit's (or, the false
    # verdict shifted out of the batch, the window's first)
    def shifted(self, win, device=None):
        ok, verdicts = host_judge_window(self, win, device)
        if not ok:
            verdicts = [True] * SIGNERS + verdicts[:-SIGNERS]
        return ok, verdicts

    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        _past_warmup(2 * WINDOWS, shifted,
                                     host_judge_window))
    result = _run(cells)
    c = result["compared"]
    assert result["correct"] is False
    assert c["rejects_misnamed"]["value"] >= 1
    assert c["forged_not_rejected"]["value"] >= 1


def test_fault_the_wrong_peer_dropped(stub_device, cells, monkeypatch):
    real = bpool.BlockPool.redo_request
    state = {"n": 0}

    def and_a_bystander(self, height):
        state["n"] += 1
        bad = real(self, height)
        if state["n"] > WINDOWS:            # past the warm-up pass
            with self._mtx:
                others = [p for p in self._peers if p not in bad]
            if others:
                self.remove_peer(others[0])
                bad = bad + [others[0]]
        return bad

    monkeypatch.setattr(bpool.BlockPool, "redo_request", and_a_bystander)
    result = _run(cells)
    c = result["compared"]
    assert result["correct"] is False
    assert c["peers_dropped_wrongly"]["value"] >= 1
    assert c["forged_stored"]["value"] == 0
    assert c["blocks_hash_differs"]["value"] == 0


def test_fault_only_the_forger_dropped(stub_device, cells, monkeypatch):
    # upstream stops both suppliers of a rejected pair; a pool that
    # spares whoever supplied the block below the forged one breaks
    # guarantee 3 although the forger is gone and the chain is right
    real = bpool.BlockPool.redo_request
    state = {"n": 0}

    def spare_the_lower(self, height):
        state["n"] += 1
        if state["n"] <= WINDOWS:           # the warm-up pass
            return real(self, height)
        with self._mtx:
            upper = self._requesters.get(height + 1)
            bad = [upper.peer_id] if upper and upper.peer_id else []
            live = [pid for pid in bad if pid in self._peers]
        for pid in bad:
            self.remove_peer(pid)
        for h in (height, height + 1):
            self._redo_request(h, bad[0] if bad else "")
        return live

    monkeypatch.setattr(bpool.BlockPool, "redo_request", spare_the_lower)
    result = _run(cells)
    c = result["compared"]
    assert result["correct"] is False
    assert c["suppliers_kept"]["value"] >= 1
    assert c["forgers_kept"]["value"] == 0
    assert c["peers_dropped_wrongly"]["value"] == 0
    assert c["forged_stored"]["value"] == 0
    assert c["blocks_hash_differs"]["value"] == 0


def test_fault_one_window_resolved_on_the_host(stub_device, cells,
                                               monkeypatch):
    real = dispatch.VerifyPipeline._stage

    def host(self, win):
        win.mode = "host"

    monkeypatch.setattr(dispatch.VerifyPipeline, "_stage",
                        _past_warmup(2 * WINDOWS + 1, host, real))
    result = _run(cells)
    c = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    assert c["blocks_off_device"]["value"] >= 1
    assert c["blocks_hash_differs"]["value"] == 0
    assert c["forged_stored"]["value"] == 0


def test_control_host_path_comes_out_not_correct(stub_device, cells,
                                                 monkeypatch):
    # benchmark/control.py's switch: every forgery still caught and the
    # right chain stored, and the chip did none of it
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD",
                        10 ** 9)
    monkeypatch.setattr(cb, "DEVICE_THRESHOLD", 10 ** 9)
    result = _run(cells)
    c = result["compared"]
    assert result["correct"] is False
    assert c["blocks_off_device"]["value"] > 0
    assert c["rejects_short"]["value"] > 0      # the device rejected none
    assert c["forged_stored"]["value"] == 0
    assert c["blocks_hash_differs"]["value"] == 0


def test_fault_a_stored_hash_altered(stub_device, cells, monkeypatch):
    from cometbft_tpu.store.blockstore import BlockStore

    real = BlockStore.save_block
    saved = {"n": 0}

    def altered(self, block, parts, seen_commit, **kw):
        saved["n"] += 1
        # past the source's 17 and the warm-up pass's 16, height 5
        if saved["n"] > 33 and block.header.height == 5:
            block.header.app_hash = b"\x09" * 8
        return real(self, block, parts, seen_commit, **kw)

    monkeypatch.setattr(BlockStore, "save_block", altered)
    result = _run(cells)
    c = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    assert c["blocks_hash_differs"]["value"] >= 1
    assert c["forged_stored"]["value"] == 0


def test_fault_a_forger_that_is_never_asked(stub_device, cells,
                                            monkeypatch):
    # the second window's forger serves true blocks past the warm-up
    # pass, and nobody forges in its place: the pass runs a reject short
    # of what the traffic states
    real = fixture_faulty.Peers._forge

    def skip_one(self, acct, window, height, peer, now, designated):
        if acct.number > 1 and window == 2:
            return self.store.load_block_bytes(height), []
        return real(self, acct, window, height, peer, now, designated)

    monkeypatch.setattr(fixture_faulty.Peers, "_forge", skip_one)
    result = _run(cells)
    c = result["compared"]
    assert result["correct"] is False
    assert c["rejects_short"]["value"] >= 1
    assert c["forged_stored"]["value"] == 0
    assert c["blocks_missing"]["value"] == 0


@pytest.mark.parametrize("gone", ["close", "windows_rejected", "seed"])
def test_mode_refuses_a_program_that_lacks_what_it_reads(
        stub_device, cells, monkeypatch, gone):
    # the parent of the PR that brought the mode: the run fails at once,
    # before any program is built, and prints no result
    if gone == "close":
        monkeypatch.delattr(libtrace, "close")
    elif gone == "windows_rejected":
        real = libmetrics.BlockSyncMetrics.__init__

        def init(self, reg):
            real(self, reg)
            del self.windows_rejected

        monkeypatch.setattr(libmetrics.BlockSyncMetrics, "__init__", init)
    else:
        monkeypatch.setattr(
            bpool.BlockPool, "__init__",
            lambda self, start_height, send_request, on_peer_error=None,
            peer_timeout=None, retry_jitter=None: None)
    built = []
    monkeypatch.setattr(programs, "ensure",
                        lambda *a, **kw: built.append(1))
    with pytest.raises(harness.BenchmarkError, match="mode faulty needs"):
        _run(cells)
    assert built == []


# -- the reference --------------------------------------------------------------------------

def test_reference_judges_a_pass_from_the_fixtures_account():
    served = [(1.0, "a", 4), (1.1, "b", 5), (2.0, "c", 4), (2.1, "d", 5),
              (0.5, "a", 3), (0.6, "c", 6)]
    base = {"forgeries": [{"number": 1, "peer": "b", "at": 1.1,
                           "block_height": 5, "commit_height": 4}],
            "served": served,
            "rejects": [{"start": 1.5, "end": 2.5, "height": 4}],
            "dialled": ["a", "b", "c", "d", "e"],
            "dropped": [(1.5, "b"), (1.5, "a")],
            "connected_at_end": ["c", "d", "e"]}
    assert set(reference_faulty.check_pass(base).values()) == {0}
    misnamed = {**base, "rejects": [{"start": 1.5, "end": 2.5,
                                     "height": 5}]}
    got = reference_faulty.check_pass(misnamed)
    assert got["rejects_misnamed"] == 1 and got["forged_not_rejected"] == 1
    kept = {**base, "connected_at_end": ["b", "c", "d", "e"]}
    assert reference_faulty.check_pass(kept)["forgers_kept"] == 1
    # the supplier of the block below spared, or dropped only later
    # (as somebody else's) - and gone before the reject is as good
    for drops, want in (([(1.5, "b")], 1), ([(1.5, "b"), (2.6, "a")], 1),
                        ([(0.9, "a"), (1.5, "b")], 0)):
        assert reference_faulty.check_pass(
            {**base, "dropped": drops})["suppliers_kept"] == want
    one_peer = {**base, "served": [(1.0, "b", 4)] + served[1:],
                "dropped": [(1.5, "b")],
                "connected_at_end": ["a", "c", "d", "e"]}
    assert set(reference_faulty.check_pass(one_peer).values()) == {0}
    bystander = {**base, "connected_at_end": ["d", "e"]}
    assert reference_faulty.check_pass(
        bystander)["peers_dropped_wrongly"] == 1
    again = {**base, "served": served + [(3.0, "d", 6)]}
    assert reference_faulty.check_pass(
        again)["blocks_refetched_beyond_pair"] == 1
    # counters: a reject short, one beyond, a refetch beyond the pairs,
    # a localisation that judged fewer signatures than the window holds
    c = {"rejects_wanted": 6, "rlc_fallbacks": 6.0, "windows_rejected": 6.0,
         "blocks_refetched": 12.0, "persig_signatures": 6 * 3744.0,
         "window_signatures": 3744}
    assert set(reference_faulty.check_counters(c).values()) == {0}
    assert reference_faulty.check_counters(
        {**c, "rlc_fallbacks": 5.0})["rejects_short"] == 1
    assert reference_faulty.check_counters(
        {**c, "rlc_fallbacks": 7.0,
         "persig_signatures": 7 * 3744.0})["rejects_beyond"] == 1
    assert reference_faulty.check_counters(
        {**c, "blocks_refetched": 13.0})["blocks_refetched_beyond_pair"] == 1
    assert reference_faulty.check_counters(
        {**c, "persig_signatures": 6 * 3744.0 - 1})["persig_sigs_short"] == 1


def test_reference_rejects_a_forged_signature_and_finds_it_stored():
    from benchmark import reference

    keys = [ed.PrivKey.generate(bytes([i + 1]) * 32) for i in range(4)]
    pubkeys = [k.pub_key().bytes() for k in keys]
    powers = [10] * 4
    order = reference.validator_order(pubkeys, powers)
    true = dict(height=7, round=0, block_hash=b"\x01" * 32, parts_total=1,
                parts_hash=b"\x02" * 32, seconds=1_700_000_007, nanos=0)
    slot = 2
    signer = keys[order[slot]]
    def sign_bytes(block_hash):
        return reference.vote_sign_bytes(
            "c", true["height"], true["round"], block_hash,
            true["parts_total"], true["parts_hash"], true["seconds"],
            true["nanos"])

    good = signer.sign(sign_bytes(true["block_hash"]))
    other = signer.sign(sign_bytes(b"\x03" * 32))
    f = {**true, "index": slot, "forged": other, "stored": [good, good]}
    assert reference_faulty.check_forgeries("c", pubkeys, powers, [f]) == {
        "forged_ref_accepted": 0, "forged_stored": 0}
    # a "forgery" that verifies is no forgery; one the stores hold is
    # a broken guarantee
    assert reference_faulty.check_forgeries(
        "c", pubkeys, powers,
        [{**f, "forged": good, "stored": []}])["forged_ref_accepted"] == 1
    assert reference_faulty.check_forgeries(
        "c", pubkeys, powers,
        [{**f, "stored": [other, good]}])["forged_stored"] == 1


def test_reference_faulty_imports_nothing_of_the_program():
    import ast

    tree = ast.parse(open(os.path.join(
        REPO, "benchmark", "reference_faulty.py")).read())
    mods = [n.module or "" for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)] + [
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names]
    assert not [m for m in mods if m.startswith("cometbft_tpu")]
    assert set(mods) <= {"__future__", "benchmark"}


# -- the manifest ------------------------------------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest():
    m = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    spec = harness.load_cell("qa175.faulty")
    assert spec["cell"] == {**spec["cell"], "config": "qa175byz",
                            "traffic": "faulty", "chips": 1}
    assert spec["traffic"]["mode"] == "faulty"
    assert spec["traffic"]["rate_metric"] == "sync_blocks_per_s"
    assert spec["traffic"]["forged_signatures_per_verify_window"] == 1
    cfg, honest = spec["config"], harness.load_cell("qa175.catchup")["config"]
    # every key of qa175 with the same value, but what names the
    # deployment and what a deployment with byzantine peers adds
    for k, v in honest.items():
        if k not in ("name", "source", "transport", "guarantees",
                     "assumed", "reduced"):
            assert cfg[k] == v, k
    assert set(honest["assumed"]) < set(cfg["assumed"])
    assert set(honest["reduced"]) == set(cfg["reduced"])
    assert cfg["peers"] == 10 and len(cfg["guarantees"]) == 4
    entry = next(c for c in m["configs"] if c["name"] == "qa175byz")
    assert entry["reduced"] == ["chain_blocks", "non_validator_nodes"]
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_METRICS:
        assert "workloads" not in per_layer[name]
        assert per_layer[name]["moves"] == "sync_blocks_per_s"
        assert per_layer[name]["better"] == "lower"
    # the last entries of their lists: nothing was put in the middle
    assert m["configs"][-1]["name"] == "qa175byz"
    assert m["workloads"][-1]["name"] == "qa175.faulty"
    assert [p["name"] for p in m["per_layer"][-5:]] == list(NEW_METRICS)
