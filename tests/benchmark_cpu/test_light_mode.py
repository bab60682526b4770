"""The benchmark's `light` mode, its fixture and its reference, driven on
the CPU at 12 and 7 validators.

As in test_benchmark_harness.py the device lane is a host judge.  What
is pinned here: that reference_light computes the hashes the program
computes and refuses what the light client refuses, on seeded churn
chains honest and broken; that a whole run of each light cell's mode
reads `correct` true when sound and false when the timed path is broken
underneath; that the build-ahead hint follows the A-table cache's own
rule; and that the manifest's light cells and metrics are all there.
"""

import copy
import dataclasses
import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    fixture_light, harness, programs_light, reference_light)

from cometbft_tpu.crypto import batch as cb  # noqa: E402
from cometbft_tpu.crypto import dispatch, sigcache  # noqa: E402
from cometbft_tpu.crypto import ed25519 as ed  # noqa: E402
from cometbft_tpu.light import client as lc  # noqa: E402
from cometbft_tpu.light.provider import MemoryProvider  # noqa: E402
from cometbft_tpu.light.store import MemoryStore  # noqa: E402
from cometbft_tpu.types import validation  # noqa: E402

CHURN = {"name": "churn12", "validators": 12, "power": 10,
         "key_type": "ed25519", "chain_blocks": 40,
         "valset_change_per_height": 1, "block_time_s": 60,
         "trust_height": 1, "trusting_period_s": 86400,
         "now_offset_s": 2400, "pruning_size": 1000,
         "chain_id": "churn-chain", "app_snapshot_interval": 0}
TINY = {"name": "tiny7", "validators": 7, "power": 10, "tx_bytes": 64,
        "txs_per_block": 3, "chain_blocks": 40, "chain_id": "tiny-chain"}
CELLS = {"churn12.sequence": (CHURN, "sequence"),
         "tiny7.light": (TINY, "light")}
WINDOW = 8
SEED = 2 ** 31 + 11


def _judge(triples):
    verdicts = [ed.PubKey(bytes(pk)).verify_signature(m, s)
                for pk, m, s in triples]
    return all(verdicts) and bool(verdicts), verdicts


def host_judge_window(self, win, device=None):
    """Stands in for VerifyPipeline._device_dispatch, with the device
    path's dispatch accounting and its signature counter."""
    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    with compile_hook.dispatch_scope(
            "ed25519_rlc", (8, dev.pad_width(len(win.items)))):
        pass
    ok, verdicts = _judge((dispatch._pk_bytes(pk), m, s)
                          for pk, m, s in win.items)
    if ok:
        cb._count_verified("rlc", len(verdicts))
    return ok, verdicts


def host_judge_batch(self):
    from cometbft_tpu.ops import compile_hook
    from cometbft_tpu.ops import ed25519 as dev

    n = dev.pad_width(len(self._items))
    with compile_hook.dispatch_scope("ed25519_rlc", (n, n)):
        pass
    cb._count_verified("rlc", len(self._items))
    return _judge(self._items)


@pytest.fixture
def stub_device(monkeypatch):
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD", 4)
    monkeypatch.setattr(cb, "DEVICE_THRESHOLD", 2)
    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        host_judge_window)
    monkeypatch.setattr(cb.TpuEd25519BatchVerifier, "_verify_items",
                        host_judge_batch)
    monkeypatch.setattr(programs_light, "expected_programs",
                        lambda signers, batches: [])


# -- the reference against the program, on a seeded churn chain ---------------------

@pytest.fixture(scope="module")
def churn():
    chain = fixture_light.build(CHURN, SEED)
    return chain, fixture_light.light_blocks(chain, 40)


def _light():
    return harness.load_module("modes", "light")


def _raw(mode, lb):
    return {"header": mode._raw_header(lb.header),
            "commit": mode._raw_commit(lb.signed_header.commit)}


def _now(chain):
    return chain.genesis.genesis_time.add_ns(2400 * 10 ** 9)


def _sync(chain, blocks, store=None):
    """A fresh client syncs 2..40 in windows of 8 from memory."""
    sigcache.reset()
    ed._A_TABLE_CACHE.clear()
    store = store if store is not None else MemoryStore()
    provider = MemoryProvider(chain.genesis.chain_id, blocks)
    client = lc.Client(
        chain.genesis.chain_id,
        lc.TrustOptions(86400 * 10 ** 9, 1, blocks[1].hash()), provider,
        witnesses=[provider], trusted_store=store,
        verification_mode=lc.SEQUENTIAL, sequential_batch_size=WINDOW,
        now_fn=lambda: _now(chain))
    client.verify_light_block_at_height(40)
    return store


def _reference(chain, raw_blocks):
    now = _now(chain)
    return reference_light.check_chain(
        chain.genesis.chain_id, raw_blocks, chain.keys_at,
        lambda h: [10] * 12, 86400 * 10 ** 9,
        now.seconds * 10 ** 9 + now.nanos, 10 ** 10,
        lambda pk, m, s: sigcache.cache().lookup(sigcache.key(pk, m, s)))


@pytest.mark.parametrize("height", [1, 2, 3, 17, 40])
def test_reference_hashes_equal_the_programs(churn, height):
    chain, blocks = churn
    mode = _light()
    lb = blocks[height]
    assert reference_light.header_hash(
        mode._raw_header(lb.header)) == lb.hash()
    assert reference_light.valset_hash(
        chain.keys_at(height), [10] * 12) == lb.header.validators_hash
    # the fixture's own account of the set agrees with the source's
    assert sorted(chain.keys_at(height)) == sorted(
        v.pub_key.bytes() for v in lb.validator_set.validators)


def test_churn_chain_changes_its_set_at_every_height(churn):
    chain, blocks = churn
    hashes = [blocks[h].header.validators_hash for h in range(1, 41)]
    assert hashes[0] == hashes[1]           # an update is in force at h + 2
    assert len(set(hashes[1:])) == 39
    for h in range(3, 41):
        gone = set(chain.keys_at(h - 1)) - set(chain.keys_at(h))
        new = set(chain.keys_at(h)) - set(chain.keys_at(h - 1))
        assert len(gone) == len(new) == 1
        assert gone == {chain.pubkeys[h - 3]}       # joined longest ago
    assert all(len(blocks[h].signed_header.commit.signatures) == 12
               for h in blocks)
    assert blocks[2].header.time.seconds \
        - blocks[1].header.time.seconds == 60


def test_merkle_root_is_rfc_6962():
    import hashlib

    def leaf(b):
        return hashlib.sha256(b"\x00" + b).digest()

    def node(a, b):
        return hashlib.sha256(b"\x01" + a + b).digest()

    a, b, c = b"a", b"bb", b"ccc"
    assert reference_light.merkle_root([]) == hashlib.sha256(b"").digest()
    assert reference_light.merkle_root([a]) == leaf(a)
    assert reference_light.merkle_root([a, b, c]) == node(
        node(leaf(a), leaf(b)), leaf(c))
    five = [bytes([i]) for i in range(5)]
    assert reference_light.merkle_root(five) == node(
        node(node(leaf(five[0]), leaf(five[1])),
             node(leaf(five[2]), leaf(five[3]))), leaf(five[4]))


def test_client_and_reference_agree_on_an_honest_chain(stub_device, churn):
    chain, blocks = churn
    mode = _light()
    store = _sync(chain, blocks)
    assert [store.light_block(h).hash() for h in range(1, 41)] == [
        blocks[h].hash() for h in range(1, 41)]
    got = _reference(chain, [_raw(mode, store.light_block(h))
                             for h in range(1, 41)])
    assert got.pop("headers_checked") == 39
    assert got.pop("sigs_checked") == 39 * 9        # 2/3 of 12, and one
    assert all(v == 0 for v in got.values()), got


def test_one_forged_signature_is_refused_by_both(stub_device, churn):
    chain, blocks = churn
    mode = _light()
    forged = dict(blocks)
    forged[20] = lb = copy.deepcopy(blocks[20])
    sigs = lb.signed_header.commit.signatures
    sigs[2] = dataclasses.replace(                  # inside the first 2/3
        sigs[2], signature=bytes([sigs[2].signature[0] ^ 1])
        + sigs[2].signature[1:])
    store = MemoryStore()
    with pytest.raises(validation.ErrInvalidSignature) as e:
        _sync(chain, forged, store)
    assert e.value.failed_ctx == 20
    # nothing of that window, nor of any other, was stored
    assert store.size() == 1 and store.latest_light_block().height == 1
    got = _reference(chain, [_raw(mode, forged[h]) for h in range(1, 41)])
    assert got["sigs_ref_rejected"] == 1
    assert got["sigs_verdict_differs"] == 0         # the system said false
    assert got["header_hash_wrong"] == got["link_broken"] == 0


def test_a_broken_next_validators_hash_is_refused_by_both(stub_device,
                                                          churn):
    chain, blocks = churn
    mode = _light()
    broken = dict(blocks)
    broken[11] = lb = copy.deepcopy(blocks[11])
    lb.signed_header.header.next_validators_hash = b"\x07" * 32
    store = MemoryStore()
    with pytest.raises(Exception) as e:
        _sync(chain, broken, store)
    assert "header" in str(e.value).lower()
    assert store.size() == 1
    got = _reference(chain, [_raw(mode, broken[h]) for h in range(1, 41)])
    assert got["link_broken"] == 1                  # 12 does not follow 11
    assert got["header_hash_wrong"] == 1            # 11's commit signs another


# -- the build-ahead hint ------------------------------------------------------------

def test_hint_follows_the_a_table_caches_rule(monkeypatch):
    from cometbft_tpu.ops import ed25519 as dev

    monkeypatch.setattr(ed.ATableCache, "MIN_K", 8)
    same = {h: [b"k%d" % i for i in range(9)] for h in range(1, 30)}
    k, root, full, tail = (dev.pad_width(10), dev.pad_width(9),
                           dev.pad_width(72), dev.pad_width(36))
    # one set all along: the root's commit is the A side's first
    # sighting, the first window builds the tables, the rest hit
    assert programs_light.expected_programs(
        same, [(1, 1), (2, 9), (10, 17), (18, 21)]) == list(dict.fromkeys([
            ("ed25519_rlc", k, root), ("ed25519_a_tables", k),
            ("ed25519_rlc_cached", k, full),
            ("ed25519_rlc_cached", k, tail)]))
    # a new key a height: no A side is seen twice, every batch is fused
    moving = {h: [b"k%d" % i for i in range(h, h + 9)]
              for h in range(1, 30)}
    got = programs_light.expected_programs(
        moving, [(1, 1), (2, 9), (10, 17), (18, 21)])
    assert {kind for kind, *_ in got} == {"ed25519_rlc"}
    assert got == list(dict.fromkeys([
        ("ed25519_rlc", k, root),
        ("ed25519_rlc", dev.pad_width(17), full),
        ("ed25519_rlc", dev.pad_width(13), tail)]))
    # below MIN_K the cache never builds
    monkeypatch.setattr(ed.ATableCache, "MIN_K", 10 ** 6)
    assert {kind for kind, *_ in programs_light.expected_programs(
        same, [(1, 1), (2, 9), (10, 17)])} == {"ed25519_rlc"}


# -- a whole run of each light cell's mode, with everything but the chip ------------

@pytest.fixture
def cells(tmp_path, monkeypatch):
    """A manifest in a temporary directory whose cells are the two small
    configurations under the real light traffic mixes (their windows
    shortened to 8 headers) and the real metrics."""
    real = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    manifest = dict(real)
    manifest["configs"], manifest["workloads"] = [], []
    for name, (cfg, traffic) in CELLS.items():
        (tmp_path / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        manifest["configs"].append(
            {"name": cfg["name"], "source": "test",
             "file": f"{cfg['name']}.json", "reduced": [], "why": "test"})
        manifest["workloads"].append(
            {"name": name, "config": cfg["name"], "traffic": traffic,
             "chips": 1, "why": "test"})
    light = {"lightbench100.sequence": "churn12.sequence",
             "qa175.light": "tiny7.light"}
    manifest["per_layer"] = [
        {**m, "workloads": [light.get(w, w) for w in m["workloads"]]}
        if "workloads" in m else m for m in real["per_layer"]]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    load = harness.load_json

    def short_windows(p):
        d = load(p)
        if os.path.dirname(p).endswith("traffic"):
            d["sequential_batch_size"] = WINDOW
        return d

    monkeypatch.setattr(harness, "load_json", short_windows)
    return str(path)


def _run(manifest, workload, trace=False):
    rc, result = harness.run_cell(workload, SEED, 0.2, trace,
                                  time.perf_counter(), check_chip=False,
                                  manifest_path=manifest)
    assert rc == 0
    return result


@pytest.mark.parametrize("workload", list(CELLS))
def test_a_sound_run_is_correct(stub_device, cells, workload):
    result = _run(cells, workload)
    headers = CELLS[workload][0]["chain_blocks"] - 1
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= headers
    assert result["attempted"] % headers == 0
    assert set(result["metrics"]) == {"sync_blocks_per_s", "setup_s"}
    assert result["metrics"]["sync_blocks_per_s"]["value"] > 0
    assert set(result["compared"]) >= {
        "headers_missing", "header_hash_differs", "set_hash_differs",
        "link_broken", "sigs_ref_rejected", "sigs_verdict_differs",
        "commits_short", "stored_before_verdict", "sigs_off_device"}
    assert all(v == {"value": 0, "limit": 0}
               for v in result["compared"].values())


NEW_METRICS = (
    "light_fetch_ms_per_block.sync", "light_collect_ms_per_block.sync",
    "light_validate_ms_per_block.sync",
    "light_host_pack_ms_per_block.sync",
    "light_verdict_wait_ms_per_block.sync",
    "light_store_ms_per_block.sync",
    "light_divergence_ms_per_block.sync", "sigs_per_dispatch.sync",
    "a_table_hit_ratio.sync")


@pytest.mark.parametrize("workload", list(CELLS))
def test_a_traced_run_reports_every_new_metric(stub_device, cells,
                                               monkeypatch, workload):
    monkeypatch.setattr(harness.Profile, "start", lambda self: setattr(
        self, "t0", 0.0))
    monkeypatch.setattr(harness.Profile, "stop", lambda self: setattr(
        self, "t1", 0.0))
    monkeypatch.setattr(harness, "read_profile", lambda *a: None)
    result = _run(cells, workload, trace=True)
    got = result["metrics"]
    for name in NEW_METRICS + ("dispatches_per_block.sync",
                               "fixture_s.setup", "warmup_s.setup",
                               "programs_s.setup", "import_s.setup"):
        assert name in got, name
    assert got["compiles_in_window.sync"]["value"] == 0
    assert got["a_table_hit_ratio.sync"]["value"] == 0     # host judges
    cfg = CELLS[workload][0]
    signers = cfg["validators"] * 2 // 3 + 1
    headers = cfg["chain_blocks"] - 1
    windows = -(-headers // WINDOW)
    # a window a dispatch and one for the trust root's own commit
    assert got["dispatches_per_block.sync"]["value"] == pytest.approx(
        (windows + 1) / headers)
    assert got["sigs_per_dispatch.sync"]["value"] == pytest.approx(
        signers * (headers + 1) / (windows + 1))
    # validate runs inside collect
    assert got["light_validate_ms_per_block.sync"]["value"] \
        < got["light_collect_ms_per_block.sync"]["value"]
    # no light line carries a metric it cannot read
    for name in ("apply_ms_per_block.sync", "decode_ms_per_block.sync",
                 "store_ms_per_block.sync", "host_pack_ms_per_block.sync"):
        assert name not in got, name
    assert result["correct"] is True


def _past_warmup(n_warm, broken, sound):
    """`broken` from call n_warm + 1 on, `sound` before."""
    state = {"n": 0}

    def fn(*a, **kw):
        state["n"] += 1
        return (broken if state["n"] > n_warm else sound)(*a, **kw)

    return fn


@pytest.mark.parametrize("workload", list(CELLS))
def test_fault_a_header_stored_before_its_verdict(stub_device, cells,
                                                  monkeypatch, workload):
    real = lc.Client._verify_sequential_pipelined

    def early(self, trusted, target, now):
        self.store.save_light_block(target)     # no verdict waited for
        return real(self, trusted, target, now)

    monkeypatch.setattr(lc.Client, "_verify_sequential_pipelined",
                        _past_warmup(1, early, real))
    result = _run(cells, workload)
    c = result["compared"]
    assert result["correct"] is False
    assert c["stored_before_verdict"]["value"] >= 1
    # what is stored is right all the same: only the order is broken
    assert c["header_hash_differs"]["value"] == 0
    assert c["sigs_off_device"]["value"] == 0


@pytest.mark.parametrize("workload", list(CELLS))
def test_fault_one_window_resolved_on_the_host(stub_device, cells,
                                               monkeypatch, workload):
    real = dispatch.VerifyPipeline._stage

    def host(self, win):
        win.mode = "host"

    windows = -(-(CELLS[workload][0]["chain_blocks"] - 1) // WINDOW)
    monkeypatch.setattr(dispatch.VerifyPipeline, "_stage",
                        _past_warmup(windows + 1, host, real))
    result = _run(cells, workload)
    c = result["compared"]
    signers = CELLS[workload][0]["validators"] * 2 // 3 + 1
    assert result["correct"] is False and result["failed"] > 0
    assert c["sigs_off_device"]["value"] >= signers * WINDOW
    assert c["headers_off_device"]["value"] >= WINDOW
    assert c["header_hash_differs"]["value"] == 0
    assert c["headers_missing"]["value"] == 0


@pytest.mark.parametrize("workload", list(CELLS))
def test_control_host_path_comes_out_not_correct(stub_device, cells,
                                                 monkeypatch, workload):
    # benchmark/control.py's switch: every signature verified, the right
    # chain stored, and the chip did none of it
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD",
                        10 ** 9)
    monkeypatch.setattr(cb, "DEVICE_THRESHOLD", 10 ** 9)
    result = _run(cells, workload)
    c = result["compared"]
    assert result["correct"] is False
    assert result["failed"] == result["attempted"]
    assert c["sigs_off_device"]["value"] > 0
    assert c["sigs_ref_rejected"]["value"] == 0
    assert c["headers_missing"]["value"] == 0


@pytest.mark.parametrize("workload", list(CELLS))
def test_fault_a_stored_hash_altered(stub_device, cells, monkeypatch,
                                     workload):
    real = MemoryStore.save_light_block
    saved = {"n": 0}
    headers = CELLS[workload][0]["chain_blocks"] - 1

    def altered(self, lb):
        saved["n"] += 1
        # past the warm-up pass (its root and its headers), height 5
        if saved["n"] > headers + 1 and lb.height == 5:
            lb = copy.deepcopy(lb)
            lb.signed_header.header.app_hash = b"\x09" * 8
        return real(self, lb)

    monkeypatch.setattr(MemoryStore, "save_light_block", altered)
    result = _run(cells, workload)
    c = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    assert c["header_hash_differs"]["value"] >= 1
    assert c["header_hash_wrong"]["value"] == 1     # the sample's height 5
    assert c["sigs_off_device"]["value"] == 0


@pytest.mark.parametrize("workload", list(CELLS))
def test_fault_an_answer_altered_where_it_is_produced(stub_device, cells,
                                                      monkeypatch,
                                                      workload):
    def lying(self, win, device=None):
        ok, verdicts = host_judge_window(self, win, device)
        return False, [False] + list(verdicts[1:])

    windows = -(-(CELLS[workload][0]["chain_blocks"] - 1) // WINDOW)
    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        _past_warmup(windows, lying, host_judge_window))
    result = _run(cells, workload)
    c = result["compared"]
    assert result["correct"] is False
    # the client raised at its first window and stored nothing
    assert c["headers_missing"]["value"] == result["attempted"]
    assert result["failed"] == result["attempted"]


@pytest.mark.parametrize("workload", list(CELLS))
def test_fault_dispatch_raises_and_the_host_drains(stub_device, cells,
                                                   monkeypatch, workload):
    def boom(self, win, device=None):
        raise RuntimeError("injected dispatch fault")

    windows = -(-(CELLS[workload][0]["chain_blocks"] - 1) // WINDOW)
    monkeypatch.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                        _past_warmup(windows, boom, host_judge_window))
    result = _run(cells, workload)
    c = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    assert c["sigs_off_device"]["value"] > 0
    assert c["header_hash_differs"]["value"] == 0


def test_mode_refuses_a_program_that_cannot_clear_its_tables(
        stub_device, cells, monkeypatch):
    # the parent of the PR that brought the mode: the run fails at once,
    # before any program is built, and prints no result
    monkeypatch.delattr(ed.ATableCache, "clear")
    with pytest.raises(harness.BenchmarkError, match="ATableCache.clear"):
        _run(cells, "churn12.sequence")


# -- the manifest's light cells ----------------------------------------------------------

def test_light_cells_and_their_metrics_are_in_the_manifest():
    m = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in m["workloads"]}
    assert cells["lightbench100.sequence"]["config"] == "lightbench100"
    assert cells["qa175.light"]["config"] == "qa175"
    light = {"lightbench100.sequence", "qa175.light"}
    for name in light:
        spec = harness.load_cell(name)
        assert spec["traffic"]["mode"] == "light"
        assert spec["traffic"]["rate_metric"] == "sync_blocks_per_s"
        assert spec["cell"]["chips"] == 1
    per_layer = {p["name"]: p for p in m["per_layer"]}
    for name in NEW_METRICS:
        assert set(per_layer[name]["workloads"]) == light
        assert per_layer[name]["moves"] == "sync_blocks_per_s"
    # a metric that reads a blocksync or state span lists the cell that
    # has one; none lists a light cell beside it
    for p in m["per_layer"]:
        if p["name"] not in NEW_METRICS and "workloads" in p:
            assert p["workloads"] == ["qa175.catchup"], p["name"]
    cfg = harness.load_cell("lightbench100.sequence")["config"]
    entry = next(c for c in m["configs"] if c["name"] == "lightbench100")
    assert entry["reduced"] == [] and cfg["reduced"] == {}
    assert (cfg["validators"], cfg["chain_blocks"],
            cfg["valset_change_per_height"]) == (100, 1000, 1)
    assert len(cfg["guarantees"]) == 4


def test_reference_light_imports_nothing_of_the_program():
    import ast

    for name in ("reference_light.py", "reference.py"):
        tree = ast.parse(open(os.path.join(REPO, "benchmark", name)).read())
        mods = [n.module or "" for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)] + [
            a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
            for a in n.names]
        assert not [m for m in mods if m.startswith("cometbft_tpu")]
