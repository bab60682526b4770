"""The per-layer metrics that read the program's state.*, verify.* and
blocksync.partset spans, from a traced tiny run on the CPU.

Two runs of the tiny cell of test_benchmark_harness.py.  In the first
the program's own funnel runs whole - parse_and_hash, pack_rlc,
rlc_verify_async, the readback, the dispatch accounting of ops/ed25519 -
and only the three jitted functions at its bottom are stood in for (an
XLA-CPU compile of an RLC program takes minutes a shape): every new
metric is reported, and the count of verify.dispatch spans equals the
program account's RLC dispatches.  In the second the host judges of
test_benchmark_harness.py stand where the funnel starts, so no verify.*
span is passed: those metrics are left out of the line, never read as 0.
"""

import json
import os

import numpy as np
import pytest

import test_benchmark_harness as base
from benchmark import harness, programs, readers

from cometbft_tpu.crypto import batch as cb
from cometbft_tpu.crypto import dispatch
from cometbft_tpu.ops import compile_hook
from cometbft_tpu.ops import ed25519 as dev
from cometbft_tpu.types import validation

INSIDE_APPLY = ("validate_ms_per_block.sync",
                "abci_finalize_ms_per_block.sync",
                "state_update_ms_per_block.sync",
                "abci_commit_ms_per_block.sync",
                "state_save_ms_per_block.sync",
                "state_events_ms_per_block.sync")
# read off spans the funnel opens (crypto/batch.py, crypto/ed25519.py)
FUNNEL = ("seam_pack_ms_per_block.sync",
          "dispatch_enqueue_ms_per_block.sync",
          "readback_wait_ms_per_block.sync",
          "rlc_dispatch_spans_per_block.sync")
NEW = INSIDE_APPLY + FUNNEL + ("apply_self_ms_per_block.sync",
                               "partset_ms_per_block.sync",
                               "device_wait_ms_per_block.sync")


def _no_profiler(monkeypatch):
    # the CPU has no device plane to trace (as base's traced test)
    monkeypatch.setattr(harness.Profile, "start",
                        lambda self: setattr(self, "t0", 0.0))
    monkeypatch.setattr(harness.Profile, "stop",
                        lambda self: setattr(self, "t1", 0.0))
    monkeypatch.setattr(harness, "read_profile", lambda *a: None)


def _traced(manifest):
    """One traced tiny run: (result, the window's Run as the readers
    saw it)."""
    seen = {}
    read = readers.read_metric

    def keep(name, run):
        seen["run"] = run
        return read(name, run)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(readers, "read_metric", keep)
        result = base._run(manifest, trace=True)
    return result, seen["run"]


@pytest.fixture(scope="module")
def tmp_manifest(tmp_path_factory):
    """The real manifest's metrics over base's tiny configuration, as
    base's `tiny` fixture builds it."""
    d = tmp_path_factory.mktemp("tiny")
    manifest = harness.load_json(os.path.join(base.REPO, "BENCHMARK.json"))
    (d / "tiny7.json").write_text(json.dumps(base.TINY))
    manifest["configs"] = [{"name": "tiny7", "source": "test",
                            "file": "tiny7.json", "reduced": [],
                            "why": "test"}]
    manifest["workloads"] = [{"name": "tiny7.catchup", "config": "tiny7",
                              "traffic": "catchup", "chips": 1,
                              "why": "test"}]
    (d / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(d / "BENCHMARK.json")


def _thresholds(mp):
    # low enough that 7 validators reach the device lane, and no device
    # program to build ahead
    mp.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD", 4)
    mp.setattr(cb, "DEVICE_THRESHOLD", 2)
    mp.setattr(programs, "expected_programs", lambda n, w: [])


@pytest.fixture(scope="module")
def funnel_run(tmp_manifest):
    """The program's own funnel down to the jitted functions, which
    accept (the chain is honest; the reference checks every signature
    itself)."""
    with pytest.MonkeyPatch.context() as mp:
        _no_profiler(mp)
        _thresholds(mp)
        # the executable store stands aside: a stand-in cannot be lowered
        mp.setattr(compile_hook, "KEEPS_EXECUTABLES", True, raising=False)
        accept = lambda *args: np.bool_(True)       # noqa: E731
        mp.setattr(dev, "_rlc_jitted", accept)
        mp.setattr(dev, "_rlc_cached_jitted", accept)
        mp.setattr(dev, "_a_tables_jitted", lambda a: (
            np.zeros((17, 4, 20, a.shape[-1]), np.int32), np.bool_(True)))
        return _traced(tmp_manifest)


@pytest.fixture(scope="module")
def judged_run(tmp_manifest):
    """base's host judges, where the funnel would start."""
    with pytest.MonkeyPatch.context() as mp:
        _no_profiler(mp)
        _thresholds(mp)
        mp.setattr(dispatch.VerifyPipeline, "_device_dispatch",
                   base.host_judge_window)
        mp.setattr(cb.TpuEd25519BatchVerifier, "_verify_items",
                   base.host_judge_batch)
        return _traced(tmp_manifest)


@pytest.mark.parametrize("name", NEW)
def test_funnel_run_reports_every_new_metric(funnel_run, name):
    result, _ = funnel_run
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert name in got, sorted(got)
    assert got[name]["value"] > 0 or name == "apply_self_ms_per_block.sync"


def test_dispatch_spans_number_the_program_accounts_rlc_dispatches(
        funnel_run):
    result, run = funnel_run
    got = result["metrics"]
    by_kind = run.counters["dispatches"]
    rlc = sum(n for k, n in by_kind.items() if k.startswith("ed25519_rlc"))
    tables = sum(n for k, n in by_kind.items()
                 if k.startswith("ed25519_a_tables"))
    # a pass: its verify windows, and a remainder a block but the first
    assert rlc >= run.units
    assert got["rlc_dispatch_spans_per_block.sync"]["value"] == \
        pytest.approx(rlc / run.units, abs=1e-12)
    assert got["dispatches_per_block.sync"]["value"] == pytest.approx(
        (rlc + tables) / run.units, abs=1e-12)
    # every dispatch was read back through the same funnel
    assert run.spans["verify.readback"]["count"] == rlc


def test_what_is_named_inside_apply_adds_up(funnel_run):
    result, _ = funnel_run
    got = {k: v["value"] for k, v in result["metrics"].items()}
    named = sum(got[name] for name in INSIDE_APPLY)
    assert got["apply_self_ms_per_block.sync"] == pytest.approx(
        got["apply_ms_per_block.sync"] - named, abs=1e-9)
    assert 0 <= got["apply_self_ms_per_block.sync"] \
        < got["apply_ms_per_block.sync"]
    # the remainder's packing and its readback lie inside validate
    assert got["validate_ms_per_block.sync"] > 0


@pytest.mark.parametrize("name", FUNNEL)
def test_a_span_not_passed_leaves_its_metric_out(judged_run, name):
    # the host judges replace TpuEd25519BatchVerifier._verify_items and
    # VerifyPipeline._device_dispatch, above crypto/batch._device_verify:
    # no batch reaches the funnel, so there is nothing to read, and
    # nothing is what the line says
    result, run = judged_run
    assert result["correct"] is True
    assert name not in result["metrics"]
    assert not any(k.startswith("verify.") for k in run.spans)


@pytest.mark.parametrize("name", INSIDE_APPLY + (
    "apply_self_ms_per_block.sync", "partset_ms_per_block.sync",
    "device_wait_ms_per_block.sync"))
def test_spans_above_the_funnel_are_read_all_the_same(judged_run, name):
    assert name in judged_run[0]["metrics"]


def test_a_program_without_the_spans_reads_as_nothing():
    # the parent of this change: blocksync.apply and nothing inside it
    run = harness.Run("w", {}, {}, 1, 1.0, {}, units=10, spans={
        "blocksync.apply": {"count": 20, "seconds": 0.5},
        "blocksync.device_wait": {"count": 2, "seconds": 0.1}})
    for name in NEW:
        want = 10.0 if name == "device_wait_ms_per_block.sync" else None
        assert readers.read_metric(name, run) == want
