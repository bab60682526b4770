"""The per-layer metrics of the garbage collector's pauses, read off the
spans gc.young and gc.full that cometbft_tpu/libs/trace.py opens from
gc.callbacks while a tracer is installed: gc_ms_per_block.sync,
gc_full_ms_per_block.sync and gc_full_per_block.sync.  The readers on
hand-made runs, a traced tiny catch-up run with a full collection forced
inside its window, and the three entries of the manifest."""

import gc

import pytest

import manifests
import test_benchmark_harness as base
from benchmark import harness, readers
from test_benchmark_harness import stub_device, tiny  # noqa: F401

ALL = "gc_ms_per_block.sync"
FULL_MS = "gc_full_ms_per_block.sync"
FULL_N = "gc_full_per_block.sync"
NAMES = (ALL, FULL_MS, FULL_N)


def _run(units, **spans):
    """A Run whose window did `units` blocks, with spans {stage: (count,
    seconds)} of subsystem gc."""
    run = harness.Run("w", {}, {}, 1, 1.0, {})
    run.units = units
    run.spans = {f"gc.{k}": {"count": n, "seconds": s}
                 for k, (n, s) in spans.items()}
    run.spans["blocksync.apply"] = {"count": units, "seconds": 0.5}
    return run


@pytest.mark.parametrize("name", NAMES)
def test_no_gc_span_reads_nothing(name):
    # a program without the collector's hook
    assert readers.read_metric(name, _run(16)) is None


@pytest.mark.parametrize("name", NAMES)
def test_no_block_done_reads_nothing(name):
    assert readers.read_metric(name, _run(0, young=(30, 0.01))) is None


@pytest.mark.parametrize("name,want", [
    (ALL, 10.0 * 1000 / 16), (FULL_MS, 0.0), (FULL_N, 0.0)])
def test_young_spans_alone_read_no_full_collection(name, want):
    # the young spans prove the hook ran: the zero is a reading
    got = readers.read_metric(name, _run(16, young=(700, 10.0)))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    (ALL, (0.132 + 0.673) * 1000 / 192), (FULL_MS, 0.673 * 1000 / 192),
    (FULL_N, 5 / 192)])
def test_both_kinds_read_per_block(name, want):
    got = readers.read_metric(name, _run(192, young=(673, 0.132),
                                         full=(5, 0.673)))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    (ALL, 0.2 * 1000 / 4), (FULL_MS, 0.2 * 1000 / 4), (FULL_N, 0.5)])
def test_full_spans_alone_read_per_block(name, want):
    got = readers.read_metric(name, _run(4, full=(2, 0.2)))
    assert got == pytest.approx(want)


def _collect_once_in_the_window(mp):
    """A full collection at the start of the window's first pass: the
    window's mark is taken before it, and the pass's spans hand it to
    the tracer."""
    load = harness.load_module

    def loading(kind, name):
        mod = load(kind, name)
        if kind == "modes":
            cls = mod.Session
            window, one_pass = cls.window, cls.one_pass

            def in_window(self, seconds):
                self.collect_once = True
                return window(self, seconds)

            def passing(self, *args, **kwargs):
                if getattr(self, "collect_once", False):
                    self.collect_once = False
                    gc.collect()
                return one_pass(self, *args, **kwargs)

            cls.window, cls.one_pass = in_window, passing
        return mod

    mp.setattr(harness, "load_module", loading)


def test_a_traced_run_reports_all_three(stub_device, tiny,  # noqa: F811
                                        monkeypatch):
    monkeypatch.setattr(harness.Profile, "start", lambda self: setattr(
        self, "t0", 0.0))
    monkeypatch.setattr(harness.Profile, "stop", lambda self: setattr(
        self, "t1", 0.0))
    monkeypatch.setattr(harness, "read_profile", lambda *a: None)
    _collect_once_in_the_window(monkeypatch)
    result = base._run(tiny, trace=True)
    got = result["metrics"]
    assert result["correct"] is True
    for name in NAMES:
        assert name in got, sorted(got)
    assert got[FULL_N]["value"] > 0
    assert got[FULL_N]["unit"] == "collections"
    assert got[ALL]["value"] >= got[FULL_MS]["value"] > 0


@manifests.GROWN
def test_the_manifest_holds_each_once_in_every_cell(grow, tmp_path):
    m = harness.load_json(manifests.real_or_grown(grow, tmp_path))
    for name in NAMES:
        (entry,) = [e for e in m["per_layer"] if e["name"] == name]
        assert "workloads" not in entry         # every cell collects
        assert entry["layer"] == "interpreter"
        assert entry["source"] == "program_span"
        assert entry["moves"] == "sync_blocks_per_s"
        assert entry["better"] == "lower"
        cells = [w["name"] for w in m["workloads"]]
        assert all(entry in harness.cell_metrics(m, c, "per_layer")
                   for c in cells)
