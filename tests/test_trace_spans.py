"""The spans inside `apply` (state.*) and round every RLC dispatch
(verify.*), the signatures-verified counter beside them, and the
parent / self-time bookkeeping of libs/trace.

(a) one block through BlockExecutor on the kvstore app, (b) one batch
through the synchronous batch seam on the CPU backend with the real
kernels (the 8 x 8 shape tests/test_ed25519.py compiles too), (c) the
same calls with nothing installed, (d) self_seconds on a hand-built
nest, (e) the garbage collector's pauses as gc.* spans.
"""

import gc
import threading

import pytest

from cometbft_tpu.crypto import batch as cb
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import trace as libtrace
from tests.test_execution import Harness

# how often _apply_block opens each stage: the crash-safety order puts
# one save and one listener on each side of the app's commit
OPENED = {"validate": 1, "abci_finalize": 1, "save": 2, "update": 1,
          "abci_commit": 1, "events": 2}


class _Installed:
    """A StageTracer and a DeviceMetrics behind the process-wide seams
    for the length of a `with`, the previous ones put back after."""

    def __enter__(self):
        self.tracer = libtrace.StageTracer()
        self.dm = libmetrics.DeviceMetrics(libmetrics.Registry())
        self._prev = (libtrace.tracer(), libmetrics.device_metrics())
        libtrace.set_tracer(self.tracer)
        libmetrics.set_device_metrics(self.dm)
        return self

    def __exit__(self, *exc):
        libtrace.set_tracer(self._prev[0])
        libmetrics.set_device_metrics(self._prev[1])
        return False

    def counted(self) -> dict:
        """The counters this file reads, as plain numbers."""
        sv, fb = self.dm.signatures_verified, self.dm.rlc_fallbacks
        with sv._mtx:
            out = {k[0]: v for k, v in sv._values.items()}
        with fb._mtx:
            out["rlc_fallbacks"] = fb._values.get((), 0.0)
        return out


def _one_block(h):
    """The reactor's two calls for the block after the tip, under the
    reactor's own span."""
    block = h.make_next_block([b"k=v"])
    bid, _ = h.commit_block(block)
    with libtrace.span("blocksync", "apply"):
        h.exec.validate_block(h.state, block)
        h.state = h.exec.apply_verified_block(h.state, bid, block)
    return block


# -- (a) the inside of apply ---------------------------------------------

@pytest.fixture(scope="module")
def applied():
    h = Harness()
    h.advance([b"a=1"])         # height 1 has no LastCommit to check
    with _Installed() as inst:
        block = _one_block(h)
    return inst.tracer, block.header.height


def test_apply_stages_are_the_ones_the_block_passes():
    assert set(OPENED) == set(libtrace.APPLY_STAGES)


@pytest.mark.parametrize("stage", libtrace.APPLY_STAGES)
def test_state_span_once_a_step_with_height_and_parent(applied, stage):
    tracer, height = applied
    got = tracer.intervals("state", stage)
    assert len(got) == OPENED[stage]
    for iv in got:
        assert iv["height"] == height
        assert iv["parent"] == "blocksync.apply"
        assert iv["end"] >= iv["start"]


def test_state_spans_lie_inside_apply_and_sum_to_within_it(applied):
    tracer, _ = applied
    (outer,) = tracer.intervals("blocksync", "apply")
    inner = tracer.intervals("state")
    assert len(inner) == sum(OPENED.values())
    for a, b in zip(inner, inner[1:]):
        assert a["end"] <= b["start"]           # one thread, in turn
    assert outer["start"] <= inner[0]["start"]
    assert inner[-1]["end"] <= outer["end"]
    covered = sum(iv["end"] - iv["start"] for iv in inner)
    whole = outer["end"] - outer["start"]
    assert 0 < covered <= whole
    # a collection of the garbage collector's between two state spans
    # is a child of apply's too
    paused = sum(iv["end"] - iv["start"] for iv in tracer.intervals("gc")
                 if iv.get("parent") == "blocksync.apply")
    assert tracer.self_seconds("blocksync", "apply") == pytest.approx(
        whole - covered - paused, abs=1e-9)


def test_a_block_validated_twice_opens_validate_once():
    h = Harness()
    h.advance()
    block = h.make_next_block()
    with _Installed() as inst:
        h.exec.validate_block(h.state, block)
        h.exec.validate_block(h.state, block)
    assert len(inst.tracer.intervals("state", "validate")) == 1


# -- (b) one batch through the synchronous seam ----------------------------

def _batch(n, tag):
    privs = [ed.PrivKey.generate(bytes([tag, i + 1]) * 16)
             for i in range(n)]
    msgs = [b"seam vote %d of %d" % (i, tag) for i in range(n)]
    return [(p.pub_key(), m, p.sign(m)) for p, m in zip(privs, msgs)]


def _through_the_seam(items):
    bv = cb.create_batch_verifier("ed25519", provider="tpu")
    assert isinstance(bv, cb.TpuEd25519BatchVerifier)
    for pk, m, s in items:
        bv.add(pk, m, s)
    return bv.verify()


@pytest.fixture(scope="module")
def seam():
    """A sound batch of five, then the same five with one signature
    altered: (tracer, counters, verdict) of each."""
    good = _batch(5, 0x51)
    pk, m, s = good[3]
    bad = good[:3] + [(pk, m, s[:7] + bytes([s[7] ^ 0x20]) + s[8:])] \
        + good[4:]
    out = {}
    for name, items in (("good", good), ("bad", bad)):
        with _Installed() as inst:
            verdict = _through_the_seam(items)
        out[name] = (inst.tracer, inst.counted(), verdict)
    return out


def test_seam_spans_in_order_and_apart(seam):
    tracer, _, (ok, verdicts) = seam["good"]
    assert ok and verdicts == [True] * 5
    got = tracer.intervals("verify")
    # pack_rlc (which hashes for itself), the dispatch, the wait for
    # its verdict
    assert [iv["stage"] for iv in got] == [
        "host_pack", "dispatch", "readback"]
    assert set(libtrace.VERIFY_STAGES) == {iv["stage"] for iv in got}
    for a, b in zip(got, got[1:]):
        assert a["end"] <= b["start"]


def test_localisation_spans_lie_inside_localize_in_order(seam):
    tracer, _, (ok, verdicts) = seam["bad"]
    assert not ok and verdicts == [True] * 3 + [False, True]
    got = {iv["stage"]: iv for iv in tracer.intervals("verify")
           if iv["stage"] in libtrace.LOCALIZE_STAGES}
    assert set(got) == set(libtrace.LOCALIZE_STAGES)
    loc = got["localize"]
    assert (loc["batch"], loc["bad"]) == (5, 1)
    # the Python hash (host_pack, packer=python), then the pack, the
    # per-signature program's enqueue and the wait for its verdicts
    rehash = tracer.intervals("verify", "host_pack")[-1]
    inner = [rehash] + [got[s] for s in libtrace.LOCALIZE_STAGES[1:]]
    assert loc["start"] <= inner[0]["start"]
    for a, b in zip(inner, inner[1:]):
        assert a["end"] <= b["start"]
    assert inner[-1]["end"] <= loc["end"]
    assert got["persig_dispatch"]["bucket"] == loc["bucket"]


@pytest.mark.parametrize("which", ["good", "bad"])
def test_host_pack_span_names_its_packer(seam, which):
    from cometbft_tpu.crypto import rlcpack

    # one span for the pack; the reject's parse_and_hash opens a second
    # of the same name, so the packing metrics see its Python hashing
    packed, *at_reject = seam[which][0].intervals("verify", "host_pack")
    assert packed["batch"] == 5
    assert packed["packer"] == (
        "native" if rlcpack.enabled() else "python")
    assert [(iv["batch"], iv["packer"]) for iv in at_reject] == (
        [(5, "python")] if which == "bad" else [])


@pytest.mark.parametrize("field,want", [
    ("k", 8), ("n", 8), ("cached", False),
    # which kernels the program at (k, n) is: off the chip the XLA path
    ("kernel", "xla")])
def test_dispatch_span_carries_the_padded_widths(seam, field, want):
    (iv,) = seam["good"][0].intervals("verify", "dispatch")
    assert iv[field] == want
    assert "parent" not in iv                   # nothing encloses it here


@pytest.mark.parametrize("which,want", [
    ("good", {"rlc": 5.0, "rlc_fallbacks": 0.0}),
    ("bad", {"persig": 5.0, "rlc_fallbacks": 1.0})])
def test_signatures_verified_counts_by_program(seam, which, want):
    assert seam[which][1] == want


def test_a_rejected_batch_is_localised_by_the_other_program(seam):
    tracer, _, (ok, verdicts) = seam["bad"]
    assert not ok and verdicts == [True] * 3 + [False, True]
    # the RLC dispatch was made and read back before the fallback
    assert len(tracer.intervals("verify", "dispatch")) == 1
    assert len(tracer.intervals("verify", "readback")) == 1


def test_counter_is_exposed_under_its_name():
    dm = libmetrics.DeviceMetrics(libmetrics.Registry(namespace="cometbft"))
    dm.signatures_verified.labels("rlc").add(3)
    assert 'cometbft_device_signatures_verified_total{program="rlc"} 3' \
        in dm.signatures_verified.collect()


# -- (c) nothing installed -------------------------------------------------

@pytest.fixture
def bare():
    prev = (libtrace.tracer(), libmetrics.device_metrics())
    libtrace.set_tracer(None)
    libmetrics.set_device_metrics(None)
    yield
    libtrace.set_tracer(prev[0])
    libmetrics.set_device_metrics(prev[1])


@pytest.mark.parametrize("name", [
    ("state", "validate"), ("state", "abci_commit"),
    ("blocksync", "partset"), ("verify", "host_pack"),
    ("verify", "dispatch"), ("verify", "readback")])
def test_no_tracer_gives_the_shared_null_span(bare, name):
    sp = libtrace.span(*name, height=3)
    assert sp is libtrace._NULL_SPAN
    with sp as inner:
        inner.note(cached=True)                 # accepted, kept nowhere
    assert not hasattr(sp, "__dict__")


def test_same_calls_record_nothing_when_nothing_is_installed(
        bare, seam, monkeypatch):
    del seam                                    # kernels compiled by now
    made = []
    init = libtrace._TimedSpan.__init__
    monkeypatch.setattr(
        libtrace._TimedSpan, "__init__",
        lambda self, *a, **kw: (made.append(a), init(self, *a, **kw))[1])
    h = Harness()
    h.advance()
    _one_block(h)
    assert _through_the_seam(_batch(5, 0x52)) == (True, [True] * 5)
    assert libtrace.tracer() is None
    assert libmetrics.device_metrics() is None
    assert made == []                           # no timed span was built
    assert getattr(libtrace._open, "stack", []) == []


# -- (d) self time on a hand-built nest ------------------------------------

def test_self_seconds_is_a_span_less_its_children():
    tr = libtrace.StageTracer()
    # outer [0, 1.0] holds mid [0.1, 0.7], which holds leaf [0.2, 0.4];
    # a second outer [2.0, 2.5] holds nothing
    tr.record("t", "leaf", 0.2, end=0.4, fields={"parent": "t.mid"})
    tr.record("t", "mid", 0.6, end=0.7, fields={"parent": "t.outer"})
    tr.record("t", "outer", 1.0, end=1.0)
    tr.record("t", "outer", 0.5, end=2.5)
    assert tr.self_seconds("t", "leaf") == pytest.approx(0.2)
    assert tr.self_seconds("t", "mid") == pytest.approx(0.4)
    assert tr.self_seconds("t", "outer") == pytest.approx(0.9)
    assert tr.self_seconds("t", "absent") == 0.0
    tr.reset()
    assert tr.self_seconds("t", "outer") == 0.0


def test_nested_spans_name_their_parent_thread_by_thread():
    import threading

    tr = libtrace.StageTracer()
    prev = libtrace.tracer()
    libtrace.set_tracer(tr)
    try:
        with libtrace.span("t", "outer"):
            with libtrace.span("t", "mid", depth=1):
                with libtrace.span("t", "leaf"):
                    pass
            # another thread's span is no child of this thread's
            th = threading.Thread(target=lambda: libtrace.span(
                "t", "elsewhere").__enter__().__exit__())
            th.start()
            th.join()
    finally:
        libtrace.set_tracer(prev)
    by = {iv["stage"]: iv for iv in tr.intervals("t")}
    assert by["leaf"]["parent"] == "t.mid"
    assert by["mid"]["parent"] == "t.outer" and by["mid"]["depth"] == 1
    assert "parent" not in by["outer"] and "parent" not in by["elsewhere"]
    assert tr.self_seconds("t", "outer") <= \
        by["outer"]["end"] - by["outer"]["start"]


def test_ring_is_bounded_per_stage_not_over_all(monkeypatch):
    # a flood of one stage leaves another stage's intervals where they
    # were: the overlap proof reads device and collect after a run
    monkeypatch.setattr(libtrace, "MAX_INTERVALS", 4)
    tr = libtrace.StageTracer()
    tr.record("blocksync", "device", 0.5, end=1.0)
    for i in range(50):
        tr.record("state", "save", 0.001, end=2.0 + i)
    tr.record("blocksync", "collect", 0.4, end=1.2)
    assert len(tr.intervals("state", "save")) == 4
    assert tr.dropped_intervals == 46
    assert tr.overlap_seconds("blocksync", "device",
                              "collect") == pytest.approx(0.2)
    assert [iv["end"] for iv in tr.intervals()] == sorted(
        iv["end"] for iv in tr.intervals())


# -- (e) the collector's pauses ------------------------------------------

@pytest.fixture
def no_hook():
    """gc.callbacks, the installed tracer and automatic collection as
    they were, after the test; automatic collection off during it, so
    the collections seen are the ones the test forces."""
    callbacks, prev, was = list(gc.callbacks), libtrace.tracer(), \
        gc.isenabled()
    gc.disable()
    libtrace.set_tracer(None)
    libtrace._gc_done.clear()
    yield
    libtrace.set_tracer(prev)
    gc.callbacks[:] = callbacks
    if was:
        gc.enable()


@pytest.fixture
def hooked(no_hook):
    """A StageTracer over TraceMetrics behind the seam: (tracer,
    registry)."""
    reg = libmetrics.Registry()
    tr = libtrace.StageTracer(libmetrics.TraceMetrics(reg))
    libtrace.set_tracer(tr)
    return tr, reg


def test_gc_stages_name_the_two_kinds():
    assert libtrace.GC_STAGES == ("young", "full")


def test_a_full_collection_in_a_span_is_its_child(hooked):
    tr, _ = hooked
    with libtrace.span("t", "outer"):
        gc.collect()
    (full,) = tr.intervals("gc", "full")
    (outer,) = tr.intervals("t", "outer")
    assert tr.intervals("gc", "young") == []
    assert full["parent"] == "t.outer" and full["generation"] == 2
    assert full["uncollectable"] >= 0 and full["collected"] >= 0
    assert outer["start"] <= full["start"] <= full["end"] <= outer["end"]
    paused = full["end"] - full["start"]
    assert paused > 0
    assert tr.self_seconds("t", "outer") == pytest.approx(
        outer["end"] - outer["start"] - paused, abs=1e-9)


@pytest.mark.parametrize("generation,stage", [
    (0, "young"), (1, "young"), (2, "full")])
def test_a_generation_lands_in_its_stage(hooked, generation, stage):
    tr, _ = hooked
    gc.collect(generation)
    with libtrace.span("t", "after"):       # the exit hands it over
        pass
    (got,) = tr.intervals("gc")
    assert got["stage"] == stage and got["generation"] == generation
    assert "parent" not in got                  # no span was open
    assert got["end"] <= tr.intervals("t", "after")[0]["end"]


def test_no_tracer_hooks_nothing_and_records_nothing(no_hook):
    before = list(gc.callbacks)
    with libtrace.span("t", "outer"):
        gc.collect()
    libtrace.close("t", "closed", 0.0)
    assert gc.callbacks == before
    assert libtrace._gc_hook not in gc.callbacks
    assert not libtrace._gc_done


def test_one_hook_however_often_a_tracer_is_set(no_hook):
    a, b = libtrace.StageTracer(), libtrace.StageTracer()
    libtrace.set_tracer(a)
    libtrace.set_tracer(b)
    libtrace.set_tracer(b)
    assert gc.callbacks.count(libtrace._gc_hook) == 1
    gc.collect()
    libtrace.set_tracer(None)       # what b's collection left goes to b
    assert gc.callbacks.count(libtrace._gc_hook) == 0
    assert len(b.intervals("gc", "full")) == 1
    assert a.intervals("gc") == []


def test_close_hands_the_collections_over(hooked):
    tr, _ = hooked
    gc.collect(0)
    libtrace.close("t", "episode", 0.0)
    assert len(tr.intervals("gc", "young")) == 1


@pytest.mark.parametrize("kind", ["span_tracer", "stage_tracer"])
def test_a_collection_under_the_tracers_own_lock(no_hook, kind):
    """A collection that starts while the tracer holds its lock (as one
    may inside record()) neither deadlocks nor is lost."""
    from benchmark.spans import SpanTracer

    if kind == "span_tracer":
        tr = SpanTracer()
        lock = tr._lock
    else:
        tr = libtrace.StageTracer()
        lock = tr._mtx
    libtrace.set_tracer(tr)
    done = threading.Event()

    def body():
        with lock:
            gc.collect()
        with libtrace.span("t", "after"):
            pass
        done.set()

    th = threading.Thread(target=body, name="gc-under-lock", daemon=True)
    th.start()
    th.join(timeout=30)
    assert done.is_set() and not th.is_alive()
    if kind == "span_tracer":
        names = [name for name, _, _ in tr.spans()]
        assert names.count("gc.full") == 1 and "t.after" in names
    else:
        assert len(tr.intervals("gc", "full")) == 1


def test_a_full_buffer_counts_what_it_drops(hooked, monkeypatch):
    tr, _ = hooked
    monkeypatch.setattr(libtrace, "GC_PENDING", 2)
    before = libtrace.gc_dropped
    for _ in range(5):
        gc.collect(0)
    assert libtrace.gc_dropped - before == 3
    assert len(libtrace._gc_done) == 2
    with libtrace.span("t", "after"):
        pass
    assert len(tr.intervals("gc", "young")) == 2
    assert tr.dropped_intervals == 3
    dropped = tr.metrics.intervals_dropped
    with dropped._mtx:
        assert dropped._values.get((), 0.0) == 3


def test_threads_draining_at_once_record_each_collection_once(hooked,
                                                              monkeypatch):
    """Many threads collect and drain at once, the buffer small enough to
    overflow: every collection is recorded once or counted dropped."""
    import sys

    tr, _ = hooked
    monkeypatch.setattr(libtrace, "GC_PENDING", 2)
    monkeypatch.setattr(libtrace, "MAX_INTERVALS", 10 ** 6)
    stops = []
    gc.callbacks.append(lambda phase, info: phase == "stop"
                        and stops.append(info["generation"]))
    before = libtrace.gc_dropped
    switch = sys.getswitchinterval()

    def worker(collects):
        for i in range(100):
            if collects:
                gc.collect(0)
            if not collects or i % 5 == 0:
                with libtrace.span("t", "w"):
                    pass

    threads = [threading.Thread(target=worker, args=(i % 2 == 0,),
                                name=f"drain-{i}", daemon=True)
               for i in range(16)]
    sys.setswitchinterval(1e-6)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(th.is_alive() for th in threads)
    libtrace.set_tracer(None)                   # hands over the rest
    dropped = libtrace.gc_dropped - before
    recorded = tr.snapshot().get("gc.young", {"count": 0})["count"]
    # a gc.collect() that finds another thread's collection under way
    # returns without collecting
    assert 0 < len(stops) <= 800
    assert recorded + dropped == len(stops)
    assert tr.dropped_intervals == dropped      # no ring overflowed


def test_trace_metrics_observe_the_full_collection(hooked):
    tr, reg = hooked
    with libtrace.span("t", "outer"):
        gc.collect()
    assert 'cometbft_trace_stage_duration_seconds_count{subsystem="gc",' \
        'stage="full"} 1' in reg.expose()
