"""The spans inside `apply` (state.*) and round every RLC dispatch
(verify.*), the signatures-verified counter beside them, and the
parent / self-time bookkeeping of libs/trace.

(a) one block through BlockExecutor on the kvstore app, (b) one batch
through the synchronous batch seam on the CPU backend with the real
kernels (the 8 x 8 shape tests/test_ed25519.py compiles too), (c) the
same calls with nothing installed, (d) self_seconds on a hand-built
nest.
"""

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
    assert tracer.self_seconds("blocksync", "apply") == pytest.approx(
        whole - covered, abs=1e-9)


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
