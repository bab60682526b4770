"""What the light-client benchmark deployment asked of the program: the
spans inside a sequential sync (light.validate, light.verdict_wait,
light.divergence), what they cost with no tracer installed, the A-table
cache's clear() and its first-sighting count.
"""

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import trace as libtrace
from cometbft_tpu.light import verifier
from cometbft_tpu.light.client import SEQUENTIAL, Client, TrustOptions
from cometbft_tpu.light.provider import MemoryProvider
from cometbft_tpu.ops import ed25519 as dev

from helpers import CHAIN_ID, ChainBuilder

HOUR = 3600 * verifier.SECOND


@pytest.fixture(autouse=True)
def _cpu_provider(monkeypatch):
    monkeypatch.setenv("COMETBFT_TPU_PROVIDER", "cpu")


@pytest.fixture(scope="module")
def chain():
    b = ChainBuilder()
    b.build(12)
    return b


def _sync(chain, witnesses: int = 1, window: int = 4) -> Client:
    """A fresh client syncs 2..10 sequentially, in windows of four."""
    p = MemoryProvider(CHAIN_ID)
    for lb in chain.blocks:
        p.add(lb)
    c = Client(CHAIN_ID, TrustOptions(24 * HOUR, 1, chain.blocks[0].hash()),
               primary=p, witnesses=[p] * witnesses,
               verification_mode=SEQUENTIAL, sequential_batch_size=window,
               now_fn=lambda: chain.blocks[-1].header.time.add_ns(
                   60 * verifier.SECOND))
    assert c.verify_light_block_at_height(10).height == 10
    return c


# -- the spans ------------------------------------------------------------

@pytest.fixture(scope="module")
def traced(chain):
    prev = libtrace.tracer()
    tracer = libtrace.StageTracer()
    libtrace.set_tracer(tracer)
    try:
        _sync(chain)
    finally:
        libtrace.set_tracer(prev)
    return tracer


@pytest.mark.parametrize("stage, count", [
    ("validate", 9),            # once a header, 2..10
    ("verdict_wait", 3),        # once a window: 4 + 4 + 1 headers
    ("divergence", 1), ("store", 1), ("collect", 3), ("fetch", 3)])
def test_sequential_sync_opens_each_light_span(traced, stage, count):
    assert stage in libtrace.LIGHT_STAGES + libtrace.PIPELINE_STAGES
    assert traced.snapshot()[f"light.{stage}"]["count"] == count


def test_validate_lies_inside_collect(traced):
    snap = traced.snapshot()
    validate = snap["light.validate"]["seconds"]
    collect = snap["light.collect"]["seconds"]
    assert 0 < validate <= collect
    # what of collect no child names is the signature collection; a
    # collection of the garbage collector's that ran in it is a child
    paused = sum(iv["end"] - iv["start"] for iv in traced.intervals("gc")
                 if iv.get("parent") == "light.collect")
    assert traced.self_seconds("light", "collect") == pytest.approx(
        collect - validate - paused, abs=1e-5)
    got = traced.intervals("light", "validate")
    assert len(got) == 9
    assert {iv["parent"] for iv in got} == {"light.collect"}


@pytest.fixture
def bare():
    prev = (libtrace.tracer(), libmetrics.device_metrics())
    libtrace.set_tracer(None)
    libmetrics.set_device_metrics(None)
    yield
    libtrace.set_tracer(prev[0])
    libmetrics.set_device_metrics(prev[1])


@pytest.mark.parametrize("stage", ["validate", "verdict_wait",
                                   "divergence"])
def test_no_tracer_gives_the_shared_null_span(bare, stage):
    # one global read and an `is None` test: no object is built
    assert libtrace.span("light", stage) is libtrace._NULL_SPAN


def test_a_sync_builds_no_timed_span_when_nothing_is_installed(
        bare, chain, monkeypatch):
    made = []
    init = libtrace._TimedSpan.__init__
    monkeypatch.setattr(
        libtrace._TimedSpan, "__init__",
        lambda self, *a, **kw: (made.append(a), init(self, *a, **kw))[1])
    _sync(chain)
    assert made == []
    assert getattr(libtrace._open, "stack", []) == []


# -- the A-table cache ---------------------------------------------------------

@pytest.fixture
def cache(monkeypatch):
    """A cache of its own whose tables are built by a stand-in (the real
    build is a device program, minutes to compile here)."""
    built = []

    def build(a_words):
        built.append(np.asarray(a_words).shape)
        k = np.asarray(a_words).shape[-1]
        return np.zeros((17, 4, 20, k), np.int32), np.bool_(True)

    monkeypatch.setattr(dev, "build_a_tables_device", build)
    c = ed.ATableCache(capacity=8)
    c.built = built
    return c


def _a_side(tag: int, k: int = 128) -> np.ndarray:
    return np.full((8, k), tag, dtype=np.uint32)


def test_sightings_first_stays_fused_second_builds_third_hits(cache):
    a = _a_side(1)
    assert cache.get_if_worthwhile(a) is None
    assert (cache.first_sightings, cache.misses, cache.hits) == (1, 0, 0)
    assert cache.get_if_worthwhile(a) is not None
    assert (cache.first_sightings, cache.misses, cache.hits) == (1, 1, 0)
    assert cache.get_if_worthwhile(a) is not None
    assert (cache.first_sightings, cache.misses, cache.hits) == (1, 1, 1)
    assert len(cache.built) == 1
    assert cache.bytes_resident == 128 * ed.BYTES_PER_A_SLOT
    # below MIN_K nothing is noted at all
    assert cache.get_if_worthwhile(_a_side(2, k=8)) is None
    assert cache.first_sightings == 1


def test_clear_leaves_entries_sightings_and_bytes_empty(cache):
    dm = libmetrics.DeviceMetrics(libmetrics.Registry())
    prev = libmetrics.device_metrics()
    libmetrics.set_device_metrics(dm)
    try:
        a, b = _a_side(1), _a_side(2)
        for _ in range(3):
            cache.get_if_worthwhile(a)
        cache.get_if_worthwhile(b)
        assert len(cache._entries) == 1 and len(cache._seen) == 2
        assert dm.a_table_cache_bytes._values[()] == cache.bytes_resident > 0
        cache.clear()
        assert len(cache._entries) == 0 and len(cache._seen) == 0
        assert cache.bytes_resident == 0
        assert dm.a_table_cache_bytes._values[()] == 0
        # a cleared cache treats a repeated A side as a first sighting
        assert cache.get_if_worthwhile(a) is None
        assert cache.get_if_worthwhile(b) is None
        assert cache.get_if_worthwhile(a) is not None
        assert len(cache.built) == 2
        # the counts run on, in the object and in the metrics
        assert (cache.first_sightings, cache.misses, cache.hits) == (4, 2, 1)
        got = {name: getattr(dm, "a_table_cache_" + name)._values.get((), 0)
               for name in ("first_sightings", "misses", "hits")}
        assert got == {"first_sightings": 4, "misses": 2, "hits": 1}
    finally:
        libmetrics.set_device_metrics(prev)


def test_first_sightings_counter_is_exposed_under_its_name():
    dm = libmetrics.DeviceMetrics(libmetrics.Registry(namespace="cometbft"))
    dm.a_table_cache_first_sightings.inc()
    assert "cometbft_device_a_table_cache_first_sightings 1" \
        in dm.a_table_cache_first_sightings.collect()
