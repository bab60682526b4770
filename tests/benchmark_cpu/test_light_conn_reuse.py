"""light_conn_reuse_share.sync: read from the spans light.rpc and
light.connect of light/provider.HttpProvider.  The tiny `light` cell
(blocks over the source's real RPC server) fills it near 1, since each
thread keeps its connection; the `sequence` cell (a memory provider)
and a program without those spans leave it out of the line."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import manifests  # noqa: E402
from benchmark import harness, readers  # noqa: E402
from test_light_mode import (  # noqa: E402,F401 - fixtures
    CELLS, _run, cells, stub_device)

NAME = "light_conn_reuse_share.sync"


def _spans(**counts):
    run = harness.Run("w", {}, {}, 1, 1.0, {})
    run.spans = {f"light.{k}": {"count": n, "seconds": 0.001 * n}
                 for k, n in counts.items()}
    return run


@pytest.mark.parametrize("counts,want", [
    ({}, None),                          # no request over HTTP
    ({"rpc": 0}, None),
    ({"rpc": 573}, 1.0),                 # every request on a kept one
    ({"rpc": 573, "connect": 1}, 1 - 1 / 573),
    ({"rpc": 573, "connect": 573}, 0.0),     # a connection a request
])
def test_the_reader(counts, want):
    got = readers.read_metric(NAME, _spans(**counts))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("workload", list(CELLS))
def test_a_traced_run_reads_the_share_where_blocks_come_over_http(
        stub_device, cells, monkeypatch, workload):
    monkeypatch.setattr(harness.Profile, "start", lambda self: setattr(
        self, "t0", 0.0))
    monkeypatch.setattr(harness.Profile, "stop", lambda self: setattr(
        self, "t1", 0.0))
    monkeypatch.setattr(harness, "read_profile", lambda *a: None)
    result = _run(cells, workload, trace=True)
    got = result["metrics"]
    mine = manifests.lists_hold(cells, workload, got)
    if harness.load_json(os.path.join(
            harness.HERE, "traffic", CELLS[workload][1] + ".json")
            )["provider"] == "http":
        assert NAME in mine
        # a pass opens one connection, for the client's new prefetch
        # thread, against two requests a header
        assert 0.95 < got[NAME]["value"] <= 1.0
    else:
        assert NAME not in got
    assert result["correct"] is True
