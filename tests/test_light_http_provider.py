"""light/provider.HttpProvider over a real in-process RPC server: one
kept HTTP/1.1 connection a thread, the same light blocks as a
connection a request gives, one reconnect where the node closed a kept
connection, and ErrNoResponse from a node that is gone.

The oracle is the provider as it was before it kept connections: three
urllib.request.urlopen calls a header, each on a connection of its own,
decoded the same way.
"""

import gc
import json
import socket
import threading
import time
import urllib.request

import pytest

from cometbft_tpu.libs import trace
from cometbft_tpu.light.provider import (
    ErrLightBlockNotFound, ErrNoResponse, HttpProvider)
from cometbft_tpu.light.types import LightBlock
from cometbft_tpu.rpc import server as rpc_server

# two /validators pages a header, as at the QA deployment's 175
VALS = 130
TOP = 8
CHAIN_ID = "provider-chain"


@pytest.fixture(scope="module")
def chain():
    from benchmark import fixture

    c = fixture.build_chain(
        {"validators": VALS, "power": 10, "chain_blocks": TOP,
         "txs_per_block": 1, "tx_bytes": 32, "chain_id": CHAIN_ID},
        seed=2 ** 31 + 38)
    yield c
    c.src.stop()


@pytest.fixture
def node(chain, monkeypatch):
    """The chain's source behind a fresh RPC server, which counts the
    connections it accepts in `.accepted`."""
    accepted = []
    real = rpc_server.KeepAliveHTTPServer.process_request

    def counting(self, request, client_address):
        accepted.append(client_address)
        real(self, request, client_address)

    monkeypatch.setattr(rpc_server.KeepAliveHTTPServer, "process_request",
                        counting)
    addr = chain.src.start_rpc()
    srv = chain.src.rpc_server
    srv.accepted = accepted
    srv.addr = addr
    yield srv
    srv.stop()
    chain.src.rpc_server = None


@pytest.fixture
def tracer():
    t = trace.StageTracer()
    trace.set_tracer(t)
    yield t
    trace.set_tracer(None)


def oracle_light_block(addr: str, height: int) -> LightBlock:
    """What the provider decoded with a connection a request."""
    from cometbft_tpu.light.rpc_decode import (
        signed_header_from_rpc, validators_from_rpc)
    from cometbft_tpu.types.validator_set import ValidatorSet

    def get(path, params):
        qs = "&".join(f"{k}={v}" for k, v in params.items())
        with urllib.request.urlopen(f"http://{addr}/{path}?{qs}",
                                    timeout=10) as resp:
            return json.loads(resp.read())["result"]

    sh = signed_header_from_rpc(get("commit", {"height": height})
                                ["signed_header"])
    vals, page = [], 1
    while True:
        res = get("validators", {"height": sh.height, "page": page,
                                 "per_page": 100})
        vals.extend(validators_from_rpc(res["validators"]))
        if len(vals) >= int(res["total"]):
            break
        page += 1
    return LightBlock(sh, ValidatorSet.from_validated(vals))


def _connects(tracer) -> list:
    return [i.get("reason") for i in tracer.intervals("light", "connect")]


def _open_server_conns(srv) -> int:
    with srv._httpd._conns_mtx:
        return len(srv._httpd._conns)


def _wait(pred, timeout=5.0) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def test_one_thread_opens_one_connection(node, tracer):
    p = HttpProvider(CHAIN_ID, f"http://{node.addr}")
    try:
        for h in range(2, TOP + 1):
            assert p.light_block(h).height == h
    finally:
        p.close()
    assert len(node.accepted) == 1
    rpcs = tracer.intervals("light", "rpc")
    # /commit and two /validators pages a header
    assert len(rpcs) == 3 * (TOP - 1)
    assert [i["reused"] for i in rpcs] == [False] + [True] * (len(rpcs) - 1)
    assert [i["path"] for i in rpcs[:3]] == ["commit", "validators",
                                            "validators"]
    assert _connects(tracer) == ["first"]
    # the connection is opened inside the request that needed it
    assert tracer.intervals("light", "connect")[0]["parent"] == "light.rpc"


@pytest.mark.parametrize("height", [0, 2, TOP // 2, TOP])
def test_light_blocks_equal_the_urlopen_oracle(node, height):
    p = HttpProvider(CHAIN_ID, f"http://{node.addr}")
    try:
        got = p.light_block(height)
    finally:
        p.close()
    want = oracle_light_block(node.addr, got.height if height == 0
                              else height)
    assert got.to_proto() == want.to_proto()
    assert got.validator_set.hash() == want.validator_set.hash() == \
        got.header.validators_hash
    if height == 0:
        # the source holds one block above chain_blocks
        assert got.height == TOP + 1


def test_a_missing_height_maps_as_before(node):
    p = HttpProvider(CHAIN_ID, f"http://{node.addr}")
    try:
        with pytest.raises(ErrLightBlockNotFound):
            p.light_block(10 ** 6)
        # the error came in a 200 answer: the connection is kept
        assert p.light_block(2).height == 2
    finally:
        p.close()
    assert len(node.accepted) == 1


@pytest.mark.parametrize("status,body", [
    (500, b'{"jsonrpc": "2.0", "id": -1, "result": {}}'),
    (200, b"not json")])
def test_a_bad_answer_is_no_response(status, body):
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Answer(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_GET(self):  # noqa: N802
            self.send_response(status)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Answer)
    srv.daemon_threads = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    p = HttpProvider(CHAIN_ID, "http://%s:%d" % srv.server_address)
    try:
        with pytest.raises(ErrNoResponse):
            p.light_block(2)
    finally:
        p.close()
        srv.shutdown()
        srv.server_close()
        t.join(timeout=5)


def test_a_connection_the_node_closed_is_replaced_once(node, tracer):
    p = HttpProvider(CHAIN_ID, f"http://{node.addr}")
    try:
        assert p.light_block(2).height == 2
        assert _wait(lambda: _open_server_conns(node) == 1)
        # the node ends the kept connection between two requests
        with node._httpd._conns_mtx:
            conns = list(node._httpd._conns)
        for s in conns:
            s.shutdown(socket.SHUT_RDWR)
        assert _wait(lambda: _open_server_conns(node) == 0)
        got = p.light_block(3)
    finally:
        p.close()
    assert len(node.accepted) == 2
    assert _connects(tracer) == ["first", "closed_by_peer"]
    assert got.to_proto() == oracle_light_block(node.addr, 3).to_proto()


def test_a_stopped_node_is_no_response_within_the_timeout(chain, node):
    kept = HttpProvider(CHAIN_ID, f"http://{node.addr}", timeout=2.0)
    fresh = HttpProvider(CHAIN_ID, f"http://{node.addr}", timeout=2.0)
    try:
        assert kept.light_block(2).height == 2
        node.stop()
        for p in (kept, fresh):
            t0 = time.monotonic()
            with pytest.raises(ErrNoResponse):
                p.light_block(3)
            assert time.monotonic() - t0 < 2.0
    finally:
        kept.close()
        fresh.close()


def test_two_threads_open_two_connections(node, tracer):
    p = HttpProvider(CHAIN_ID, f"http://{node.addr}")
    got: dict = {}
    errors: list = []

    def fetch(heights):
        try:
            for h in heights:
                got[h] = p.light_block(h)
        except Exception as e:  # noqa: BLE001 - asserted below
            errors.append(e)

    threads = [threading.Thread(target=fetch, args=(hs,), daemon=True)
               for hs in (range(2, 6), range(5, TOP + 1))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        p.close()
    assert not errors
    assert len(node.accepted) == 2
    assert _connects(tracer) == ["first", "first"]
    for h in range(2, TOP + 1):
        assert got[h].to_proto() == \
            oracle_light_block(node.addr, h).to_proto()


def test_a_thread_that_ends_lets_its_connection_go(node):
    p = HttpProvider(CHAIN_ID, f"http://{node.addr}")
    try:
        t = threading.Thread(target=p.light_block, args=(2,), daemon=True)
        t.start()
        t.join(timeout=30)
        del t
        gc.collect()
        assert _wait(lambda: _open_server_conns(node) == 0)
        assert p.light_block(3).height == 3
        assert _open_server_conns(node) == 1
    finally:
        p.close()
    # close() closed what the provider still held
    assert _wait(lambda: _open_server_conns(node) == 0)
    assert len(node.accepted) == 2


def test_after_close_a_request_opens_a_new_connection(node, tracer):
    p = HttpProvider(CHAIN_ID, f"http://{node.addr}")
    try:
        assert p.light_block(2).height == 2
        p.close()
        assert p.light_block(3).height == 3
    finally:
        p.close()
    assert _connects(tracer) == ["first", "first"]
    assert len(node.accepted) == 2
