"""rpc/server on a kept-alive HTTP/1.1 connection: several requests on
one socket, each answered without the 40 ms that Nagle's algorithm and
a delayed ACK would hold a reply's body; a reply to a request whose body
was never read closes the connection; an idle connection is closed after
the handler's timeout; stop() ends kept connections at once."""

import http.client
import json
import socket
import time

import pytest

from cometbft_tpu.rpc import server as rpc_server
from cometbft_tpu.rpc.core import Environment


@pytest.fixture
def serve(monkeypatch):
    """serve(timeout=None) -> a started RPCServer over an empty
    Environment (enough for /health), stopped at the end."""
    servers = []

    def start(timeout=None):
        if timeout is not None:
            monkeypatch.setattr(rpc_server, "READ_TIMEOUT_S", timeout)
        srv = rpc_server.RPCServer(Environment(), "127.0.0.1:0")
        srv.start()
        servers.append(srv)
        return srv

    yield start
    for srv in servers:
        srv.stop()


def _conn(srv) -> http.client.HTTPConnection:
    host, port = srv.bound_addr.rsplit(":", 1)
    return http.client.HTTPConnection(host, int(port), timeout=5)


def _health(conn) -> dict:
    conn.request("GET", "/health")
    resp = conn.getresponse()
    assert resp.status == 200 and not resp.will_close
    return json.loads(resp.read())


def _closed(sock, within: float) -> bool:
    """The server closed `sock` within `within` seconds: a read sees
    the end of the stream (or a reset)."""
    sock.settimeout(within)
    try:
        return sock.recv(1) == b""
    except ConnectionResetError:
        return True
    except TimeoutError:
        return False


def test_two_requests_on_one_socket_are_both_answered(serve):
    conn = _conn(serve())
    try:
        for _ in range(2):
            assert _health(conn) == {"jsonrpc": "2.0", "id": -1,
                                     "result": {}}
        sock = conn.sock
        conn.request("POST", "/", body=json.dumps(
            {"jsonrpc": "2.0", "id": 7, "method": "health"}))
        assert json.loads(conn.getresponse().read())["id"] == 7
        # still the first socket
        assert conn.sock is sock
    finally:
        conn.close()


def test_twenty_requests_on_a_kept_connection_do_not_stall(serve):
    conn = _conn(serve())
    try:
        _health(conn)
        t0 = time.perf_counter()
        for _ in range(20):
            _health(conn)
        elapsed = time.perf_counter() - t0
    finally:
        conn.close()
    # a Nagle stall costs ~40 ms a reply: 0.8 s for twenty
    assert elapsed < 0.4, elapsed


@pytest.mark.parametrize("length,status", [(str(10 ** 7), 413),
                                           ("many", 400), ("-5", 400)])
def test_a_reply_that_leaves_the_body_unread_closes(serve, length, status):
    srv = serve()
    host, port = srv.bound_addr.rsplit(":", 1)
    with socket.create_connection((host, int(port)), timeout=5) as sock:
        sock.sendall(b"POST / HTTP/1.1\r\nHost: x\r\nContent-Length: "
                     + length.encode() + b"\r\n\r\n")
        resp = http.client.HTTPResponse(sock)
        resp.begin()
        assert resp.status == status
        assert resp.getheader("Connection") == "close"
        resp.read()
        # body bytes sent now are never taken for a next request
        try:
            sock.sendall(b"GET /health HTTP/1.1\r\nHost: x\r\n\r\n")
        except OSError:
            pass
        assert _closed(sock, 2.0)


def test_an_idle_kept_connection_is_closed_after_the_timeout(serve):
    conn = _conn(serve(timeout=0.3))
    try:
        _health(conn)
        t0 = time.monotonic()
        assert _closed(conn.sock, 5.0)
        assert 0.2 < time.monotonic() - t0 < 3.0
    finally:
        conn.close()


def test_stop_returns_at_once_with_a_kept_connection_open(serve):
    srv = serve()
    conn = _conn(srv)
    try:
        _health(conn)
        t0 = time.monotonic()
        srv.stop()
        # serve_forever notices shutdown() within its 0.5 s poll
        assert time.monotonic() - t0 < 2.0
        # and the connection is not served on
        assert _closed(conn.sock, 2.0)
    finally:
        conn.close()
