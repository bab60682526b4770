"""JSON-RPC 2.0 over HTTP (reference rpc/jsonrpc/server/).

Accepts POST / with a JSON-RPC envelope and GET /<method>?arg=...
URI-style calls, like the reference's http_json_handler + uri handler.
The handler factory and the server class are shared with the light
proxy (which serves a different route table and no websocket).

Connections are HTTP/1.1 and kept alive, as Go's net/http server keeps
them: a light client's provider asks three questions a header on one
connection.  A reply leaves in one go (TCP_NODELAY: the headers and the
body are two writes, and Nagle would hold the body until the client's
delayed ACK, 40 ms and more), and a connection that sits idle is closed
after READ_TIMEOUT_S."""

from __future__ import annotations

import json
import socket
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qsl, urlparse

from ..libs import lockrank
from .core import ROUTES, Environment, RPCError

MAX_BODY_BYTES = 1_000_000
# seconds a connection may wait for its next request (or stall one read
# or write) before the server closes it: rpc/jsonrpc/server
# DefaultConfig's ReadTimeout, which net/http also takes for the idle
# wait of a kept connection
READ_TIMEOUT_S = 10.0


class KeepAliveHTTPServer(ThreadingHTTPServer):
    """A thread a connection, and the connections it holds open: close
    shuts each down, so a kept-alive one is not served on after stop()
    and its thread ends at once (handler threads are daemons, which
    server_close does not join)."""

    daemon_threads = True

    def __init__(self, addr, handler):
        self._conns: set = set()
        self._conns_mtx = lockrank.RankedLock("rpc.conns")
        super().__init__(addr, handler)

    def process_request(self, request, client_address) -> None:
        with self._conns_mtx:
            self._conns.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request) -> None:
        with self._conns_mtx:
            self._conns.discard(request)
        super().shutdown_request(request)

    def server_close(self) -> None:
        super().server_close()
        with self._conns_mtx:
            conns = list(self._conns)
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


class RPCServer:
    def __init__(self, env: Environment, addr: str, routes=None,
                 with_websocket: bool = True):
        routes = ROUTES if routes is None else routes
        host, _, port = addr.rpartition(":")
        self._env = env

        def dispatch(method: str, params: dict, req_id) -> dict:
            attr = routes.get(method)
            if attr is None:
                return _err(req_id, -32601, f"method {method} not found")
            return _call_target(getattr(env, attr), params, req_id)

        self._httpd = KeepAliveHTTPServer(
            (host or "127.0.0.1", int(port)),
            make_json_handler(dispatch, sorted(routes),
                              env=env if with_websocket else None))
        self._thread: threading.Thread | None = None
        self.bound_addr = "%s:%d" % self._httpd.server_address

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="rpc-http",
            daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            self._httpd.shutdown()
        self._httpd.server_close()
        # the lazily-created serving plane (rpc/core.py _lightserve)
        # owns a flusher thread; close is idempotent, so the public
        # and privileged servers sharing one Environment both calling
        # it is fine
        ls = getattr(self._env, "lightserve", None)
        if ls is not None:
            try:
                ls.close()
            except Exception:
                pass


def _err(req_id, code: int, message: str, data: str = "") -> dict:
    e = {"code": code, "message": message}
    if data:
        e["data"] = data
    return {"jsonrpc": "2.0", "id": req_id, "error": e}


def _coerce_params(params: dict) -> dict:
    """URI params arrive as strings; strip surrounding quotes the way
    the reference's uri handler tolerates."""
    out = {}
    for k, v in params.items():
        if isinstance(v, str) and len(v) >= 2 and \
                v[0] == '"' and v[-1] == '"':
            v = v[1:-1]
        out[k] = v
    return out


def _call_target(fn, params: dict, req_id) -> dict:
    """Invoke one handler with JSON-RPC error mapping."""
    try:
        return {"jsonrpc": "2.0", "id": req_id,
                "result": fn(**_coerce_params(params))}
    except RPCError as e:
        return _err(req_id, e.code, e.message, e.data)
    except TypeError as e:
        return _err(req_id, -32602, f"invalid params: {e}")
    except Exception as e:
        return _err(req_id, -32603, str(e))


def make_json_handler(dispatch, route_names, env=None):
    """HTTP handler over a `dispatch(method, params, id) -> response`
    function.  `env` (when given) enables the /websocket upgrade for
    event subscriptions; the light proxy passes env=None."""

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        disable_nagle_algorithm = True
        timeout = READ_TIMEOUT_S

        def log_message(self, *args) -> None:
            pass  # quiet

        # -- helpers -------------------------------------------------------
        def _reply(self, status: int, payload, close: bool = False) -> None:
            """`close` where the request's body was left unread: the
            connection cannot be read on, and the client is told."""
            body = json.dumps(payload).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if close:
                self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(body)

        def _call(self, method: str, params: dict, req_id) -> dict:
            return dispatch(method, params, req_id)

        # -- JSON-RPC over POST -------------------------------------------
        def _call_envelope(self, req) -> dict:
            """One envelope -> one response; malformed shapes get
            -32600 instead of dropping the connection (the reference's
            jsonrpc server maps every decode failure to an error
            response, rpc/jsonrpc/server/http_json_handler.go)."""
            if not isinstance(req, dict):
                return _err(None, -32600,
                            f"invalid request: expected object, got "
                            f"{type(req).__name__}")
            method = req.get("method", "")
            if not isinstance(method, str):
                return _err(req.get("id"), -32600,
                            "invalid request: method must be a string")
            params = req.get("params") or {}
            if not isinstance(params, dict):
                return _err(req.get("id"), -32602,
                            "invalid params: expected object")
            return self._call(method, params, req.get("id"))

        def do_POST(self) -> None:  # noqa: N802
            try:
                length = int(self.headers.get("Content-Length", "0"))
            except ValueError:
                self._reply(400, _err(None, -32700,
                                      "invalid Content-Length"), close=True)
                return
            if length > MAX_BODY_BYTES:
                self._reply(413, {"error": "body too large"}, close=True)
                return
            if length < 0:
                self._reply(400, _err(None, -32700,
                                      "invalid Content-Length"), close=True)
                return
            try:
                req = json.loads(self.rfile.read(length) or b"{}")
            except (json.JSONDecodeError, UnicodeDecodeError):
                self._reply(400, _err(None, -32700, "parse error"))
                return
            if isinstance(req, list):  # batch
                if not req:            # JSON-RPC 2.0 §6: empty batch
                    self._reply(200, _err(None, -32600, "empty batch"))
                    return
                resp = [self._call_envelope(r) for r in req]
            else:
                resp = self._call_envelope(req)
            self._reply(200, resp)

        # -- WebSocket upgrade (reference ws_handler.go) -------------------
        def _do_websocket(self) -> None:
            from . import websocket as ws

            key = self.headers.get("Sec-WebSocket-Key", "")
            if not key:
                self._reply(400, {"error": "missing Sec-WebSocket-Key"})
                return
            self.send_response(101, "Switching Protocols")
            self.send_header("Upgrade", "websocket")
            self.send_header("Connection", "Upgrade")
            self.send_header("Sec-WebSocket-Accept", ws.accept_key(key))
            self.end_headers()
            self.close_connection = True
            # a subscription waits on its events as long as it likes
            self.connection.settimeout(None)
            session = ws.WSSession(
                env, self.rfile, self.wfile,
                "%s:%d" % self.client_address[:2], self._call)
            session.run()

        # -- URI-style GET -------------------------------------------------
        def do_GET(self) -> None:  # noqa: N802
            parsed = urlparse(self.path)
            method = parsed.path.strip("/")
            if env is not None and method == "websocket" and \
                    "upgrade" in self.headers.get("Connection", "").lower():
                self._do_websocket()
                return
            if method == "":
                # route listing (reference serves an HTML index)
                self._reply(200, {"jsonrpc": "2.0", "id": -1,
                                  "result": {"routes": list(route_names)}})
                return
            params = dict(parse_qsl(parsed.query))
            self._reply(200, self._call(method, params, -1))

    return Handler
