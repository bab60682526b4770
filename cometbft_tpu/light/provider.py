"""Light-block providers (light/provider analog).

Provider is the seam the client fetches LightBlocks through
(/root/reference/light/provider/provider.go:15-40). HttpProvider speaks
the CometBFT JSON-RPC /commit + /validators endpoints of a full node, so
this client can sync against real reference chains; tests use in-memory
providers.
"""

from __future__ import annotations

import http.client
import json
import threading
import weakref
from typing import Protocol
from urllib.parse import urlsplit

from ..libs import lockrank
from ..libs.trace import span as trace_span
from .types import LightBlock


class ProviderError(Exception):
    pass


class ErrLightBlockNotFound(ProviderError):
    pass


class ErrNoResponse(ProviderError):
    pass


class ErrHeightTooHigh(ProviderError):
    pass


class ErrBadLightBlock(ProviderError):
    pass


class Provider(Protocol):
    def light_block(self, height: int) -> LightBlock:
        """Fetch the light block at height (0 = latest).

        Raises ProviderError subclasses on failure."""
        ...

    def chain_id(self) -> str: ...

    def report_evidence(self, ev) -> None:
        """Submit misbehavior evidence back to this provider's node
        (provider.Provider ReportEvidence)."""
        ...


class MemoryProvider:
    """In-memory provider for tests and local verification."""

    def __init__(self, chain_id: str,
                 blocks: dict[int, LightBlock] | None = None):
        self._chain_id = chain_id
        self._blocks: dict[int, LightBlock] = dict(blocks or {})
        self.reported_evidence: list = []

    def add(self, lb: LightBlock) -> None:
        self._blocks[lb.height] = lb

    def report_evidence(self, ev) -> None:
        self.reported_evidence.append(ev)

    def chain_id(self) -> str:
        return self._chain_id

    def light_block(self, height: int) -> LightBlock:
        if height == 0:
            if not self._blocks:
                raise ErrLightBlockNotFound("no blocks")
            height = max(self._blocks)
        lb = self._blocks.get(height)
        if lb is None:
            if self._blocks and height > max(self._blocks):
                raise ErrHeightTooHigh(str(height))
            raise ErrLightBlockNotFound(str(height))
        return lb


# what a kept connection that the node closed meanwhile raises at its
# next request (RemoteDisconnected is both a ConnectionResetError and a
# BadStatusLine): the request goes once more on a fresh connection, as
# Go's Transport retries an idempotent request on a reused connection
_PEER_CLOSED = (ConnectionResetError, BrokenPipeError,
                http.client.BadStatusLine)
_CONNECTIONS = {"http": http.client.HTTPConnection,
                "https": http.client.HTTPSConnection}


class HttpProvider:
    """JSON-RPC provider over a CometBFT full node's RPC
    (light/provider/http/http.go analog: /commit + /validators with
    pagination).

    Each thread that asks keeps one HTTP/1.1 connection to the node (the
    client's own thread and its prefetch worker never share a socket),
    as the Go client's Transport keeps its connections; a thread's
    connection closes when the thread ends, or at close()."""

    def __init__(self, chain_id: str, base_url: str, timeout: float = 10.0):
        self._chain_id = chain_id
        self._base = base_url.rstrip("/")
        url = urlsplit(self._base)
        self._scheme, self._netloc, self._prefix = \
            url.scheme, url.netloc, url.path
        self._timeout = timeout
        self._local = threading.local()
        # finalizers of the sockets opened, for close()
        self._mtx = lockrank.RankedLock("light.provider")
        self._closers: list = []

    def chain_id(self) -> str:
        return self._chain_id

    def close(self) -> None:
        """Close every connection this provider opened, on any thread;
        a later request opens a new one."""
        self._local = threading.local()
        with self._mtx:
            closers, self._closers = self._closers, []
        for close in closers:
            close()

    def _connect(self, reason: str) -> http.client.HTTPConnection:
        with trace_span("light", "connect", reason=reason):
            cls = _CONNECTIONS.get(self._scheme)
            if cls is None:
                raise ValueError(f"unknown url type: {self._base!r}")
            conn = cls(self._netloc, timeout=self._timeout)
            conn.connect()
        # the socket closes when the connection is let go of (its
        # thread ended, or it failed) or at close()
        closer = weakref.finalize(conn, conn.sock.close)
        with self._mtx:
            self._closers = [c for c in self._closers if c.alive]
            self._closers.append(closer)
        return conn

    def _exchange(self, conn: http.client.HTTPConnection, target: str):
        conn.request("GET", target)
        resp = conn.getresponse()
        data = resp.read()
        if resp.will_close:
            # the node said it closes this one (Connection: close)
            self._local.conn = None
            self._local.reason = "closed_by_peer"
        return resp, data

    def _get(self, target: str, sp):
        """(response, body) of one GET on this thread's connection: a
        kept one that the node has closed is replaced once; any other
        failure drops it and is ErrNoResponse."""
        local = self._local
        conn = getattr(local, "conn", None)
        sp.note(reused=conn is not None)
        try:
            if conn is None:
                conn = local.conn = self._connect(
                    getattr(local, "reason", "first"))
                return self._exchange(conn, target)
            try:
                return self._exchange(conn, target)
            except _PEER_CLOSED:
                conn.close()
                conn = local.conn = self._connect("closed_by_peer")
                return self._exchange(conn, target)
        except (OSError, http.client.HTTPException, ValueError) as e:
            if conn is not None:
                conn.close()
            local.conn = None
            local.reason = "error"
            raise ErrNoResponse(str(e)) from e

    def _rpc(self, path: str, params: dict) -> dict:
        qs = "&".join(f"{k}={v}" for k, v in params.items())
        target = f"{self._prefix}/{path}?{qs}" if qs else \
            f"{self._prefix}/{path}"
        with trace_span("light", "rpc", path=path) as sp:
            resp, data = self._get(target, sp)
        if not 200 <= resp.status < 300:
            raise ErrNoResponse(f"HTTP Error {resp.status}: {resp.reason}")
        try:
            body = json.loads(data)
        except ValueError as e:
            raise ErrNoResponse(str(e)) from e
        if "error" in body and body["error"]:
            msg = str(body["error"])
            if "height" in msg and "must be less" in msg:
                raise ErrHeightTooHigh(msg)
            raise ErrLightBlockNotFound(msg)
        return body["result"]

    def light_block(self, height: int) -> LightBlock:
        from .rpc_decode import signed_header_from_rpc, validators_from_rpc

        hparam = {} if height == 0 else {"height": height}
        commit_res = self._rpc("commit", hparam)
        sh = signed_header_from_rpc(commit_res["signed_header"])
        # pin the validators query to the commit's height: with height=0
        # ("latest") a new block could land between the two RPCs
        vparam = {"height": sh.height}
        vals = []
        page, per_page = 1, 100
        while True:
            res = self._rpc("validators", {**vparam, "page": page,
                                           "per_page": per_page})
            batch = validators_from_rpc(res["validators"])
            if not batch:
                raise ErrBadLightBlock(
                    f"validators page {page} empty with "
                    f"{len(vals)}/{res['total']} fetched")
            vals.extend(batch)
            if len(vals) >= int(res["total"]):
                break
            page += 1
        from ..types.validator_set import ValidatorSet
        vs = ValidatorSet.from_validated(vals)
        lb = LightBlock(sh, vs)
        try:
            lb.validate_basic(self._chain_id)
        except ValueError as e:
            raise ErrBadLightBlock(str(e)) from e
        return lb

    def report_evidence(self, ev) -> None:
        """POST the evidence to the node's /broadcast_evidence
        (light/provider/http ReportEvidence)."""
        import base64

        from ..types.evidence import evidence_to_proto_wrapped

        from urllib.parse import quote

        wrapped = base64.b64encode(
            evidence_to_proto_wrapped(ev)).decode()
        self._rpc("broadcast_evidence", {"evidence": quote(wrapped)})
