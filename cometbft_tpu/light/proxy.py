"""Light-client RPC proxy (reference cmd/cometbft/commands/light.go +
light/proxy/): serves a JSON-RPC subset where every header/commit
handed out has been light-verified against the trust root, so a wallet
can point at an untrusted full node through this proxy.
"""

from __future__ import annotations

import threading

from ..rpc import serialize as ser
from ..rpc.server import (
    KeepAliveHTTPServer, _call_target, _err, make_json_handler)


class LightProxy:
    """Verifying proxy over a light.Client."""

    def __init__(self, client, addr: str):
        self._client = client
        self._light_requests = 0
        self._light_headers = 0
        host, _, port = addr.replace("tcp://", "").rpartition(":")

        def dispatch(method, params, req_id):
            fn_name = _ROUTES.get(method)
            if fn_name is None:
                return _err(req_id, -32601,
                            f"method {method} not found (light proxy "
                            "serves verified routes only)")
            return _call_target(getattr(self, fn_name), params, req_id)

        self._httpd = KeepAliveHTTPServer(
            (host or "127.0.0.1", int(port)),
            make_json_handler(dispatch, sorted(_ROUTES)))
        self.bound_addr = "%s:%d" % self._httpd.server_address
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="light-proxy",
            daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            self._httpd.shutdown()
        self._httpd.server_close()

    # -- verified handlers -------------------------------------------------

    def _verified_block(self, height):
        from ..types.timestamp import Timestamp
        h = int(height) if height else 0
        if h <= 0:
            lb = self._client.update(Timestamp.now())
            if lb is None:
                lb = self._client.latest_trusted()
        else:
            lb = self._client.verify_light_block_at_height(
                h, Timestamp.now())
        if lb is None:
            raise ValueError("no verifiable block")
        return lb

    def header(self, height=None) -> dict:
        lb = self._verified_block(height)
        return {"header": ser.header_json(lb.signed_header.header)}

    def commit(self, height=None) -> dict:
        lb = self._verified_block(height)
        return {
            "signed_header": {
                "header": ser.header_json(lb.signed_header.header),
                "commit": ser.commit_json(lb.signed_header.commit),
            },
            "canonical": True,
        }

    def validators(self, height=None) -> dict:
        lb = self._verified_block(height)
        vs = lb.validator_set
        return {
            "block_height": str(lb.height),
            "validators": [ser.validator_json(v) for v in vs.validators],
            "count": str(len(vs.validators)),
            "total": str(len(vs.validators)),
        }

    def status(self) -> dict:
        latest = self._client.latest_trusted()
        return {
            "node_info": {"moniker": "light-proxy"},
            "sync_info": {
                "latest_block_height":
                    str(latest.height) if latest else "0",
                "latest_block_hash":
                    ser.hex_upper(latest.hash()) if latest else "",
            },
        }

    # -- lightserve routes (same wire shape as rpc/core.py) ----------------

    def light_sync(self, trusted_height=None, target_height=None) -> dict:
        """Proxy-side light_sync: verify the target through the
        wrapped light client (the bisection trace lands in its trusted
        store), then serve the pivot-path blocks the store now holds —
        every block handed out went through verify_header."""
        import json

        from ..lightserve import skip_path
        from ..lightserve.codec import encode_payload

        target_lb = self._verified_block(target_height)
        target = target_lb.height
        trusted = int(trusted_height) if trusted_height else 0
        if trusted <= 0:
            first = self._client.store.first_light_block()
            trusted = first.height if first is not None else 1
        path = []
        blocks = []
        for h in skip_path(trusted, target):
            lb = target_lb if h == target \
                else self._client.trusted_light_block(h)
            if lb is None:
                continue
            path.append(h)
            blocks.append(json.loads(encode_payload(
                h, lb.signed_header.header, lb.signed_header.commit,
                lb.validator_set)))
        self._light_requests += 1
        self._light_headers += len(path)
        return {
            "trusted_height": str(trusted),
            "target_height": str(target),
            "path": [str(h) for h in path],
            "light_blocks": blocks,
            "coalesced": False,
        }

    def light_status(self) -> dict:
        latest = self._client.latest_trusted()
        first = self._client.store.first_light_block()
        return {
            "coalescing": False,
            "chain_id": self._client.chain_id,
            "latest_height": str(latest.height) if latest else "0",
            "base_height": str(first.height) if first else "0",
            "requests": str(self._light_requests),
            "headers_served": str(self._light_headers),
            "verify_windows": "0",
            "verify_sigs": "0",
            "failed_heights": "0",
            "coalesced_heights": "0",
            "inflight_heights": "0",
            "planner": {},
        }


_ROUTES = {"header": "header", "commit": "commit",
           "validators": "validators", "status": "status",
           "light_sync": "light_sync", "light_status": "light_status"}
