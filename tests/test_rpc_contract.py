"""RPC contract conformance: every route the server dispatches is
declared in rpc/openapi.yaml, and every declared route's LIVE response
validates against its schema (the reference ships the same discipline
as rpc/openapi/openapi.yaml + a Dredd run, dredd.yml).

The spec's x-contract extension drives the calls: example params with
$var placeholders resolved against the running chain (a committed tx's
hash/height, fresh mempool txs, block hashes).
"""

import base64
import json
import os
import urllib.parse
import urllib.request

import pytest
import yaml

from cometbft_tpu.config import test_config as _tcfg
from cometbft_tpu.libs.metrics import Registry, StateMetrics
from cometbft_tpu.node import Node, init_files
from cometbft_tpu.rpc.core import PRIVILEGED_ROUTES, ROUTES

from tests.test_consensus import wait_for_height
from tests.test_valset_resume import _afresh

SPEC_PATH = os.path.join(os.path.dirname(__file__), os.pardir,
                         "cometbft_tpu", "rpc", "openapi.yaml")


def load_spec():
    with open(SPEC_PATH) as f:
        return yaml.safe_load(f)


# -- a small JSON-schema validator (the subset the spec uses) -------------

class SchemaError(AssertionError):
    pass


def _resolve(schema, spec):
    if "$ref" in schema:
        ref = schema["$ref"]
        assert ref.startswith("#/"), ref
        node = spec
        for part in ref[2:].split("/"):
            node = node[part]
        return node
    return schema


def validate(instance, schema, spec, path="$"):
    schema = _resolve(schema, spec)
    if instance is None:
        if schema.get("nullable"):
            return
        if schema.get("type") is None and "allOf" not in schema:
            return                      # untyped: anything goes
        raise SchemaError(f"{path}: null not allowed by {schema}")
    for sub in schema.get("allOf", []):
        validate(instance, sub, spec, path)
    typ = schema.get("type")
    if typ == "object":
        if not isinstance(instance, dict):
            raise SchemaError(f"{path}: expected object, got "
                              f"{type(instance).__name__}")
        props = schema.get("properties", {})
        for req in schema.get("required", []):
            if req not in instance:
                raise SchemaError(f"{path}: missing required {req!r} "
                                  f"(have {sorted(instance)})")
        if schema.get("additionalProperties") is False:
            extra = set(instance) - set(props)
            if extra:
                raise SchemaError(f"{path}: unexpected keys {extra}")
        for key, sub in props.items():
            if key in instance:
                validate(instance[key], sub, spec, f"{path}.{key}")
    elif typ == "array":
        if not isinstance(instance, list):
            raise SchemaError(f"{path}: expected array")
        sub = schema.get("items")
        if sub:
            for i, item in enumerate(instance):
                validate(item, sub, spec, f"{path}[{i}]")
    elif typ == "string":
        if not isinstance(instance, str):
            raise SchemaError(f"{path}: expected string, got "
                              f"{instance!r}")
    elif typ == "integer":
        if not isinstance(instance, int) or isinstance(instance, bool):
            raise SchemaError(f"{path}: expected integer, got "
                              f"{instance!r}")
    elif typ == "number":
        if not isinstance(instance, (int, float)) \
                or isinstance(instance, bool):
            raise SchemaError(f"{path}: expected number, got "
                              f"{instance!r}")
    elif typ == "boolean":
        if not isinstance(instance, bool):
            raise SchemaError(f"{path}: expected boolean, got "
                              f"{instance!r}")
    if "enum" in schema and instance not in schema["enum"]:
        raise SchemaError(f"{path}: {instance!r} not in {schema['enum']}")


# -- live node ------------------------------------------------------------

@pytest.fixture(scope="module")
def contract_node(tmp_path_factory):
    home = str(tmp_path_factory.mktemp("contract-home"))
    cfg = _tcfg(home)
    cfg.rpc.privileged_laddr = "127.0.0.1:0"
    init_files(cfg, chain_id="contract-chain")
    n = Node(cfg)
    n.start()
    assert wait_for_height(n.consensus_state, 3, timeout=60)
    yield n
    n.stop()


def _get(addr, method, params, timeout=15.0):
    qs = "&".join(f"{k}={urllib.parse.quote(str(v))}"
                  for k, v in params.items())
    url = f"http://{addr}/{method}" + (f"?{qs}" if qs else "")
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def contract_vars(contract_node):
    """Chain-derived values for the spec's $var placeholders."""
    addr = contract_node.rpc_addr
    tx = b"contract-key=contract-val"
    res = _get(addr, "broadcast_tx_commit",
               {"tx": base64.b64encode(tx).decode()}, timeout=40.0)
    assert "error" not in res or not res["error"], res
    result = res["result"]
    assert result["tx_result"] is not None, result
    height = int(result["height"])
    blk = _get(addr, "block", {"height": height})["result"]
    raw_hash = bytes.fromhex(blk["block_id"]["hash"])
    counter = [0]

    def fresh_tx():
        counter[0] += 1
        raw = b"ck%d=cv%d" % (counter[0], counter[0])
        return base64.b64encode(raw).decode()

    return {
        "$height": str(height),
        "$block_hash_hex": blk["block_id"]["hash"],
        "$block_hash_b64": base64.b64encode(raw_hash).decode(),
        "$tx_hash_hex": result["hash"],
        "$tx_key_hex": b"contract-key".hex(),
        "$fresh_tx_b64": fresh_tx,
    }


def test_spec_covers_every_dispatched_route():
    """The router and the contract cannot drift: every ROUTES /
    PRIVILEGED_ROUTES key has a path in the spec, and vice versa."""
    spec = load_spec()
    spec_routes = {p.lstrip("/") for p in spec["paths"]}
    ws = {"subscribe", "unsubscribe", "unsubscribe_all"}
    dispatched = set(ROUTES) | set(PRIVILEGED_ROUTES) | ws
    assert spec_routes == dispatched, (
        f"spec-only: {spec_routes - dispatched}, "
        f"undocumented: {dispatched - spec_routes}")


def test_every_route_conforms(contract_node, contract_vars):
    """Hit every non-websocket route with its example params and
    validate the result against the declared schema."""
    spec = load_spec()
    pub = contract_node.rpc_addr
    priv = contract_node.privileged_rpc_server.bound_addr
    failures = []
    checked = 0
    for path, methods in spec["paths"].items():
        op = methods["get"]
        contract = op.get("x-contract", {})
        if contract.get("websocket") or contract.get("skip"):
            continue
        params = {}
        for k, v in (contract.get("params") or {}).items():
            if isinstance(v, str) and v.startswith("$"):
                v = contract_vars[v]
                if callable(v):
                    v = v()
            params[k] = v
        addr = priv if contract.get("privileged") else pub
        schema = (op["responses"]["200"]["content"]
                  ["application/json"]["schema"])
        try:
            body = _get(addr, path.lstrip("/"), params,
                        timeout=float(contract.get("timeout", 15)))
            assert body.get("jsonrpc") == "2.0", body
            if body.get("error"):
                raise SchemaError(f"error response: {body['error']}")
            validate(body["result"], schema, spec, path)
            checked += 1
        except Exception as e:
            failures.append(f"{path}: {e}")
    assert not failures, "\n".join(failures)
    assert checked >= 30    # ~all public + privileged HTTP routes


def test_post_envelope_conforms(contract_node):
    """The same contract holds over POSTed JSON-RPC envelopes."""
    spec = load_spec()
    addr = contract_node.rpc_addr
    for method, schema_name in [("status", "StatusResult"),
                                ("abci_info", "ABCIInfoResult"),
                                ("num_unconfirmed_txs",
                                 "NumUnconfirmedTxsResult")]:
        payload = json.dumps({"jsonrpc": "2.0", "id": 7,
                              "method": method, "params": {}}).encode()
        req = urllib.request.Request(
            f"http://{addr}/", data=payload,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=15) as resp:
            body = json.loads(resp.read())
        assert body["id"] == 7 and not body.get("error"), body
        validate(body["result"],
                 {"$ref": f"#/components/schemas/{schema_name}"},
                 spec, method)


def test_validator_rejects_drift():
    """The mini-validator actually bites: shape violations raise."""
    spec = load_spec()
    good = {"n_txs": "0", "total": "0", "total_bytes": "0"}
    validate(good, {"$ref": "#/components/schemas/NumUnconfirmedTxsResult"},
             spec)
    for bad in ({"n_txs": "0", "total": "0"},          # missing required
                {"n_txs": 0, "total": "0", "total_bytes": "0"},  # int64-as-int
                []):                                    # wrong type
        with pytest.raises(SchemaError):
            validate(bad,
                     {"$ref": "#/components/schemas/NumUnconfirmedTxsResult"},
                     spec)


# -- /validators over a 175-validator chain: the answer and its cost ------------

QA_VALS, QA_TOP = 175, 14     # blocks grown; a source serves heights below


@pytest.fixture(scope="module")
def qa_source():
    """A source node holding QA_TOP blocks signed by 175 equal-power
    validators whose set changes at no height (every validators record
    above height 1 is a pointer to it), behind the real RPC server."""
    from benchmark import fixture

    chain = fixture.build_chain(
        {"validators": QA_VALS, "power": 10, "chain_blocks": QA_TOP - 1,
         "txs_per_block": 1, "tx_bytes": 32, "chain_id": "qa-contract"},
        seed=2 ** 31 + 30)
    chain.src.state_store.metrics = StateMetrics(Registry("t"))
    addr = chain.src.start_rpc()
    yield chain, addr
    chain.src.stop()


@pytest.mark.parametrize("height", [3, QA_TOP - 1])
@pytest.mark.parametrize("page", [1, 2])
def test_validators_pages_are_the_fresh_catch_up(qa_source, height, page):
    from cometbft_tpu.rpc import serialize as ser

    chain, addr = qa_source
    # the set as load_validators built it before it kept anything
    vals = _afresh(chain.src.state_store._db, height).validators
    sel = vals[(page - 1) * 100:page * 100]
    want = {"block_height": str(height),
            "validators": [ser.validator_json(v) for v in sel],
            "count": str(len(sel)), "total": str(QA_VALS)}
    assert len(sel) == (100, 75)[page - 1]
    # twice: the second answer is served from where the first one stood
    for _ in (0, 1):
        got = _get(addr, "validators",
                   {"height": height, "page": page, "per_page": 100})
        assert json.dumps(got["result"], sort_keys=True) == \
            json.dumps(want, sort_keys=True)


def test_a_light_sync_costs_one_round_a_height(qa_source, monkeypatch):
    """A fresh light client over HttpProvider asks for the target first
    and then for the heights from its trust root up, two /validators
    pages each: the source restarts the catch-up once, for the target,
    and runs one round a height after it (from the full record at every
    request it would run about n squared); a second client runs no more
    than one round a height."""
    from cometbft_tpu.crypto import batch as cb
    from cometbft_tpu.light.client import SEQUENTIAL, Client, TrustOptions
    from cometbft_tpu.light.provider import HttpProvider
    from cometbft_tpu.light.store import MemoryStore
    from cometbft_tpu.types import validation

    # the client's commits are judged on the host: no device program
    monkeypatch.setattr(cb, "DEVICE_THRESHOLD", 10 ** 9)
    monkeypatch.setattr(validation.DeferredSigBatch, "DEVICE_THRESHOLD",
                        10 ** 9)
    chain, addr = qa_source
    m = chain.src.state_store.metrics
    target = QA_TOP - 1
    n = target - 1
    root = chain.src.block_store.load_block_meta(1).header.hash()

    def counts():
        return (m.validators_catchup_rounds._values.get((), 0.0),
                m.validators_loads._values.get(("resumed",), 0.0))

    def sync():
        store = MemoryStore()
        Client(chain.genesis.chain_id,
               TrustOptions(period_ns=100 * 365 * 86400 * 10 ** 9,
                            height=1, hash=root),
               HttpProvider(chain.genesis.chain_id, f"http://{addr}"),
               trusted_store=store, verification_mode=SEQUENTIAL,
               sequential_batch_size=4
               ).verify_light_block_at_height(target)
        assert store.light_block(target).header.hash() == \
            chain.src.block_store.load_block_meta(target).header.hash()

    # forget what the tests above left: this client finds a store that
    # was never asked
    chain.src.state_store._decoded.clear()
    rounds0, resumed0 = counts()
    sync()
    rounds1, resumed1 = counts()
    assert 0 < rounds1 - rounds0 <= n + (target - 1)
    # of a height's two pages the second is always resumed, and the first
    # too from the second pointer record on; height 2 holds a full set
    # (two loads `stored`), so 2n - 4 and not 2n - 2
    assert resumed1 - resumed0 >= 2 * n - 4
    sync()
    rounds2, resumed2 = counts()
    assert rounds2 - rounds1 <= n
    assert resumed2 - resumed1 >= 2 * n - 4
