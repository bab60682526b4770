"""Operator CLI (reference cmd/cometbft/main.go + commands/).

    python -m cometbft_tpu.cmd.main --home ~/.cometbft-tpu init
    python -m cometbft_tpu.cmd.main --home ~/.cometbft-tpu start
    ... show-node-id | show-validator | gen-node-key | version |
        unsafe-reset-all | replay
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys

SOFTWARE_VERSION = "0.1.0-tpu"
DEFAULT_HOME = os.path.expanduser("~/.cometbft-tpu")


def _load_config(home: str):
    from ..config import load_config
    cfg = load_config(home)
    cfg.base.root_dir = home
    return cfg


def cmd_init(args) -> int:
    """commands/init.go InitFilesCmd."""
    from ..config import write_config_file
    from ..node import init_files
    cfg = _load_config(args.home)
    genesis = init_files(cfg, chain_id=args.chain_id)
    write_config_file(os.path.join(args.home, "config", "config.toml"),
                      cfg)
    print(f"Initialized node in {args.home} "
          f"(chain_id={genesis.chain_id})")
    return 0


def cmd_start(args) -> int:
    """commands/run_node.go NewRunNodeCmd."""
    from ..node import Node
    from ..ops import compile_hook
    # a restarted node must not recompile its verify programs for
    # minutes: keep them in the persistent cache
    compile_hook.ensure_compile_cache()
    cfg = _load_config(args.home)
    if args.proxy_app:
        cfg.base.abci = args.proxy_app
    if args.p2p_laddr:
        cfg.p2p.laddr = args.p2p_laddr
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    if args.persistent_peers:
        cfg.p2p.persistent_peers = args.persistent_peers

    node = Node(cfg, block_sync=args.block_sync)
    node.start()
    print(f"Node started: p2p={node.p2p_addr} rpc={node.rpc_addr}")

    stop = {"flag": False}

    def handle(sig, frame):
        stop["flag"] = True

    signal.signal(signal.SIGINT, handle)
    signal.signal(signal.SIGTERM, handle)
    try:
        while not stop["flag"]:
            node.wait(0.5)
    finally:
        node.stop()
    return 0


def cmd_show_node_id(args) -> int:
    from ..p2p.key import NodeKey
    cfg = _load_config(args.home)
    print(NodeKey.load_or_gen(cfg.node_key_file()).id)
    return 0


def cmd_show_validator(args) -> int:
    from ..privval import FilePV
    cfg = _load_config(args.home)
    pv = FilePV.load_or_generate(cfg.priv_validator_key_file(),
                                 cfg.priv_validator_state_file())
    import base64
    print(json.dumps({
        "type": "tendermint/PubKeyEd25519",
        "value": base64.b64encode(pv.get_pub_key().bytes()).decode(),
    }))
    return 0


def cmd_gen_node_key(args) -> int:
    from ..crypto import ed25519
    from ..p2p.key import NodeKey
    nk = NodeKey(ed25519.PrivKey.generate())
    print(nk.id)
    return 0


def cmd_unsafe_reset_all(args) -> int:
    """commands/reset.go: wipe data, keep the validator key."""
    cfg = _load_config(args.home)
    data_dir = cfg.db_dir()
    if os.path.isdir(data_dir):
        shutil.rmtree(data_dir)
    os.makedirs(data_dir, exist_ok=True)
    from ..privval import FilePV
    if os.path.exists(cfg.priv_validator_key_file()):
        pv = FilePV.load(cfg.priv_validator_key_file(),
                         cfg.priv_validator_state_file())
        pv.reset()
    print(f"Reset {data_dir}")
    return 0


def cmd_rollback(args) -> int:
    """commands/rollback.go: revert state one height (--hard also
    deletes the block) to recover from app-hash divergence."""
    cfg = _load_config(args.home)
    from ..state.rollback import RollbackError, rollback_state
    from ..state.store import StateStore
    from ..store.blockstore import BlockStore
    from ..store.kv import open_db
    backend = cfg.base.db_backend
    block_store = BlockStore(
        open_db(backend, os.path.join(cfg.db_dir(), "blockstore.db")))
    state_store = StateStore(
        open_db(backend, os.path.join(cfg.db_dir(), "state.db")))
    try:
        height, app_hash = rollback_state(state_store, block_store,
                                          remove_block=args.hard)
    except RollbackError as e:
        print(f"rollback failed: {e}", file=sys.stderr)
        return 1
    print(f"Rolled back state to height {height} and hash "
          f"{app_hash.hex().upper()}")
    return 0


def cmd_gen_validator(args) -> int:
    """commands/gen_validator.go: print a fresh validator key."""
    import base64

    from ..crypto.ed25519 import PrivKey
    priv = PrivKey.generate()
    pub = priv.pub_key()
    print(json.dumps({
        "address": pub.address().hex().upper(),
        "pub_key": {"type": "tendermint/PubKeyEd25519",
                    "value": base64.b64encode(pub.bytes()).decode()},
        "priv_key": {"type": "tendermint/PrivKeyEd25519",
                     "value": base64.b64encode(priv.bytes()).decode()},
    }, indent=2))
    return 0


def cmd_inspect(args) -> int:
    """internal/inspect/inspect.go:51: serve RPC over the stores of a
    crashed/stopped node WITHOUT running consensus."""
    from ..rpc.core import Environment
    from ..rpc.server import RPCServer
    from ..state.store import StateStore
    from ..store.blockstore import BlockStore
    from ..store.kv import open_db
    from ..types.genesis import GenesisDoc

    cfg = _load_config(args.home)
    backend = cfg.base.db_backend
    env = Environment(
        state_store=StateStore(open_db(
            backend, os.path.join(cfg.db_dir(), "state.db"))),
        block_store=BlockStore(open_db(
            backend, os.path.join(cfg.db_dir(), "blockstore.db"))),
        genesis=GenesisDoc.from_file(cfg.genesis_file())
        if os.path.exists(cfg.genesis_file()) else None,
        config=cfg)
    if cfg.tx_index.indexer == "kv":
        from ..state.indexer import BlockIndexer, TxIndexer
        env.tx_indexer = TxIndexer(open_db(
            backend, os.path.join(cfg.db_dir(), "tx_index.db")))
        env.block_indexer = BlockIndexer(open_db(
            backend, os.path.join(cfg.db_dir(), "block_index.db")))
    addr = (args.rpc_laddr or cfg.rpc.laddr).replace("tcp://", "")
    server = RPCServer(env, addr)
    server.start()
    print(f"Inspect RPC serving on {server.bound_addr} (no consensus); "
          "Ctrl-C to stop")
    try:
        signal.pause()
    except KeyboardInterrupt:
        pass
    server.stop()
    return 0


def cmd_light(args) -> int:
    """commands/light.go: verifying RPC proxy over an untrusted node."""
    from ..light.client import Client, TrustOptions
    from ..light.provider import HttpProvider
    from ..light.proxy import LightProxy

    if not args.trusted_height or not args.trusted_hash:
        print("--trusted-height and --trusted-hash are required",
              file=sys.stderr)
        return 1
    def _norm(addr: str) -> str:
        return addr if "://" in addr else "http://" + addr

    primary = HttpProvider(args.chain_id, _norm(args.primary))
    witnesses = [HttpProvider(args.chain_id, _norm(w))
                 for w in (args.witnesses.split(",")
                           if args.witnesses else []) if w]
    client = Client(
        args.chain_id,
        TrustOptions(period_ns=int(args.trust_period * 1e9),
                     height=int(args.trusted_height),
                     hash=bytes.fromhex(args.trusted_hash)),
        primary, witnesses)
    proxy = LightProxy(client, args.laddr)
    proxy.start()
    print(f"Light proxy serving verified RPC on {proxy.bound_addr}; "
          "Ctrl-C to stop")
    try:
        signal.pause()
    except KeyboardInterrupt:
        pass
    proxy.stop()
    return 0


def cmd_testnet(args) -> int:
    """commands/testnet.go: generate N validator homes with a shared
    genesis and fully-meshed persistent peers."""
    from ..config import load_config, write_config_file
    from ..p2p.key import NodeKey
    from ..privval import FilePV
    from ..types.genesis import GenesisDoc, GenesisValidator
    from ..types.timestamp import Timestamp

    n = args.v
    out = args.o or os.path.join(args.home, "testnet")
    chain_id = args.chain_id or "chain-%s" % os.urandom(3).hex()
    homes, validators, node_ids = [], [], []
    for i in range(n):
        home = os.path.join(out, f"{args.node_dir_prefix}{i}")
        cfg = load_config(home)
        cfg.base.root_dir = home
        cfg.ensure_dirs()
        pv = FilePV.load_or_generate(cfg.priv_validator_key_file(),
                                     cfg.priv_validator_state_file())
        key = NodeKey.load_or_gen(cfg.node_key_file())
        homes.append((home, cfg))
        node_ids.append(key.id)
        validators.append(GenesisValidator(pub_key=pv.get_pub_key(),
                                           power=1))
    genesis = GenesisDoc(chain_id=chain_id, genesis_time=Timestamp.now(),
                         validators=validators)
    base_p2p, base_rpc = args.starting_port, args.starting_port + 1000
    peers = ",".join(
        f"{node_ids[i]}@127.0.0.1:{base_p2p + i}" for i in range(n))
    for i, (home, cfg) in enumerate(homes):
        genesis.save_as(cfg.genesis_file())
        cfg.p2p.laddr = f"tcp://0.0.0.0:{base_p2p + i}"
        cfg.rpc.laddr = f"tcp://0.0.0.0:{base_rpc + i}"
        cfg.p2p.persistent_peers = ",".join(
            p for j, p in enumerate(peers.split(",")) if j != i)
        write_config_file(os.path.join(home, "config", "config.toml"),
                          cfg)
    print(f"Generated {n} node homes under {out} (chain_id={chain_id})")
    return 0


def cmd_compact_db(args) -> int:
    """commands/compact.go analog: VACUUM the sqlite stores."""
    import sqlite3
    cfg = _load_config(args.home)
    n = 0
    for name in os.listdir(cfg.db_dir()):
        if not name.endswith(".db"):
            continue
        path = os.path.join(cfg.db_dir(), name)
        try:
            conn = sqlite3.connect(path)
            conn.execute("VACUUM")
            conn.close()
            n += 1
        except sqlite3.DatabaseError as e:
            print(f"skip {name}: {e}", file=sys.stderr)
    print(f"Compacted {n} databases in {cfg.db_dir()}")
    return 0


def cmd_version(args) -> int:
    print(SOFTWARE_VERSION)
    return 0


def cmd_reindex_event(args) -> int:
    """commands/reindex_event.go analog: rebuild the tx/block event
    indexes from the block store + stored FinalizeBlockResponses
    (recovers from indexer corruption or an indexer=null era)."""
    from ..abci import types as at
    from ..state.indexer import BlockIndexer, TxIndexer
    from ..state.store import StateStore
    from ..store.blockstore import BlockStore
    from ..store.kv import open_db
    from ..types import events as ev

    cfg = _load_config(args.home)
    backend = cfg.base.db_backend
    block_store = BlockStore(open_db(
        backend, os.path.join(cfg.db_dir(), "blockstore.db")))
    state_store = StateStore(open_db(
        backend, os.path.join(cfg.db_dir(), "state.db")))
    tx_indexer = TxIndexer(open_db(
        backend, os.path.join(cfg.db_dir(), "tx_index.db")))
    block_indexer = BlockIndexer(open_db(
        backend, os.path.join(cfg.db_dir(), "block_index.db")))

    base = max(block_store.base(), 1)
    height = block_store.height()
    start = args.start_height or base
    end = args.end_height or height
    if start < base or end > height or start > end:
        print(f"height range [{start},{end}] outside stored "
              f"[{base},{height}]", file=sys.stderr)
        return 1
    n_blocks = n_txs = 0
    for h in range(start, end + 1):
        block = block_store.load_block(h)
        raw = state_store.load_finalize_block_response(h)
        if block is None or raw is None:
            print(f"skip height {h}: missing block or results",
                  file=sys.stderr)
            continue
        fin = at.FinalizeBlockResponse.from_proto(raw)
        # the same composite maps the live event bus feeds the indexers
        bev = ev.block_events_map(h, fin.events)
        bev.setdefault(ev.EVENT_TYPE_KEY, []).append(
            ev.EVENT_NEW_BLOCK_EVENTS)
        block_indexer.index(h, bev)
        n_blocks += 1
        for i, tx in enumerate(block.data.txs):
            result = fin.tx_results[i] if i < len(fin.tx_results) else None
            tev = ev.tx_events_map(h, bytes(tx),
                                   getattr(result, "events", None))
            tev.setdefault(ev.EVENT_TYPE_KEY, []).append(ev.EVENT_TX)
            tx_indexer.index(h, i, bytes(tx), result, tev)
            n_txs += 1
    print(f"Reindexed {n_blocks} blocks / {n_txs} txs "
          f"over heights [{start},{end}]")
    if n_blocks == 0:
        print("nothing reindexed (blocks or results missing for the "
              "whole range)", file=sys.stderr)
        return 1
    return 0


def cmd_debug_dump(args) -> int:
    """commands/debug (dump mode) analog: snapshot a running node's
    observable state over RPC into a directory — status, net_info,
    consensus state dumps, unconfirmed txs, optionally at intervals."""
    import json as _json
    import time as _time
    import urllib.request

    os.makedirs(args.output_directory, exist_ok=True)
    routes = ["status", "net_info", "dump_consensus_state",
              "consensus_state", "num_unconfirmed_txs", "abci_info"]

    def snapshot(tag: str) -> None:
        out = {}
        for r in routes:
            url = f"http://{args.rpc_laddr.replace('tcp://', '')}/{r}"
            try:
                with urllib.request.urlopen(url, timeout=10) as resp:
                    body = _json.loads(resp.read())
                    out[r] = body.get("result") or body
            except Exception as e:
                out[r] = {"error": str(e)}
        path = os.path.join(args.output_directory, f"dump_{tag}.json")
        with open(path, "w") as f:
            _json.dump(out, f, indent=1)
        print(f"wrote {path}")

    # --frequency alone means "snapshot forever at that interval";
    # --count bounds the number of snapshots (1 snapshot by default)
    count = args.count if args.count > 1 else \
        (2**62 if args.frequency else 1)
    i = 0
    while i < count:
        snapshot(f"{int(_time.time())}_{i}")
        i += 1
        if i < count:
            _time.sleep(max(args.frequency, 1.0))
    return 0


def cmd_debug_kill(args) -> int:
    """commands/debug/kill.go: aggregate a running node's state
    (status, net_info, consensus state over RPC; WAL + config copies)
    into a zip archive, then kill the process with SIGABRT."""
    import json as _json
    import shutil
    import signal as _signal
    import tempfile
    import urllib.request
    import zipfile

    cfg = _load_config(args.home)
    tmp = tempfile.mkdtemp(prefix="cometbft_debug_")
    try:
        for route, fname in (("status", "status.json"),
                             ("net_info", "net_info.json"),
                             ("dump_consensus_state",
                              "consensus_state.json")):
            url = (f"http://{args.rpc_laddr.replace('tcp://', '')}"
                   f"/{route}")
            try:
                with urllib.request.urlopen(url, timeout=10) as resp:
                    body = _json.loads(resp.read())
                payload = body.get("result") or body
            except Exception as e:
                payload = {"error": str(e)}
            with open(os.path.join(tmp, fname), "w") as f:
                _json.dump(payload, f, indent=1)

        wal_path = cfg.wal_file()
        if os.path.exists(wal_path):
            shutil.copy2(wal_path, os.path.join(tmp, "cs.wal"))
        conf_dir = os.path.join(cfg.base.root_dir, "config")
        if os.path.isdir(conf_dir):
            shutil.copytree(conf_dir, os.path.join(tmp, "config"),
                            dirs_exist_ok=True)

        # SIGABRT, like the reference (stacktrace-on-abort semantics;
        # Python nodes dump a traceback via faulthandler when enabled)
        killed = True
        try:
            os.kill(args.pid, _signal.SIGABRT)
        except ProcessLookupError:
            killed = False
            print(f"process {args.pid} not found", file=sys.stderr)

        with zipfile.ZipFile(args.output_file, "w",
                             zipfile.ZIP_DEFLATED) as zf:
            for root, _, files in os.walk(tmp):
                for fn in files:
                    full = os.path.join(root, fn)
                    zf.write(full, os.path.relpath(full, tmp))
        print(f"wrote {args.output_file}")
        return 0 if killed else 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def cmd_replay(args) -> int:
    """commands/replay.go: replay the WAL through a fresh consensus
    state (console mode prints each message)."""
    cfg = _load_config(args.home)
    from ..consensus.wal import WAL
    wal = WAL(cfg.wal_file())
    n = 0
    for timed in wal.replay():
        n += 1
        if args.console:
            print(type(timed.msg).__name__, timed.msg)
    print(f"replayed {n} WAL messages")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="cometbft-tpu",
        description="TPU-native BFT state-machine replication engine")
    parser.add_argument("--home", default=DEFAULT_HOME,
                        help="node home directory")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("init", help="initialize config/keys/genesis")
    p.add_argument("--chain-id", default="")
    p.set_defaults(fn=cmd_init)

    p = sub.add_parser("start", help="run the node")
    p.add_argument("--proxy-app", default="",
                   help="ABCI app address or 'kvstore'")
    p.add_argument("--p2p-laddr", default="")
    p.add_argument("--rpc-laddr", default="")
    p.add_argument("--persistent-peers", default="")
    p.add_argument("--block-sync", action="store_true")
    p.set_defaults(fn=cmd_start)

    for name, fn in (("show-node-id", cmd_show_node_id),
                     ("show-validator", cmd_show_validator),
                     ("gen-node-key", cmd_gen_node_key),
                     ("unsafe-reset-all", cmd_unsafe_reset_all),
                     ("version", cmd_version)):
        p = sub.add_parser(name)
        p.set_defaults(fn=fn)

    p = sub.add_parser("replay", help="replay the consensus WAL")
    p.add_argument("--console", action="store_true")
    p.set_defaults(fn=cmd_replay)

    p = sub.add_parser("rollback",
                       help="roll chain state back one height")
    p.add_argument("--hard", action="store_true",
                   help="also delete the invalidated block")
    p.set_defaults(fn=cmd_rollback)

    p = sub.add_parser("gen-validator",
                       help="print a fresh validator keypair")
    p.set_defaults(fn=cmd_gen_validator)

    p = sub.add_parser("inspect",
                       help="serve RPC over the stores, no consensus")
    p.add_argument("--rpc-laddr", default="")
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser("light", help="light-verifying RPC proxy")
    p.add_argument("chain_id")
    p.add_argument("--primary", required=True,
                   help="primary full-node RPC address (host:port)")
    p.add_argument("--witnesses", default="",
                   help="comma-separated witness RPC addresses")
    p.add_argument("--trusted-height", type=int, default=0)
    p.add_argument("--trusted-hash", default="")
    p.add_argument("--trust-period", type=float, default=168 * 3600,
                   help="trusting period in seconds")
    p.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    p.set_defaults(fn=cmd_light)

    p = sub.add_parser("testnet", help="generate a local testnet")
    p.add_argument("--v", type=int, default=4,
                   help="number of validators")
    p.add_argument("--o", default="", help="output directory")
    p.add_argument("--chain-id", default="")
    p.add_argument("--node-dir-prefix", default="node")
    p.add_argument("--starting-port", type=int, default=26656)
    p.set_defaults(fn=cmd_testnet)

    p = sub.add_parser("reindex-event",
                       help="rebuild tx/block event indexes from the "
                            "block store")
    p.add_argument("--start-height", type=int, default=0)
    p.add_argument("--end-height", type=int, default=0)
    p.set_defaults(fn=cmd_reindex_event)

    p = sub.add_parser(
        "debug", help="debug a running node (dump | kill)")
    dsub = p.add_subparsers(dest="debug_mode", required=True)
    d = dsub.add_parser("dump", help="snapshot node state over RPC")
    d.add_argument("--rpc-laddr", default="tcp://127.0.0.1:26657")
    d.add_argument("--output-directory", default="debug-dump")
    d.add_argument("--frequency", type=float, default=0.0,
                   help="seconds between snapshots (0 = one snapshot)")
    d.add_argument("--count", type=int, default=1)
    d.set_defaults(fn=cmd_debug_dump)
    k = dsub.add_parser(
        "kill", help="archive node state, then SIGABRT the process")
    k.add_argument("pid", type=int)
    k.add_argument("output_file")
    k.add_argument("--rpc-laddr", default="tcp://127.0.0.1:26657")
    k.set_defaults(fn=cmd_debug_kill)

    p = sub.add_parser("compact-db", help="compact the sqlite stores")
    p.set_defaults(fn=cmd_compact_db)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
