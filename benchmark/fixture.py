"""The chain a cell's syncing nodes catch up on, made from --seed.

A copy of simnet.grow_chain / make_sim_genesis and of chip_smoke's
caching signer, kept here because later PRs may change those and may not
change the yardstick.  Three things differ from the originals, none of
them in what the chain is:

- validator keys are derived with OpenSSL (0.03 ms a key) and not with
  the pure-Python keygen (2.4 ms a key: 24 s of every run at 10,000);
- all signers of a height share one timestamp, so the sign-bytes are
  built once a height and not once a validator;
- transactions have the configuration's size (1,024 bytes in the QA
  deployment) and their bytes come from the seed.

Every run grows its chain anew: the driver gives each run of a set its
own seed, so a chain kept on disk would hit only in the second set and
make set-up two-valued.
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
import time


def _seed_bytes(tag: str, seed: int) -> bytes:
    return hashlib.sha256(f"benchmark/{seed}/{tag}".encode()).digest()


class _Signer:
    """A validator key that records each signature it makes as verified
    in the program's verdict cache: the source node checks every
    LastCommit it builds through the batch seam, and verifying the
    fixture's own signatures on the device would add programs to trace
    for nothing a cell measures.  The cache is reset before every pass,
    so nothing recorded here reaches the path under test."""

    def __init__(self, seed32: bytes):
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey)

        from cometbft_tpu.crypto import ed25519

        self._key = Ed25519PrivateKey.from_private_bytes(seed32)
        pub = self._key.public_key().public_bytes_raw()
        self.priv = ed25519.PrivKey(seed32 + pub)
        self.pub = self.priv.pub_key()

    def pub_key(self):
        return self.pub

    def sign(self, msg: bytes) -> bytes:
        from cometbft_tpu.crypto import sigcache

        sig = self._key.sign(msg)
        sigcache.insert(self.pub, msg, sig, True)
        return sig


@dataclasses.dataclass
class Chain:
    config: dict
    seed: int
    net: object
    genesis: object
    src: object
    n_blocks: int            # heights a syncer can complete
    pubkeys: list            # raw 32-byte keys, in genesis order
    grow_s: float


def make_app(config: dict):
    """The configuration's application.  The kvstore app would serialise
    its whole store as a state-sync snapshot at every commit
    (snapshot_interval=1, its default here): with 200 KiB of values a
    block that is quadratic in the chain's depth and would be most of
    `apply`.  No deployment snapshots every block; the configuration
    says how often (0: never)."""
    from cometbft_tpu.apps.kvstore import KVStoreApplication

    every = int(config.get("app_snapshot_interval", 0))
    return KVStoreApplication(snapshot_interval=every or 10 ** 12)


def make_txs(rng: random.Random, height: int, n: int, size: int) -> list:
    """n kvstore transactions ("key=value") of exactly `size` bytes."""
    out = []
    for t in range(n):
        head = f"h{height}t{t}=".encode()
        pad = max(1, size - len(head))
        out.append(head + rng.randbytes((pad + 1) // 2).hex()
                   .encode()[:pad])
    return out


def build_chain(config: dict, seed: int) -> Chain:
    """Genesis and a source node that holds chain_blocks + 1 real
    blocks (a syncer converges one block behind the serving tip).  The
    whole chain exists before anything dials, so verify windows fill."""
    from cometbft_tpu.simnet import SimNetwork, SimNode
    from cometbft_tpu.simnet.node import GENESIS_TIME
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    t0 = time.perf_counter()
    n_vals = int(config["validators"])
    n_blocks = int(config["chain_blocks"])
    net = SimNetwork(seed=seed & 0x7FFFFFFF)
    signers = [_Signer(_seed_bytes(f"val-{i}", seed))
               for i in range(n_vals)]
    genesis = GenesisDoc(
        chain_id=str(config.get("chain_id", "benchmark-chain")),
        genesis_time=GENESIS_TIME,
        validators=[GenesisValidator(pub_key=s.pub,
                                     power=int(config["power"]))
                    for s in signers])
    src = SimNode("src", genesis, net, seed=seed & 0x7FFFFFFF,
                  app=make_app(config))
    grow_chain(src, signers, n_blocks + 1,
               int(config["txs_per_block"]), int(config["tx_bytes"]),
               random.Random(seed))
    return Chain(config, seed, net, genesis, src, n_blocks,
                 [s.pub.bytes() for s in signers],
                 time.perf_counter() - t0)


def grow_chain(node, signers, n_blocks: int, txs_per_block: int,
               tx_bytes: int, rng: random.Random,
               time_step_ns: int = 1_000_000_000) -> None:
    """Extend node's chain by n_blocks through its own executor: every
    commit signature is a real Ed25519 signature over the canonical
    precommit sign-bytes."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, ExtendedCommit, ExtendedCommitSig)
    from cometbft_tpu.types.part_set import PartSet

    state = node.state_store.load()
    by_addr = {s.pub.address(): s for s in signers}
    last_ext = ExtendedCommit()
    h0 = state.last_block_height
    for h in range(h0 + 1, h0 + n_blocks + 1):
        for tx in make_txs(rng, h, txs_per_block, tx_bytes):
            node.mempool.check_tx(tx)
        proposer = state.validators.get_proposer().address
        block = node.block_exec.create_proposal_block(
            h, state, last_ext, proposer)
        parts = PartSet.from_data(block.to_proto())
        bid = BlockID(block.hash(), parts.header)
        ts = block.header.time.add_ns(time_step_ns)
        sb = canonical.vote_sign_bytes(state.chain_id, canonical.PRECOMMIT,
                                       h, 0, bid, ts)
        last_ext = ExtendedCommit(
            height=h, round=0, block_id=bid,
            extended_signatures=[
                ExtendedCommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                  by_addr[v.address].sign(sb))
                for v in state.validators.validators])
        node.block_store.save_block(block, parts, last_ext.to_commit())
        state = node.block_exec.apply_block(state, bid, block)
