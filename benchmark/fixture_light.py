"""The chain a light cell's clients sync, made from --seed, and the light
blocks its providers serve.

Two kinds of chain, chosen by what the configuration states:

- a configuration with `valset_change_per_height` (upstream's
  light-client benchmark: `genMockNodeWithKeys(chainID, 1000, 100, 1,
  bTime)`) gets a CHURN chain, grown here: at every height the validator
  that joined longest ago leaves and a fresh key joins, through the
  source node's own executor - two `val:` transactions of the kvstore
  app a block, `state.update`, `ValidatorSet.update_with_change_set` -
  so the set in force changes at every height from 3 on (an update of
  block h is in force at h + 2).  Every validator in force signs every
  commit; blocks are `block_time_s` apart.
- any other configuration gets fixture.build_chain's chain, untouched.

The light blocks are read from the source's stores (header, the commit
of that height, the validator set the state store kept for it): real
blocks behind the in-memory provider.  The fixture keeps its own account
of which keys are in force at which height, in joining order and as raw
bytes, for the reference: the reference is never handed the program's
ValidatorSet.
"""

from __future__ import annotations

import base64
import dataclasses
import time

from benchmark import fixture


@dataclasses.dataclass
class LightChain:
    config: dict
    seed: int
    genesis: object
    src: object
    n_blocks: int            # heights a light client can be served
    power: int
    grow_s: float
    pubkeys: list            # raw 32-byte keys, in joining order
    churn: int               # validators replaced a height (0: none)
    n_vals: int

    def keys_at(self, height: int) -> list:
        """The raw keys in force at `height`, in joining order."""
        if not self.churn:
            return self.pubkeys
        first = max(0, height - 2) * self.churn
        return self.pubkeys[first:first + self.n_vals]


def build(config: dict, seed: int) -> LightChain:
    if "valset_change_per_height" not in config:
        c = fixture.build_chain(config, seed)
        return LightChain(config, seed, c.genesis, c.src, c.n_blocks,
                          int(config["power"]), c.grow_s, c.pubkeys, 0,
                          int(config["validators"]))
    return _build_churn(config, seed)


def _build_churn(config: dict, seed: int) -> LightChain:
    from cometbft_tpu.simnet import SimNetwork, SimNode
    from cometbft_tpu.simnet.node import GENESIS_TIME
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    t0 = time.perf_counter()
    n_vals = int(config["validators"])
    n_blocks = int(config["chain_blocks"])
    churn = int(config["valset_change_per_height"])
    power = int(config["power"])
    # one block past the target, as build_chain: the commit of height h
    # is read from block h + 1
    grown = n_blocks + 1
    signers = [fixture._Signer(fixture._seed_bytes(f"val-{i}", seed))
               for i in range(n_vals + churn * grown)]
    genesis = GenesisDoc(
        chain_id=str(config.get("chain_id", "benchmark-chain")),
        genesis_time=GENESIS_TIME,
        validators=[GenesisValidator(pub_key=s.pub, power=power)
                    for s in signers[:n_vals]])
    src = SimNode("src", genesis, SimNetwork(seed=seed & 0x7FFFFFFF),
                  seed=seed & 0x7FFFFFFF, app=fixture.make_app(config))
    _grow(src, signers, n_vals, churn, power, grown,
          int(config["block_time_s"]) * 1_000_000_000)
    return LightChain(config, seed, genesis, src, n_blocks, power,
                      time.perf_counter() - t0,
                      [s.pub.bytes() for s in signers], churn, n_vals)


def _val_tx(signer, power: int) -> bytes:
    return b"val:" + base64.b64encode(signer.pub.bytes()) \
        + b"!" + str(power).encode()


def _grow(node, signers, n_vals: int, churn: int, power: int,
          n_blocks: int, time_step_ns: int) -> None:
    """fixture.grow_chain with the block's transactions replaced by the
    height's validator updates: block h takes out the `churn` validators
    that joined longest ago and brings in as many fresh keys."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, BlockID, ExtendedCommit, ExtendedCommitSig)
    from cometbft_tpu.types.part_set import PartSet

    state = node.state_store.load()
    by_addr = {s.pub.address(): s for s in signers}
    last_ext = ExtendedCommit()
    for h in range(1, n_blocks + 1):
        out = signers[(h - 1) * churn:h * churn]
        new = signers[n_vals + (h - 1) * churn:n_vals + h * churn]
        for tx in [_val_tx(s, 0) for s in out] \
                + [_val_tx(s, power) for s in new]:
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


def light_blocks(chain: LightChain, top: int) -> dict:
    """{height: LightBlock} of heights 1..top, from the source's
    stores."""
    from cometbft_tpu.light.types import LightBlock, SignedHeader

    src = chain.src
    out = {}
    for h in range(1, top + 1):
        commit = src.block_store.load_block_commit(h) \
            or src.block_store.load_seen_commit(h)
        out[h] = LightBlock(
            SignedHeader(src.block_store.load_block_meta(h).header,
                         commit),
            src.state_store.load_validators(h))
    return out
