"""Which device programs a light client's pass dispatches: the hint that
programs.ensure builds ahead in a light cell (programs.py says what a
hint is, and what a stale one costs)."""

from __future__ import annotations


def expected_programs(signers: dict, batches: list) -> list[tuple]:
    """(kind, K, N) of each program, in the order a pass first needs
    them.  signers: {height: the raw keys whose signatures
    verify_commit_light counts there, in the set's order}; batches:
    [(first height, last height)] of each RLC batch a pass makes, the
    trust root's own commit first.  Widths from ops/ed25519.pad_width
    over the chain's own signers, never from a guess; kinds from the
    A-table cache's own rule: an A side (the batch's distinct keys in
    first-seen order, as pack_rlc lays them out) stays on the fused
    program at its first sighting, builds its tables at the second and
    hits from then on."""
    from cometbft_tpu.crypto.ed25519 import ATableCache
    from cometbft_tpu.ops import ed25519 as dev

    seen: set = set()
    built: set = set()
    out = []
    for first, last in batches:
        keys: dict = {}
        n_sigs = 0
        for h in range(first, last + 1):
            n_sigs += len(signers[h])
            for pk in signers[h]:
                keys.setdefault(pk)
        k, n = dev.pad_width(1 + len(keys)), dev.pad_width(n_sigs)
        a_side = tuple(keys)
        if k < ATableCache.MIN_K or a_side not in seen:
            seen.add(a_side)
            out.append(("ed25519_rlc", k, n))
            continue
        if a_side not in built:
            built.add(a_side)
            out.append(("ed25519_a_tables", k))
        out.append(("ed25519_rlc_cached", k, n))
    return list(dict.fromkeys(out))
