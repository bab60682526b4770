"""The plain reference of a `faulty` cell: what a node that catches up
from peers of which some forge must have done, from the fixture's own
account of what was forged and the run's record.

It imports nothing of the program (benchmark/reference.py, which it
uses, does not either).  Its inputs are plain bytes, numbers and names.
Beside reference.py's counts over the stored chain it holds the run to
the configuration's guarantees under forgery: every forged signature is
one OpenSSL rejects over the true sign-bytes, which this file builds
itself; none is stored; each was rejected, by a reject that names the
height whose commit held it; the peers dropped are the two suppliers of
each rejected pair, both of them, and nobody else; nothing is asked for
again but what
a dropped peer had been asked for; the device localised each reject
(one RLC fallback a forged commit, the per-signature program judging
the whole window).

Every number compared is a count and its limit is 0.
"""

from __future__ import annotations

from benchmark import reference


def check_forgeries(chain_id: str, pubkeys: list, powers: list,
                    forgeries: list) -> dict:
    """forgeries: one dict a forged signature - height, round,
    block_hash, parts_total, parts_hash (of the commit that held it),
    index (in the set's order), seconds, nanos, forged, and stored: the
    signatures the node's stores hold at that height and index (the
    commit as the block above brought it, the commit the block was
    stored with, and the LastCommit of the block above)."""
    order = reference.validator_order(pubkeys, powers)
    out = {"forged_ref_accepted": 0, "forged_stored": 0}
    for f in forgeries:
        msg = reference.vote_sign_bytes(
            chain_id, f["height"], f["round"], f["block_hash"],
            f["parts_total"], f["parts_hash"], f["seconds"], f["nanos"])
        if reference.verify(pubkeys[order[f["index"]]], msg, f["forged"]):
            out["forged_ref_accepted"] += 1
        out["forged_stored"] += sum(1 for s in f["stored"]
                                    if s == f["forged"])
    return out


def supplier_before(served: list, height: int, t: float):
    """Who had last been asked for `height` before time t."""
    who = None
    for at, peer, h in served:
        if h == height and at <= t:
            who = peer
    return who


def check_pass(p: dict) -> dict:
    """One pass's record: forgeries [{number, peer, at, block_height,
    commit_height}], served [(time, peer, height)], rejects [{start,
    end, height}] (the program's blocksync.reject spans), dialled and
    connected_at_end (peer names), dropped [(time, peer)] (when the
    node stopped a peer for an error)."""
    out = {"forged_not_rejected": 0, "rejects_misnamed": 0,
           "peers_dropped_wrongly": 0, "forgers_kept": 0,
           "suppliers_kept": 0, "blocks_refetched_beyond_pair": 0}
    dropped = set(p["dialled"]) - set(p["connected_at_end"])
    may_go: set = set()
    rejects = sorted(p["rejects"], key=lambda r: r["start"])
    taken: set = set()
    commits = {}
    for f in p["forgeries"]:
        commits.setdefault((f["commit_height"], f["block_height"]), f)
    for (low, high), f in sorted(commits.items()):
        # the reject of this forged commit: the first, after the block
        # was handed out, that names the commit's height
        hit = next((i for i, r in enumerate(rejects)
                    if i not in taken and r["height"] == low
                    and r["end"] >= f["at"]), None)
        if hit is None:
            out["forged_not_rejected"] += 1
            continue
        taken.add(hit)
        start = rejects[hit]["start"]
        may_go.add(f["peer"])
        lower = supplier_before(p["served"], low, start)
        if lower is not None:
            may_go.add(lower)
        if f["peer"] in p["connected_at_end"]:
            out["forgers_kept"] += 1
        # upstream stops BOTH peers: whoever supplied the block the
        # forged commit is for goes with the forger, by the time the
        # reject is over (it may have gone before, as another's)
        if lower not in (None, f["peer"]) and not any(
                who == lower and at <= rejects[hit]["end"]
                for at, who in p["dropped"]):
            out["suppliers_kept"] += 1
    out["rejects_misnamed"] = len(rejects) - len(taken)
    out["peers_dropped_wrongly"] = len(dropped - may_go)
    # a height is asked for again only where everyone asked before has
    # been dropped: the pair's two suppliers, and what a dropped peer
    # still owed (its answer is lost with the connection)
    asked: dict = {}
    for _, peer, h in sorted(p["served"]):
        if any(q not in dropped for q in asked.get(h, ())):
            out["blocks_refetched_beyond_pair"] += 1
        asked.setdefault(h, []).append(peer)
    return out


def check_counters(c: dict) -> dict:
    """The window's counters: forged_commits (handed out, by the
    fixture), rejects_wanted (the traffic's count a window times the
    windows of the passes), rlc_fallbacks, windows_rejected,
    blocks_refetched, persig_signatures, window_signatures (of one
    verify window)."""
    want = c["rejects_wanted"]
    got = int(c["rlc_fallbacks"])
    pairs = int(c["windows_rejected"])
    return {
        "rejects_short": max(0, want - got),
        "rejects_beyond": max(0, got - want),
        "blocks_refetched_beyond_pair": max(
            0, int(c["blocks_refetched"]) - 2 * pairs),
        "persig_sigs_short": max(
            0, got * c["window_signatures"] - int(c["persig_signatures"])),
    }
