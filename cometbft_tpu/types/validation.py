"""Commit verification — THE hot path (types/validation.go analog).

verify_commit / verify_commit_light / verify_commit_light_trusting
reproduce the reference's ignore/count/threshold semantics
(/root/reference/types/validation.go:28,63,129,220-324,333-408) with the
batch routed to the TPU BatchVerifier (crypto/batch.py). Differences by
design:
- the batch threshold is higher than the reference's 2 because the
  device round-trip has fixed cost (crypto/batch.DEVICE_THRESHOLD);
- mixed-keytype commits batch through MixedBatchVerifier instead of
  falling back to per-signature CPU verification (BASELINE.json target).
"""

from __future__ import annotations

import os
from dataclasses import dataclass

from ..crypto import batch as crypto_batch
from ..crypto import sigcache
from .block import (
    BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BlockID, Commit,
)
from .validator_set import ValidatorSet

BATCH_VERIFY_THRESHOLD = 2


@dataclass(frozen=True)
class Fraction:
    numerator: int
    denominator: int


DEFAULT_TRUST_LEVEL = Fraction(1, 3)


class CommitVerificationError(Exception):
    pass


class ErrNotEnoughVotingPowerSigned(CommitVerificationError):
    def __init__(self, got: int, needed: int):
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, "
            f"needed more than {needed}")
        self.got = got
        self.needed = needed


class ErrInvalidSignature(CommitVerificationError):
    pass


def _should_batch_verify(vals: ValidatorSet, commit: Commit) -> bool:
    if len(commit.signatures) < BATCH_VERIFY_THRESHOLD:
        return False
    if vals.all_keys_have_same_type():
        proposer = vals.get_proposer()
        return proposer is not None and proposer.pub_key is not None and \
            crypto_batch.supports_batch_verifier(proposer.pub_key.type())
    # mixed keytypes: our device path handles them (reference refuses,
    # types/validation.go:18)
    return True


class DeferredSigBatch:
    """Cross-commit signature batching: several commit verifications
    collect their signature checks here (host-side structure + voting
    power tallies still run per commit at collect time), then ONE
    device batch verifies them all — the shape behind the light
    client's windowed sequential sync and the blocksync-replay bench.
    The reference has no analog (it verifies one commit at a time,
    validation.go:220); this is the TPU-first reformulation: the batch
    axis spans commits, and pack_rlc's per-pubkey aggregation makes the
    repeated validator set nearly free.
    """

    def __init__(self):
        # (label, context, pubkey, sign_bytes, sig); context is an
        # opaque caller value (e.g. a height) surfaced as
        # .failed_ctx on the raised error for blame attribution
        self._entries: list = []

    def count(self) -> int:
        return len(self._entries)

    def _extend(self, label: str, ctx, entries) -> None:
        for _, val, sign_bytes, sig in entries:
            self._entries.append((label, ctx, val.pub_key, sign_bytes,
                                  sig))

    # Below this many signatures the host fast path wins over a device
    # dispatch (and avoids cold-compiling a fresh batch shape).  The
    # crossover is higher than crypto/batch.DEVICE_THRESHOLD (which
    # gates a SINGLE commit's verify) because deferred windows produce
    # more distinct batch shapes; tunable, never below the batch knob.
    DEVICE_THRESHOLD = max(
        crypto_batch.DEVICE_THRESHOLD,
        int(os.environ.get("COMETBFT_TPU_DEFERRED_THRESHOLD", "128")))

    @staticmethod
    def _fail(label, ctx, sig):
        err = ErrInvalidSignature(
            f"wrong signature in {label}: {sig.hex()}")
        err.failed_ctx = ctx
        return err

    def verify(self) -> None:
        """Raises ErrInvalidSignature naming the first failing commit
        (with .failed_ctx carrying that commit's context value)."""
        if not self._entries:
            return
        self._entries, entries = [], self._entries
        # verdict-cache partition: triples the process already proved
        # (the previous window's commits, the live vote stream) skip
        # the dispatch entirely; a cached NEGATIVE raises the same
        # error the uncached path would, immediately
        cached, miss_idx = sigcache.partition(
            [(pub, sign_bytes, sig)
             for _, _, pub, sign_bytes, sig in entries])
        for (label, ctx, _, _, sig), v in zip(entries, cached):
            if v is False:
                raise self._fail(label, ctx, sig)
        entries = [entries[i] for i in miss_idx]
        if not entries:
            return
        if len(entries) < self.DEVICE_THRESHOLD:
            for label, ctx, pub, sign_bytes, sig in entries:
                if not crypto_batch.safe_verify(pub, sign_bytes, sig):
                    raise self._fail(label, ctx, sig)
            return
        bv = crypto_batch.MixedBatchVerifier()
        for _, _, pub, sign_bytes, sig in entries:
            bv.add(pub, sign_bytes, sig)
        ok, verdicts = bv.verify()
        if ok:
            return
        for (label, ctx, _, _, sig), valid in zip(entries, verdicts):
            if not valid:
                raise self._fail(label, ctx, sig)
        raise CommitVerificationError(
            "BUG: deferred batch failed with no invalid signatures")

    def verify_async(self, pipeline, subsystem: str = "pipeline",
                     lane: str | None = None):
        """Submit the collected entries through an overlapped
        VerifyPipeline (crypto/dispatch.py) instead of verifying
        inline; returns a waiter whose .wait() has EXACTLY verify()'s
        semantics (raises ErrInvalidSignature naming the first failing
        commit, with .failed_ctx) once the window's verdict future
        resolves.  The caller keeps collecting the next window while
        this one is staged/on device.  `lane` re-lanes the window
        under a different QoS priority (crypto/sched.py) without
        touching `subsystem`'s trace/ledger attribution."""
        self._entries, entries = [], self._entries
        if not entries:
            return _DeferredVerdict(entries, None)
        handle = pipeline.submit(
            [(pub, sign_bytes, sig)
             for _, _, pub, sign_bytes, sig in entries],
            subsystem=subsystem, ctx=entries[0][1],
            device_threshold=self.DEVICE_THRESHOLD, lane=lane)
        return _DeferredVerdict(entries, handle)


class _DeferredVerdict:
    """In-flight window verdict: .wait() mirrors
    DeferredSigBatch.verify()'s raise contract."""

    __slots__ = ("_entries", "handle")

    def __init__(self, entries, handle):
        self._entries = entries
        self.handle = handle

    def done(self) -> bool:
        return self.handle is None or self.handle.done()

    def wait(self, timeout: float | None = None) -> None:
        if self.handle is None:
            return
        ok, verdicts = self.handle.result(timeout)
        if ok:
            return
        for (label, ctx, _, _, sig), valid in zip(self._entries,
                                                  verdicts):
            if not valid:
                raise DeferredSigBatch._fail(label, ctx, sig)
        raise CommitVerificationError(
            "BUG: deferred window failed with no invalid signatures")

    def settle(self, timeout: float | None = None) -> None:
        """Block until the window has resolved, whatever it resolved
        to.  For a caller that gives a window up (blocksync's lookahead
        at a reject) but wants its verdicts in the verdict cache, and
        its future read, before it collects the same blocks again."""
        if self.handle is not None:
            try:
                self.handle.result(timeout)
            except Exception:       # noqa: BLE001 - given up, not judged
                pass

    def failed_contexts(self, timeout: float | None = None) -> set:
        """Per-context verdicts instead of first-failure raise: the
        set of ctx values (heights, for commit collection) that had at
        least one invalid signature.  Empty set = the whole window
        verified.  The lightserve coalescer merges MANY clients'
        heights into one window and must fail only the requests whose
        heights are actually bad, not the whole flush."""
        if self.handle is None:
            return set()
        ok, verdicts = self.handle.result(timeout)
        if ok:
            return set()
        return {ctx for (_, ctx, _, _, _), valid
                in zip(self._entries, verdicts) if not valid}


def verify_commit(chain_id: str, vals: ValidatorSet, block_id: BlockID,
                  height: int, commit: Commit) -> None:
    """+2/3 signed; checks ALL signatures (validation.go:28-56)."""
    _verify_basic(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    ignore = lambda cs: cs.block_id_flag == BLOCK_ID_FLAG_ABSENT  # noqa: E731
    count = lambda cs: cs.block_id_flag == BLOCK_ID_FLAG_COMMIT  # noqa: E731
    _verify(chain_id, vals, commit, needed, ignore, count,
            count_all=True, lookup_by_index=True)


def verify_commit_light(chain_id: str, vals: ValidatorSet,
                        block_id: BlockID, height: int,
                        commit: Commit, defer_to=None) -> None:
    """+2/3 signed; stops as soon as the tally crosses (validation.go:63).
    With defer_to (a DeferredSigBatch), signature checks are collected
    instead of verified; the caller runs defer_to.verify() later."""
    _verify_commit_light(chain_id, vals, block_id, height, commit,
                         count_all=False, defer_to=defer_to)


def verify_commit_light_all_signatures(chain_id: str, vals: ValidatorSet,
                                       block_id: BlockID, height: int,
                                       commit: Commit) -> None:
    _verify_commit_light(chain_id, vals, block_id, height, commit,
                         count_all=True)


def _verify_commit_light(chain_id, vals, block_id, height, commit,
                         count_all, defer_to=None):
    _verify_basic(vals, commit, height, block_id)
    needed = vals.total_voting_power() * 2 // 3
    ignore = lambda cs: cs.block_id_flag != BLOCK_ID_FLAG_COMMIT  # noqa: E731
    count = lambda cs: True  # noqa: E731
    _verify(chain_id, vals, commit, needed, ignore, count,
            count_all=count_all, lookup_by_index=True, defer_to=defer_to,
            defer_label=f"commit at height {height}", defer_ctx=height)


def verify_commit_light_trusting(chain_id: str, vals: ValidatorSet,
                                 commit: Commit,
                                 trust_level: Fraction) -> None:
    """trust_level of the (possibly different) valset signed
    (validation.go:129-204); lookup by address, early exit."""
    _verify_commit_light_trusting(chain_id, vals, commit, trust_level,
                                  count_all=False)


def verify_commit_light_trusting_all_signatures(
        chain_id: str, vals: ValidatorSet, commit: Commit,
        trust_level: Fraction) -> None:
    _verify_commit_light_trusting(chain_id, vals, commit, trust_level,
                                  count_all=True)


def _verify_commit_light_trusting(chain_id, vals, commit, trust_level,
                                  count_all):
    if vals is None:
        raise CommitVerificationError("nil validator set")
    if commit is None:
        raise CommitVerificationError("nil commit")
    if trust_level.denominator == 0:
        raise CommitVerificationError("trustLevel has zero Denominator")
    total = vals.total_voting_power()
    if total * trust_level.numerator > (1 << 63) - 1:
        raise CommitVerificationError("int64 overflow in voting power")
    needed = total * trust_level.numerator // trust_level.denominator
    ignore = lambda cs: cs.block_id_flag != BLOCK_ID_FLAG_COMMIT  # noqa: E731
    count = lambda cs: True  # noqa: E731
    _verify(chain_id, vals, commit, needed, ignore, count,
            count_all=count_all, lookup_by_index=False)


def _verify_basic(vals, commit, height, block_id):
    if vals is None:
        raise CommitVerificationError("nil validator set")
    if commit is None:
        raise CommitVerificationError("nil commit")
    if vals.size() != len(commit.signatures):
        raise CommitVerificationError(
            f"invalid commit -- wrong set size: {vals.size()} vs "
            f"{len(commit.signatures)}")
    if height != commit.height:
        raise CommitVerificationError(
            f"invalid commit -- wrong height: {height} vs {commit.height}")
    if block_id != commit.block_id:
        raise CommitVerificationError(
            f"invalid commit -- wrong block ID: want {block_id}, "
            f"got {commit.block_id}")


def _verify(chain_id, vals, commit, needed, ignore, count, count_all,
            lookup_by_index, defer_to=None, defer_label="",
            defer_ctx=None):
    """Unified batch/single verification.

    Mirrors verifyCommitBatch/verifyCommitSingle (validation.go:220-408):
    collect the non-ignored sigs (resolving validators by index or
    address), tally counted voting power with early exit, then verify —
    on device when batching is worthwhile, else host-by-host.
    """
    use_batch = _should_batch_verify(vals, commit)

    entries = []          # (commit_idx, validator, sign_bytes, signature)
    seen: dict[int, int] = {}
    tallied = 0
    # one columnar splice for the whole commit (types/canonical.py);
    # the loop body pays a list index per signature
    sign_bytes_all = commit.vote_sign_bytes_all(chain_id)

    for idx, cs in enumerate(commit.signatures):
        if ignore(cs):
            continue
        if lookup_by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(cs.validator_address)
            if val is None:
                continue
            if val_idx in seen:
                raise CommitVerificationError(
                    f"double vote from {val.address.hex()} "
                    f"({seen[val_idx]} and {idx})")
            seen[val_idx] = idx
        if val.pub_key is None:
            raise CommitVerificationError(
                f"validator {val.address.hex()} has nil pubkey at "
                f"index {idx}")
        if not use_batch:
            cs.validate_basic()
        sign_bytes = sign_bytes_all[idx]
        entries.append((idx, val, sign_bytes, cs.signature))
        if count(cs):
            tallied += val.voting_power
        if not count_all and tallied > needed:
            break

    if tallied <= needed:
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    if not entries:
        raise CommitVerificationError("BUG: no signatures to verify")

    if defer_to is not None:
        defer_to._extend(defer_label, defer_ctx, entries)
        return

    if use_batch:
        # verdict-cache partition (crypto/sigcache.py): only misses
        # reach a verifier; a cached negative rejects immediately with
        # the SAME localization message as the uncached path (on a hot
        # cache every entry is cached, so the first False in entry
        # order is the same index the uncached scan would name)
        cached, miss_idx = sigcache.partition(
            [(val.pub_key, sign_bytes, sig)
             for _, val, sign_bytes, sig in entries])
        for (idx, _, _, sig), v in zip(entries, cached):
            if v is False:
                raise ErrInvalidSignature(
                    f"wrong signature (#{idx}): {sig.hex()}")
        misses = [entries[i] for i in miss_idx]
        if not misses:
            return
        bv = crypto_batch.MixedBatchVerifier() \
            if not vals.all_keys_have_same_type() \
            else crypto_batch.create_batch_verifier(
                vals.get_proposer().pub_key.type(), n_hint=len(misses))
        for _, val, sign_bytes, sig in misses:
            bv.add(val.pub_key, sign_bytes, sig)
        ok, verdicts = bv.verify()
        if ok:
            return
        for (idx, _, _, sig), valid in zip(misses, verdicts):
            if not valid:
                raise ErrInvalidSignature(
                    f"wrong signature (#{idx}): {sig.hex()}")
        raise CommitVerificationError(
            "BUG: batch verification failed with no invalid signatures")

    for idx, val, sign_bytes, sig in entries:
        if not crypto_batch.safe_verify(val.pub_key, sign_bytes, sig):
            raise ErrInvalidSignature(
                f"wrong signature (#{idx}): {sig.hex()}")
