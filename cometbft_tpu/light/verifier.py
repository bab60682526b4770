"""Pure light-client verification (light/verifier.go analog).

verify_adjacent / verify_non_adjacent / verify_backwards reproduce
/root/reference/light/verifier.go:30,91,129,196-230 exactly; the
signature checks route through the TPU batch verifier via
types/validation.py. Durations are nanoseconds (ints).
"""

from __future__ import annotations

from ..crypto import sigcache
from ..libs.trace import span as trace_span
from ..types.timestamp import Timestamp
from ..types.validation import (
    ErrNotEnoughVotingPowerSigned, Fraction, verify_commit_light,
    verify_commit_light_trusting,
)
from .types import LightBlock, SignedHeader

DEFAULT_TRUST_LEVEL = Fraction(1, 3)

SECOND = 1_000_000_000
DEFAULT_MAX_CLOCK_DRIFT = 10 * SECOND


class LightClientError(Exception):
    pass


class ErrOldHeaderExpired(LightClientError):
    pass


class ErrInvalidHeader(LightClientError):
    pass


class ErrNewValSetCantBeTrusted(LightClientError):
    pass


class ErrHeaderHeightAdjacent(LightClientError):
    pass


class ErrHeaderHeightNotAdjacent(LightClientError):
    pass


class ErrInvalidTrustLevel(LightClientError):
    pass


def validate_trust_level(lvl: Fraction) -> None:
    """[1/3, 1] (verifier.go:184-192)."""
    if (lvl.numerator * 3 < lvl.denominator
            or lvl.numerator > lvl.denominator
            or lvl.denominator == 0):
        raise ErrInvalidTrustLevel(f"trust level must be in [1/3, 1]: {lvl}")


def header_expired(h: SignedHeader, trusting_period_ns: int,
                   now: Timestamp) -> bool:
    expiration = h.header.time.add_ns(trusting_period_ns)
    return expiration <= now


def _verify_new_header_and_vals(untrusted: SignedHeader, untrusted_vals,
                                trusted: SignedHeader, now: Timestamp,
                                max_clock_drift_ns: int) -> None:
    try:
        untrusted.validate_basic(trusted.chain_id)
    except ValueError as e:
        raise ErrInvalidHeader(f"header validate basic: {e}") from e
    if untrusted.height <= trusted.height:
        raise ErrInvalidHeader(
            f"expected new header height {untrusted.height} > "
            f"{trusted.height}")
    if untrusted.header.time <= trusted.header.time:
        raise ErrInvalidHeader("non-monotonic header time")
    if untrusted.header.time >= now.add_ns(max_clock_drift_ns):
        raise ErrInvalidHeader("new header time exceeds max clock drift")
    if untrusted.header.validators_hash != untrusted_vals.hash():
        raise ErrInvalidHeader(
            f"validators hash mismatch at height {untrusted.height}")


def verify_adjacent(trusted: SignedHeader, untrusted: SignedHeader,
                    untrusted_vals, trusting_period_ns: int, now: Timestamp,
                    max_clock_drift_ns: int, defer_to=None) -> None:
    """verifier.go:91-127.  defer_to (validation.DeferredSigBatch)
    collects the commit's signature checks for a later cross-header
    device batch; every header/valset structural check still runs
    immediately."""
    # everything that is not a signature: validate_basic with the
    # header's hash, the validator set's Merkle hash, the link to the
    # trusted header's next_validators_hash
    with trace_span("light", "validate"):
        if untrusted.height != trusted.height + 1:
            raise ErrHeaderHeightNotAdjacent()
        if header_expired(trusted, trusting_period_ns, now):
            raise ErrOldHeaderExpired()
        _verify_new_header_and_vals(untrusted, untrusted_vals, trusted,
                                    now, max_clock_drift_ns)
        if untrusted.header.validators_hash != \
                trusted.header.next_validators_hash:
            raise ErrInvalidHeader(
                f"expected old header next validators "
                f"({trusted.header.next_validators_hash.hex()}) to match "
                f"those from new header "
                f"({untrusted.header.validators_hash.hex()})")
    try:
        # commits the full node already verified (consensus/blocksync)
        # are verdict-cache hits here — attributed to the "light"
        # consumer in CacheMetrics
        with sigcache.consumer("light"):
            verify_commit_light(trusted.chain_id, untrusted_vals,
                                untrusted.commit.block_id,
                                untrusted.height, untrusted.commit,
                                defer_to=defer_to)
    except Exception as e:
        raise ErrInvalidHeader(str(e)) from e


def verify_non_adjacent(trusted: SignedHeader, trusted_vals,
                        untrusted: SignedHeader, untrusted_vals,
                        trusting_period_ns: int, now: Timestamp,
                        max_clock_drift_ns: int,
                        trust_level: Fraction) -> None:
    """verifier.go:30-89: 1/3 overlap with trusted vals, then +2/3 of
    the new set. The order matters: the trusting check runs first so an
    attacker can't DOS with a huge fake untrusted valset."""
    if untrusted.height == trusted.height + 1:
        raise ErrHeaderHeightAdjacent()
    if header_expired(trusted, trusting_period_ns, now):
        raise ErrOldHeaderExpired()
    _verify_new_header_and_vals(untrusted, untrusted_vals, trusted, now,
                                max_clock_drift_ns)
    try:
        with sigcache.consumer("light"):
            verify_commit_light_trusting(trusted.chain_id, trusted_vals,
                                         untrusted.commit, trust_level)
    except ErrNotEnoughVotingPowerSigned as e:
        raise ErrNewValSetCantBeTrusted(str(e)) from e
    try:
        with sigcache.consumer("light"):
            verify_commit_light(trusted.chain_id, untrusted_vals,
                                untrusted.commit.block_id,
                                untrusted.height, untrusted.commit)
    except Exception as e:
        raise ErrInvalidHeader(str(e)) from e


def verify(trusted: SignedHeader, trusted_vals, untrusted: SignedHeader,
           untrusted_vals, trusting_period_ns: int, now: Timestamp,
           max_clock_drift_ns: int, trust_level: Fraction) -> None:
    """verifier.go:131-148: adjacent or skipping."""
    if untrusted.height != trusted.height + 1:
        verify_non_adjacent(trusted, trusted_vals, untrusted,
                            untrusted_vals, trusting_period_ns, now,
                            max_clock_drift_ns, trust_level)
    else:
        verify_adjacent(trusted, untrusted, untrusted_vals,
                        trusting_period_ns, now, max_clock_drift_ns)


def verify_backwards(untrusted_header, trusted_header) -> None:
    """verifier.go:196-230: hash-chain one height backwards."""
    try:
        untrusted_header.validate_basic()
    except ValueError as e:
        raise ErrInvalidHeader(str(e)) from e
    if untrusted_header.chain_id != trusted_header.chain_id:
        raise ErrInvalidHeader("header belongs to another chain")
    if untrusted_header.time >= trusted_header.time:
        raise ErrInvalidHeader(
            "expected older header time to be before new header time")
    if untrusted_header.hash() != trusted_header.last_block_id.hash:
        raise ErrInvalidHeader(
            "older header hash does not match trusted header's last block")


def verify_light_block(trusted: LightBlock, untrusted: LightBlock,
                       trusting_period_ns: int, now: Timestamp,
                       max_clock_drift_ns: int,
                       trust_level: Fraction) -> None:
    verify(trusted.signed_header, trusted.validator_set,
           untrusted.signed_header, untrusted.validator_set,
           trusting_period_ns, now, max_clock_drift_ns, trust_level)
