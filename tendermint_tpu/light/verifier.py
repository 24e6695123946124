"""Core light-client verification logic.

Semantics parity: reference light/verifier.go — VerifyNonAdjacent (:33),
VerifyAdjacent (:102), Verify dispatch (:147), verifyNewHeaderAndVals
(:162), HeaderExpired (:199), ValidateTrustLevel (:210).

TPU redesign: every commit verification already runs as ONE batched
device call (types/validator.py), and `verify_adjacent_range` extends
this across a whole window of sequential headers — the commits of N
adjacent light blocks are verified as a single device batch, the
light-sync analog of the fast-sync pipeline batch
(reference light/verifier.go:81,141 are sequential per-signature loops).
"""

from __future__ import annotations

from fractions import Fraction

from tendermint_tpu.types.light import LightBlock, SignedHeader
from tendermint_tpu.types.validator import (
    CommitVerifyJob,
    ErrNotEnoughVotingPowerSigned,
    ValidatorSet,
    batch_verify_commits,
)
from tendermint_tpu.utils.metrics import Counter

from .errors import (
    ErrInvalidHeader,
    ErrNewValSetCantBeTrusted,
    ErrOldHeaderExpired,
)

DEFAULT_TRUST_LEVEL = Fraction(1, 3)

# Bumped once a trusting check, in `verify_non_adjacent` (process-wide;
# registered by node/metrics.py with light/client.py's LIGHT_COUNTERS):
# the rows of accepted jumps, and the rows a refused jump verified before
# it ran out of trusted power (0 where a refused jump shares no validator).
TRUSTING_ROWS_TOTAL = Counter(
    "trusting_rows_total",
    "Commit rows verified by trusting checks that found enough trusted power",
    namespace="tendermint", subsystem="light",
)
REFUSED_ROWS_TOTAL = Counter(
    "refused_rows_total",
    "Commit rows verified by trusting checks that ran out of trusted power",
    namespace="tendermint", subsystem="light",
)


def validate_trust_level(lvl: Fraction) -> None:
    """Trust level must lie in [1/3, 1] (reference verifier.go:210-218)."""
    if (
        lvl.numerator * 3 < lvl.denominator
        or lvl.numerator > lvl.denominator
        or lvl.denominator == 0
    ):
        raise ValueError(f"trustLevel must be within [1/3, 1], given {lvl}")


def header_expired(h: SignedHeader, trusting_period_ns: int, now_ns: int) -> bool:
    """reference verifier.go:199-207."""
    return h.header.time_ns + trusting_period_ns <= now_ns


def _verify_new_header_and_vals(
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusted_header: SignedHeader,
    now_ns: int,
    max_clock_drift_ns: int,
) -> None:
    """reference verifier.go:162-197."""
    chain_id = trusted_header.header.chain_id
    try:
        untrusted_header.validate_basic(chain_id)
    except ValueError as e:
        raise ErrInvalidHeader(f"untrustedHeader.ValidateBasic failed: {e}") from e

    if untrusted_header.height <= trusted_header.height:
        raise ErrInvalidHeader(
            f"expected new header height {untrusted_header.height} to be greater "
            f"than one of old header {trusted_header.height}"
        )
    if untrusted_header.header.time_ns <= trusted_header.header.time_ns:
        raise ErrInvalidHeader(
            f"expected new header time {untrusted_header.header.time_ns} to be "
            f"after old header time {trusted_header.header.time_ns}"
        )
    if untrusted_header.header.time_ns >= now_ns + max_clock_drift_ns:
        raise ErrInvalidHeader(
            f"new header has a time from the future {untrusted_header.header.time_ns} "
            f"(now: {now_ns}; max clock drift: {max_clock_drift_ns})"
        )
    if untrusted_header.header.validators_hash != untrusted_vals.hash():
        raise ErrInvalidHeader(
            f"expected new header validators ({untrusted_header.header.validators_hash.hex()}) "
            f"to match those supplied ({untrusted_vals.hash().hex()}) "
            f"at height {untrusted_header.height}"
        )


def _verify_commit_light(
    untrusted_vals: ValidatorSet,
    chain_id: str,
    untrusted_header: SignedHeader,
    commit_verifier,
) -> None:
    """The new set's own +2/3 check, routed through `commit_verifier`
    when given (a batch_verify_commits-compatible callable — the
    gateway's cross-client coalescer) and straight to the validator-set
    surface otherwise."""
    if commit_verifier is None:
        untrusted_vals.verify_commit_light(
            chain_id,
            untrusted_header.commit.block_id,
            untrusted_header.height,
            untrusted_header.commit,
        )
    else:
        commit_verifier([
            CommitVerifyJob(
                val_set=untrusted_vals,
                chain_id=chain_id,
                block_id=untrusted_header.commit.block_id,
                height=untrusted_header.height,
                commit=untrusted_header.commit,
                mode="light",
            )
        ])


def verify_non_adjacent(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
    *,
    commit_verifier=None,
) -> None:
    """Skipping verification across a height gap (reference verifier.go:33-99).

    Raises ErrNewValSetCantBeTrusted if less than trust_level of the
    trusted set signed the new header (→ bisection pivot), ErrInvalidHeader
    if the new set's own commit does not carry +2/3.  Any other failure
    of the trusting check — a wrong signature, a double vote — passes
    through as the ValueError it is (reference verifier.go:73-80 maps
    only ErrNotEnoughVotingPowerSigned): the caller fails the
    verification and does not pivot.
    """
    if untrusted_header.height == trusted_header.height + 1:
        raise ValueError("headers must be non adjacent in height")
    if header_expired(trusted_header, trusting_period_ns, now_ns):
        raise ErrOldHeaderExpired(
            trusted_header.header.time_ns + trusting_period_ns, now_ns
        )
    _verify_new_header_and_vals(
        untrusted_header, untrusted_vals, trusted_header, now_ns, max_clock_drift_ns
    )

    chain_id = trusted_header.header.chain_id
    try:
        rows = trusted_vals.verify_commit_light_trusting(
            chain_id, untrusted_header.commit, trust_level
        )
    except ErrNotEnoughVotingPowerSigned as e:
        REFUSED_ROWS_TOTAL.inc(e.rows)
        raise ErrNewValSetCantBeTrusted(str(e)) from e
    TRUSTING_ROWS_TOTAL.inc(rows)

    try:
        _verify_commit_light(untrusted_vals, chain_id, untrusted_header,
                             commit_verifier)
    except ValueError as e:
        raise ErrInvalidHeader(str(e)) from e


def verify_adjacent(
    trusted_header: SignedHeader,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
    *,
    commit_verifier=None,
) -> None:
    """Sequential (height+1) verification (reference verifier.go:102-145)."""
    if untrusted_header.height != trusted_header.height + 1:
        raise ValueError("headers must be adjacent in height")
    if header_expired(trusted_header, trusting_period_ns, now_ns):
        raise ErrOldHeaderExpired(
            trusted_header.header.time_ns + trusting_period_ns, now_ns
        )
    _verify_new_header_and_vals(
        untrusted_header, untrusted_vals, trusted_header, now_ns, max_clock_drift_ns
    )
    if (
        untrusted_header.header.validators_hash
        != trusted_header.header.next_validators_hash
    ):
        raise ErrInvalidHeader(
            f"expected old header next validators "
            f"({trusted_header.header.next_validators_hash.hex()}) to match those "
            f"from new header ({untrusted_header.header.validators_hash.hex()})"
        )
    try:
        _verify_commit_light(untrusted_vals, trusted_header.header.chain_id,
                             untrusted_header, commit_verifier)
    except ValueError as e:
        raise ErrInvalidHeader(str(e)) from e


def verify(
    trusted_header: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
    *,
    commit_verifier=None,
) -> None:
    """Dispatch adjacent vs non-adjacent (reference verifier.go:147-160)."""
    if untrusted_header.height != trusted_header.height + 1:
        verify_non_adjacent(
            trusted_header,
            trusted_vals,
            untrusted_header,
            untrusted_vals,
            trusting_period_ns,
            now_ns,
            max_clock_drift_ns,
            trust_level,
            commit_verifier=commit_verifier,
        )
    else:
        verify_adjacent(
            trusted_header,
            untrusted_header,
            untrusted_vals,
            trusting_period_ns,
            now_ns,
            max_clock_drift_ns,
            commit_verifier=commit_verifier,
        )


def verify_adjacent_range(
    trusted: LightBlock,
    blocks: list[LightBlock],
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
    *,
    verify_fn=None,
) -> None:
    """Verify a whole window of consecutive light blocks at once.

    All host-side chain checks (height/time monotonicity, NextValidatorsHash
    linkage, validator-set hash) run first; then the commits of every block
    in the window are verified as ONE device batch via batch_verify_commits
    — N blocks × M signatures in a single XLA call, instead of the
    reference's per-header, per-signature loop (light/verifier.go:102-145
    called once per height from light/client.go:583+).

    `verify_fn` overrides the commit-batch sink (contract of
    batch_verify_commits) — the gateway routes it into its cross-client
    coalescer so concurrent clients share flushes.

    Raises the same errors verify_adjacent would raise for the first
    offending block.
    """
    if header_expired(trusted.signed_header, trusting_period_ns, now_ns):
        raise ErrOldHeaderExpired(trusted.time_ns + trusting_period_ns, now_ns)
    prev = trusted
    jobs = []
    for lb in blocks:
        if lb.height != prev.height + 1:
            raise ValueError(
                f"blocks not consecutive: {prev.height} then {lb.height}"
            )
        _verify_new_header_and_vals(
            lb.signed_header,
            lb.validator_set,
            prev.signed_header,
            now_ns,
            max_clock_drift_ns,
        )
        if (
            lb.header.validators_hash
            != prev.signed_header.header.next_validators_hash
        ):
            raise ErrInvalidHeader(
                f"header #{lb.height} validators hash does not match "
                f"#{prev.height} next validators hash"
            )
        jobs.append(
            CommitVerifyJob(
                val_set=lb.validator_set,
                chain_id=trusted.header.chain_id,
                block_id=lb.commit.block_id,
                height=lb.height,
                commit=lb.commit,
                mode="light",
            )
        )
        prev = lb
    try:
        (verify_fn if verify_fn is not None else batch_verify_commits)(jobs)
    except ValueError as e:
        raise ErrInvalidHeader(str(e)) from e
