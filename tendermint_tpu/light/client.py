"""Light client: trusted-store-backed header verification with sequential
and skipping (bisection) modes, backwards verification, primary/witness
management, and divergence detection.

Semantics parity: reference light/client.go — NewClient (:114),
initializeWithTrustOptions (:296), VerifyLightBlockAtHeight (:445),
verifySequential (:583), verifySkipping (:683), backwards (:994),
replacePrimaryProvider (:1046), pruning (:931).

TPU redesign: sequential verification over a window of already-fetched
blocks routes through verifier.verify_adjacent_range — one device batch
for the whole window's commits — rather than one verify call per header.
"""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction

from tendermint_tpu.types.basic import now_ns as _now_ns
from tendermint_tpu.types.light import LightBlock
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.utils.metrics import Counter

from . import verifier
from .detector import detect_divergence
from .errors import (
    ErrLightBlockNotFound,
    ErrLightClientAttack,
    ErrNewValSetCantBeTrusted,
    ErrNoResponse,
    ErrOldHeaderExpired,
    ErrVerificationFailed,
    LightClientError,
)
from .provider import Provider
from .store import LightBlockStore

SEQUENTIAL = "sequential"
SKIPPING = "skipping"

DEFAULT_PRUNING_SIZE = 1000  # reference client.go:40
DEFAULT_MAX_CLOCK_DRIFT_NS = 10 * 1_000_000_000  # client.go:46
SEQUENTIAL_BATCH_WINDOW = 64  # blocks per batched device call

# Bumped where a skipping client tries a jump and where any client
# fetches a light block (process-wide; registered by node/metrics.py).
HOPS_TOTAL = Counter(
    "hops_total",
    "Jumps tried by skipping verification: accepted (the candidate became "
    "trusted), refused (too little trusted power: a pivot), failed",
    namespace="tendermint", subsystem="light", label_names=("outcome",),
)
FETCHES_TOTAL = Counter(
    "fetches_total", "Light blocks fetched from a provider and validated",
    namespace="tendermint", subsystem="light",
)
LIGHT_COUNTERS = (HOPS_TOTAL, verifier.TRUSTING_ROWS_TOTAL,
                  verifier.REFUSED_ROWS_TOTAL, FETCHES_TOTAL)


@dataclass
class TrustOptions:
    """Root of trust (reference light/client.go:57-88)."""

    period_ns: int
    height: int
    hash: bytes

    def validate_basic(self) -> None:
        if self.period_ns <= 0:
            raise ValueError("negative or zero trusting period")
        if self.height <= 0:
            raise ValueError("non-positive trusted height")
        if len(self.hash) != 32:
            raise ValueError(f"expected hash size 32, got {len(self.hash)}")


class Client:
    def __init__(
        self,
        chain_id: str,
        trust_options: TrustOptions,
        primary: Provider,
        witnesses: list[Provider],
        trusted_store: LightBlockStore | None = None,
        mode: str = SKIPPING,
        trust_level: Fraction = verifier.DEFAULT_TRUST_LEVEL,
        max_clock_drift_ns: int = DEFAULT_MAX_CLOCK_DRIFT_NS,
        pruning_size: int = DEFAULT_PRUNING_SIZE,
        now_fn=_now_ns,
        logger=None,
        commit_verifier=None,
    ):
        verifier.validate_trust_level(trust_level)
        trust_options.validate_basic()
        if mode not in (SEQUENTIAL, SKIPPING):
            raise ValueError(f"unknown verification mode {mode!r}")
        self.chain_id = chain_id
        self.trusting_period_ns = trust_options.period_ns
        self.trust_level = trust_level
        self.mode = mode
        self.max_clock_drift_ns = max_clock_drift_ns
        self.pruning_size = pruning_size
        self.primary = primary
        self.witnesses = list(witnesses)
        self.store = trusted_store if trusted_store is not None else LightBlockStore()
        self.now_fn = now_fn
        self.logger = logger
        # commit-batch sink override (contract of batch_verify_commits):
        # a gateway-driven client points this at the cross-client verify
        # coalescer so N clients syncing one chain share device flushes
        self.commit_verifier = commit_verifier
        # what the verification under way did so far: the attributes of
        # its `light.verify_to_height` span
        self._walk = {"hops": 0, "refused": 0, "fetched": 0}
        self.latest_trusted: LightBlock | None = self.store.latest_light_block()
        self._initialize(trust_options)

    # -- initialization -------------------------------------------------

    def _initialize(self, opts: TrustOptions) -> None:
        """Fetch + self-verify the root-of-trust block
        (reference client.go:296-361 initializeWithTrustOptions)."""
        if self.latest_trusted is not None:
            # Existing trusted state: confirm it agrees with the options
            # (reference checkTrustedHeaderUsingOptions, client.go:381-443).
            stored = self.store.light_block(opts.height)
            if stored is not None and stored.hash() != opts.hash:
                raise LightClientError(
                    f"existing trusted header at height {opts.height} "
                    f"({stored.hash().hex()}) does not match trust options hash "
                    f"({opts.hash.hex()}); purge the trusted store to continue"
                )
            return
        lb = self._light_block_from_primary(opts.height)
        if lb.hash() != opts.hash:
            raise LightClientError(
                f"expected header's hash {opts.hash.hex()}, but got {lb.hash().hex()}"
            )
        lb.validate_basic(self.chain_id)
        # Self-verification: the user-pinned hash is the trust root; the
        # block's own validator set must carry +2/3 on it (client.go:341-352).
        lb.validator_set.verify_commit_light(
            self.chain_id, lb.commit.block_id, lb.height, lb.commit
        )
        self._update_trusted_light_block(lb)

    # -- public API -----------------------------------------------------

    def trusted_light_block(self, height: int) -> LightBlock | None:
        return self.store.light_block(height)

    def first_trusted_height(self) -> int:
        first = self.store.first_light_block()
        return first.height if first else -1

    def last_trusted_height(self) -> int:
        last = self.store.latest_light_block()
        return last.height if last else -1

    def update(self, now_ns: int | None = None) -> LightBlock | None:
        """Verify the latest header from primary (reference client.go:523-549)."""
        now = self.now_fn() if now_ns is None else now_ns
        latest = self._light_block_from_primary(0)
        if self.latest_trusted and latest.height <= self.latest_trusted.height:
            return None
        return self.verify_light_block_at_height(latest.height, now)

    def verify_light_block_at_height(
        self, height: int, now_ns: int | None = None
    ) -> LightBlock:
        """reference client.go:445-480."""
        if height <= 0:
            raise ValueError("negative or zero height")
        now = self.now_fn() if now_ns is None else now_ns
        existing = self.store.light_block(height)
        if existing is not None:
            return existing
        if self.latest_trusted is None:
            raise LightClientError("no trusted state")
        if height < self.latest_trusted.height:
            return self._backwards(height, now)
        # spans (utils/trace): the whole forward verification, then one
        # `light.hop` a jump tried, one `light.fetch` a block fetched and
        # one `light.store` for the saves (docs/observability.md)
        self._walk = walk = {"hops": 0, "refused": 0, "fetched": 0}
        with _trace.span("light.verify_to_height", mode=self.mode, target=height,
                         **{"from": self.latest_trusted.height}) as sp:
            try:
                target = self._light_block_from_primary(height)
                self._verify_light_block(target, now)
            finally:
                sp.set(**walk)
        return target

    # -- forward verification -------------------------------------------

    def _verify_light_block(self, new_lb: LightBlock, now: int) -> None:
        """reference client.go:551-581: dispatch by mode, cross-check with
        witnesses, persist."""
        trusted = self.latest_trusted
        if trusted is None:
            raise LightClientError("no trusted state")
        if self.mode == SEQUENTIAL:
            trace = self._verify_sequential(trusted, new_lb, now)
        else:
            trace = self._verify_skipping_against_primary(trusted, new_lb, now)
        # Persist ONLY after witness cross-examination: a detected attack
        # must leave no forged block in the trusted store, or the next
        # call would return it from cache without any witness check
        # (reference stores via updateTrustedLightBlock after detection,
        # client.go:551-581).
        if self.witnesses:
            detect_divergence(self, trace, now)
        with _trace.span("light.store", blocks=len(trace) - 1):
            for lb in trace[1:]:
                self.store.save_light_block(lb)
            self._update_trusted_light_block(trace[-1] if trace else new_lb)

    def _verify_sequential(
        self, trusted: LightBlock, target: LightBlock, now: int
    ) -> list[LightBlock]:
        """Batched sequential verification (reference client.go:583-650):
        fetch a window of consecutive blocks, verify the window's commits
        as one device call, advance."""
        trace = [trusted]
        h = trusted.height + 1
        while h <= target.height:
            window_end = min(h + SEQUENTIAL_BATCH_WINDOW - 1, target.height)
            blocks = []
            for hh in range(h, window_end + 1):
                blocks.append(
                    target if hh == target.height else self._light_block_from_primary(hh)
                )
            try:
                verifier.verify_adjacent_range(
                    trusted, blocks, self.trusting_period_ns, now,
                    self.max_clock_drift_ns,
                    verify_fn=self.commit_verifier,
                )
            except ErrOldHeaderExpired:
                raise
            except LightClientError as e:
                # Fall back to per-block to pinpoint the offender, then
                # try a replacement primary (reference client.go:614-641).
                bad_height = self._first_bad_height(trusted, blocks, now)
                replacement = self._find_new_primary(bad_height, now)
                if replacement is None:
                    raise ErrVerificationFailed(trusted.height, bad_height, e)
                # Re-fetch the target from the NEW primary; if it differs
                # from what the old primary served, the old primary lied
                # about the target itself (reference client.go:652-681
                # applies the same hash cross-check on replacement).
                new_target = self._light_block_from(self.primary, target.height)
                if new_target.hash() != target.hash():
                    raise LightClientError(
                        f"primary and its replacement serve different blocks "
                        f"at height {target.height}; aborting"
                    ) from e
                return self._verify_sequential(trace[0], target, now)
            trace.extend(blocks)
            trusted = blocks[-1]
            h = window_end + 1
        return trace

    def _first_bad_height(
        self, trusted: LightBlock, blocks: list[LightBlock], now: int
    ) -> int:
        prev = trusted
        for lb in blocks:
            try:
                verifier.verify_adjacent(
                    prev.signed_header,
                    lb.signed_header,
                    lb.validator_set,
                    self.trusting_period_ns,
                    now,
                    self.max_clock_drift_ns,
                )
            except LightClientError:
                return lb.height
            prev = lb
        return blocks[-1].height

    def _verify_skipping_against_primary(
        self, trusted: LightBlock, target: LightBlock, now: int
    ) -> list[LightBlock]:
        """reference client.go:652-681."""
        try:
            return self._verify_skipping(self.primary, trusted, target, now)
        except ErrOldHeaderExpired:
            raise
        except LightClientError as e:
            replacement = self._find_new_primary(target.height, now)
            if replacement is None:
                raise
            target2 = self._light_block_from_primary(target.height)
            if target2.hash() != target.hash():
                raise LightClientError(
                    f"replacement provider has a different block at height "
                    f"{target.height}"
                ) from e
            return self._verify_skipping(self.primary, trusted, target2, now)

    def _verify_skipping(
        self, source: Provider, trusted: LightBlock, target: LightBlock, now: int
    ) -> list[LightBlock]:
        """Bisection (reference client.go:683-761 verifySkipping).

        blockCache holds candidate blocks, deepest = lowest height; on
        ErrNewValSetCantBeTrusted a pivot halfway between the verified
        and failing heights is fetched and pushed.
        """
        cache = [target]
        depth = 0
        verified = trusted
        trace = [trusted]
        walk = self._walk
        while True:
            candidate = cache[depth]
            failure = None
            with _trace.span("light.hop", trusted=verified.height,
                             candidate=candidate.height) as sp:
                try:
                    verifier.verify(
                        verified.signed_header,
                        verified.validator_set,
                        candidate.signed_header,
                        candidate.validator_set,
                        self.trusting_period_ns,
                        now,
                        self.max_clock_drift_ns,
                        self.trust_level,
                        commit_verifier=self.commit_verifier,
                    )
                    outcome = "accepted"
                except ErrNewValSetCantBeTrusted:
                    outcome = "refused"
                except (LightClientError, ValueError) as e:
                    # a wrong signature or a double vote in the trusting
                    # check arrives as the ValueError it is: a failed
                    # verification, never a pivot (reference client.go:744)
                    outcome, failure = "failed", e
                sp.set(outcome=outcome)
            HOPS_TOTAL.inc(outcome=outcome)
            if failure is not None:
                raise ErrVerificationFailed(
                    verified.height, candidate.height, failure) from failure
            if outcome == "refused":
                walk["refused"] += 1
                if depth == len(cache) - 1:
                    pivot = (candidate.height + verified.height) // 2
                    if pivot in (verified.height, candidate.height):
                        raise ErrVerificationFailed(
                            verified.height,
                            candidate.height,
                            ErrNewValSetCantBeTrusted("bisection exhausted"),
                        )
                    cache.append(self._light_block_from(source, pivot))
                depth += 1
            else:
                walk["hops"] += 1
                verified = candidate
                trace.append(verified)
                if depth == 0:
                    return trace
                cache.pop(depth)
                depth -= 1

    # -- backwards verification -----------------------------------------

    def _backwards(self, height: int, now: int) -> LightBlock:
        """Hash-chain verification below the trusted head
        (reference client.go:994-1044)."""
        # Anchor on the closest trusted block ABOVE the target: the hash
        # chain (LastBlockID) only links downward, so a trusted block
        # below the target can't vouch for it.
        trusted = self.store.light_block_after(height)
        if trusted is None:
            trusted = self.latest_trusted
        if trusted is None or trusted.height <= height:
            raise ErrLightBlockNotFound(
                f"no trusted header above height {height} to verify backwards from"
            )
        if verifier.header_expired(
            trusted.signed_header, self.trusting_period_ns, now
        ):
            raise ErrOldHeaderExpired(
                trusted.time_ns + self.trusting_period_ns, now
            )
        for h in range(trusted.height - 1, height - 1, -1):
            interim = self._light_block_from_primary(h)
            if interim.header.hash() != trusted.header.last_block_id.hash:
                raise LightClientError(
                    f"header #{h} hash {interim.header.hash().hex()} does not "
                    f"match trusted LastBlockID hash "
                    f"{trusted.header.last_block_id.hash.hex()}"
                )
            if interim.time_ns >= trusted.time_ns:
                raise LightClientError(
                    f"expected older header time {interim.time_ns} to be before "
                    f"newer header time {trusted.time_ns}"
                )
            trusted = interim
        self.store.save_light_block(trusted)
        return trusted

    # -- provider management --------------------------------------------

    def _light_block_from(self, source: Provider, height: int) -> LightBlock:
        with _trace.span("light.fetch", height=height):
            lb = source.light_block(height)
            lb.validate_basic(self.chain_id)
        self._walk["fetched"] += 1
        FETCHES_TOTAL.inc()
        return lb

    def _light_block_from_primary(self, height: int) -> LightBlock:
        try:
            return self._light_block_from(self.primary, height)
        except (ErrNoResponse, ErrLightBlockNotFound):
            replacement = self._find_new_primary(height, self.now_fn())
            if replacement is None:
                raise
            return replacement

    def _find_new_primary(self, height: int, now: int) -> LightBlock | None:
        """Promote the first witness that serves `height`
        (reference client.go:1046-1090 replacePrimaryProvider)."""
        for i, w in enumerate(list(self.witnesses)):
            try:
                lb = self._light_block_from(w, height)
            except LightClientError:
                continue
            self.primary = w
            self.witnesses.pop(i)
            # The failed primary is dropped from rotation (reference
            # client.go:1046-1090): re-adding it would let two colluding
            # providers swap places forever, turning a verification
            # failure into unbounded retries.
            return lb
        return None

    def remove_witness(self, w: Provider) -> None:
        try:
            self.witnesses.remove(w)
        except ValueError:
            pass

    # -- persistence ----------------------------------------------------

    def _update_trusted_light_block(self, lb: LightBlock) -> None:
        self.store.save_light_block(lb)
        if self.latest_trusted is None or lb.height > self.latest_trusted.height:
            self.latest_trusted = lb
        if self.pruning_size > 0:
            self.store.prune(self.pruning_size)
