"""Light-client error types (reference light/errors.go)."""

from __future__ import annotations


class LightClientError(Exception):
    """Base for all light-client failures."""


class ErrOldHeaderExpired(LightClientError):
    """Trusted header is outside the trusting period (errors.go:15-24)."""

    def __init__(self, expired_at_ns: int, now_ns: int):
        self.expired_at_ns = expired_at_ns
        self.now_ns = now_ns
        super().__init__(
            f"old header has expired at {expired_at_ns} (now: {now_ns})"
        )


class ErrNewValSetCantBeTrusted(LightClientError):
    """< trust-level of trusted power signed the new header (errors.go:32-40).

    Drives the bisection pivot in skipping verification."""


class ErrInvalidHeader(LightClientError):
    """New header could not be verified (errors.go:42-50)."""


class ErrVerificationFailed(LightClientError):
    """Skipping verification failed at some intermediate height, carrying
    the bisection position for diagnostics (errors.go:52-70)."""

    def __init__(self, from_height: int, to_height: int, reason: Exception):
        self.from_height = from_height
        self.to_height = to_height
        self.reason = reason
        super().__init__(
            f"verify from #{from_height} to #{to_height} failed: {reason}"
        )


class ErrLightClientAttack(LightClientError):
    """Divergence detected and evidence submitted (errors.go:72-79)."""

    def __init__(self) -> None:
        super().__init__(
            "attempted attack detected, light client received valid conflicting header from witness"
        )


class ErrLightBlockNotFound(LightClientError):
    """Provider has no block at the requested height (provider/errors.go:12)."""


class ErrNoResponse(LightClientError):
    """Provider failed to respond (provider/errors.go:15)."""


class ErrFailedHeaderCrossReferencing(LightClientError):
    """Too few witnesses responded to cross-check the header (errors.go:84)."""
