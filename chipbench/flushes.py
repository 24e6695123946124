"""The service worker's spans tied by the `flush` number they carry
(crypto/async_verify.py), never by their order: two flushes are in flight
at once, so the spans of one lie among the other's."""

from __future__ import annotations

HOST_PREP = "verify.host_prep"
HOST_PREP_STEPS = tuple(f"{HOST_PREP}.{step}" for step in ("rows", "canon", "msgs", "k", "pad"))


def by_flush(spans: list[dict], names) -> dict:
    """{flush: {name: seconds summed}} over the spans of those names."""
    out: dict = {}
    for s in spans:
        if s["name"] in names and "flush" in s["attrs"]:
            at = out.setdefault(s["attrs"]["flush"], {})
            at[s["name"]] = at.get(s["name"], 0.0) + s["dur_ns"] / 1e9
    return out


def host_prep(spans: list[dict]) -> list[dict]:
    """Per flush whose `verify.host_prep` was recorded with its steps:
    {name: seconds} of it and of each step (0 for a step it lacks).
    Empty on a program whose host prep has no steps."""
    flushes = by_flush(spans, (HOST_PREP,) + HOST_PREP_STEPS).values()
    return [{name: f.get(name, 0.0) for name in (HOST_PREP,) + HOST_PREP_STEPS}
            for f in flushes if HOST_PREP in f and len(f) > 1]


def mean_ms(values: list[float]) -> float | None:
    return sum(values) / len(values) * 1e3 if values else None
