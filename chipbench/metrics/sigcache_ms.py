"""Service / router: the verified-signature cache's three bulk passes a
flush — Σ `verify.submit.keys` + `verify.submit.probe` + `verify.cache_put`
over the slice ÷ the `verify.resolve` spans with `path == "device"`.  None
without a `verify.cache_put` span (a program before PR 35 has none: a
partial sum would read as a gain)."""

_PASSES = ("verify.submit.keys", "verify.submit.probe", "verify.cache_put")


def read(obs):
    if not any(s["name"] == "verify.cache_put" for s in obs.spans):
        return None
    flushes = sum(1 for s in obs.spans if s["name"] == "verify.resolve"
                  and s["attrs"].get("path") == "device")
    if not flushes:
        return None
    total = sum(s["dur_ns"] for s in obs.spans if s["name"] in _PASSES)
    return total / flushes / 1e6
