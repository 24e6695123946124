"""Device: share of the traced slice's idle seconds that no span of the
program covers — what `breakdown.idle_gaps` lists under `harness.call
(outside the service's spans)` and `no span (...)`, computed from the
gaps themselves so that the list's cut at ten labels cannot hide it.
Gaps under 50 us (between device operations) count as idle, not as
unattributed, as they do there."""

from chipbench import tracing


def read(obs):
    if obs.trace is None:
        return None
    idle_s = obs.trace.window_s - obs.trace.busy_s
    if idle_s <= 0:
        return None
    spans = [(s["t0_ns"] / 1e9, (s["t0_ns"] + s["dur_ns"]) / 1e9) for s in obs.spans]
    bare = 0.0
    for a, b in obs.trace.gaps:
        if b - a < tracing.MIN_LABELLED_GAP_S:
            continue
        covered = tracing._union([(max(a, s0), min(b, s1)) for s0, s1 in spans
                                  if s0 < b and s1 > a])
        bare += (b - a) - sum(hi - lo for lo, hi in covered)
    return bare / idle_s * 100.0
