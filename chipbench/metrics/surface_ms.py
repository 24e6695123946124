"""Verify surfaces: what a call spends outside the service — sign-bytes
assembly and job set-up before its `verify.submit` span starts, future
resolution, tallying and raising after its flush's `verify.device_execute`
span ends.  Mean over the window's calls, ms."""


def read(obs):
    submits = [s for s in obs.spans if s["name"] == "verify.submit"]
    execs = [s for s in obs.spans if s["name"] == "verify.device_execute"]
    if not submits or not execs:
        return None
    total, n = 0.0, 0
    si = ei = 0
    for c in obs.calls:
        a, b = c.t_start * 1e9, (c.t_start + c.seconds) * 1e9
        while si < len(submits) and submits[si]["t0_ns"] < a:
            si += 1
        while ei < len(execs) and execs[ei]["t0_ns"] + execs[ei]["dur_ns"] < a:
            ei += 1
        if si == len(submits) or ei == len(execs):
            break
        sub, ex = submits[si], execs[ei]
        end = ex["t0_ns"] + ex["dur_ns"]
        if sub["t0_ns"] <= b and end <= b:
            total += (b - a) - (end - sub["t0_ns"])
            n += 1
    return total / n / 1e6 if n else None
