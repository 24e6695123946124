"""Verify surfaces: what the caller still spends collecting futures once
the worker is done — per `verify.wait` span, its end minus the end of the
last `verify.resolve` that began inside it (never under 0); mean over the
waits that held one."""


def read(obs):
    ends = [(s["t0_ns"], s["t0_ns"] + s["dur_ns"]) for s in obs.spans
            if s["name"] == "verify.resolve"]
    tails = []
    for w in obs.spans:
        if w["name"] != "verify.wait":
            continue
        w_end = w["t0_ns"] + w["dur_ns"]
        inside = [end for t0, end in ends if w["t0_ns"] <= t0 <= w_end]
        if inside:
            tails.append(max(0, w_end - max(inside)))
    return sum(tails) / len(tails) / 1e6 if tails else None
