"""Verify surfaces: per call, the `commit.select` spans (commit basics and
the index-selection loop) plus the `commit.add` spans (the `bv.add` loop)
of its jobs; mean of the sums.  A call is the spans of one thread up to
and including its `commit.verify`; one refused before that is left out."""


def read(obs):
    sums = []
    open_sum: dict = {}          # caller thread -> select + add so far
    for s in sorted(obs.spans, key=lambda s: s["t0_ns"]):
        if s["name"] in ("commit.select", "commit.add"):
            open_sum[s["tid"]] = open_sum.get(s["tid"], 0) + s["dur_ns"]
        elif s["name"] == "commit.verify" and s["tid"] in open_sum:
            sums.append(open_sum.pop(s["tid"]))
    return sum(sums) / len(sums) / 1e6 if sums else None
