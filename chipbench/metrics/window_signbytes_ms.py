"""Verify surfaces: per step, the SUM of its jobs' `commit.sign_bytes`
spans (one native call a job; `signbytes_ms` is the mean of one span, so
of one job); mean of the sums.  A step is the spans of one thread up to
and including its `commit.verify`, as `assemble_ms` pairs them."""


def read(obs):
    sums = []
    open_sum: dict = {}          # caller thread -> sign-bytes so far
    for s in sorted(obs.spans, key=lambda s: s["t0_ns"]):
        if s["name"] == "commit.sign_bytes":
            open_sum[s["tid"]] = open_sum.get(s["tid"], 0) + s["dur_ns"]
        elif s["name"] == "commit.verify" and s["tid"] in open_sum:
            sums.append(open_sum.pop(s["tid"]))
    return sum(sums) / len(sums) / 1e6 if sums else None
