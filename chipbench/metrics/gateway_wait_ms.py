"""Gateway: what a job waits for its flush — submit to the start of the
verify call that carries it: the queue, and the linger of the first job
seen.  Sum of the `gateway.flush` spans' `wait_sum_ns` over the sum of
their `jobs`."""


def read(obs):
    flushes = [s["attrs"] for s in obs.spans if s["name"] == "gateway.flush"]
    jobs = sum(a["jobs"] for a in flushes)
    return sum(a["wait_sum_ns"] for a in flushes) / jobs / 1e6 if jobs else None
