"""Service / router: mean `verify.account` span — the worker's per-request
queue-wait observes, after a batch is taken and before it is routed."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "verify.account"]
    return sum(durs) / len(durs) / 1e6 if durs else None
