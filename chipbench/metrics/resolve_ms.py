"""Readback / resolve: mean `verify.resolve` span of the flushes the
device answered (`path == "device"`): cache puts, end-to-end observes and
`future.set_result`, once per request."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "verify.resolve"
            and s["attrs"].get("path") == "device"]
    return sum(durs) / len(durs) / 1e6 if durs else None
