"""Gateway: mean `gateway.flush` span — the one verify call of a flush:
its jobs' select / sign-bytes / add, the service's flush on the device,
the tally."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "gateway.flush"]
    return sum(durs) / len(durs) / 1e6 if durs else None
