"""Light client: mean `light.hop` span of the jumps a skipping client was
REFUSED (too little trusted power signed the candidate: the header checks,
the address-matched walk over the candidate's commit, a flush of whatever
rows it matched, and then a pivot)."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "light.hop"
            and s["attrs"].get("outcome") == "refused"]
    return sum(durs) / len(durs) / 1e6 if durs else None
