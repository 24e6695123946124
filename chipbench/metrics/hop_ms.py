"""Light client: mean `light.hop` span of the jumps a skipping client
ACCEPTED — the trusting check against the trusted set, then the new set's
own light check, each with its flush.  Nothing to read where the program
has no such span."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "light.hop"
            and s["attrs"].get("outcome") == "accepted"]
    return sum(durs) / len(durs) / 1e6 if durs else None
