"""Light client: per call, the SUM of its `light.store` spans (the trusted
light blocks encoded and saved, the trusted head moved, the prune); mean of
the sums.  A call is a `light.verify_to_height` span, and its saves are the
spans parented under it."""


def read(obs):
    sums = {s["id"]: 0 for s in obs.spans if s["name"] == "light.verify_to_height"}
    for s in obs.spans:
        if s["name"] == "light.store" and s["parent"] in sums:
            sums[s["parent"]] += s["dur_ns"]
    return sum(sums.values()) / len(sums) / 1e6 if sums else None
