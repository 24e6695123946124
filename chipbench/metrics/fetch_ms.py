"""Light client: per call, the SUM of its `light.fetch` spans (a provider
fetch with its decode and the block's basic validation: the set's hash,
the header's); mean of the sums.  A call is a `light.verify_to_height`
span, and its fetches are the spans parented under it."""


def read(obs):
    sums = {s["id"]: 0 for s in obs.spans if s["name"] == "light.verify_to_height"}
    for s in obs.spans:
        if s["name"] == "light.fetch" and s["parent"] in sums:
            sums[s["parent"]] += s["dur_ns"]
    return sum(sums.values()) / len(sums) / 1e6 if sums else None
