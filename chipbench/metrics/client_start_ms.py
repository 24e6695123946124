"""Light client: `light.client_start` — `Client.__init__` from the trust
options' checks to the end of its initialisation (the trusted store read
back, `decodes` blocks decoded) —, mean over the window's calls, one
client a call.  None on a program without the span."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "light.client_start"]
    return sum(durs) / len(durs) / 1e6 if durs else None
