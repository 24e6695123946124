"""Consensus: the receive loop's tick precheck a call — the sum of its
`consensus.precheck` spans (the batch verification of a burst's votes,
the service's submit and wait under it) over the window's calls.  Nothing
to read where the program has no such span."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "consensus.precheck"]
    return sum(durs) / len(obs.calls) / 1e6 if durs and obs.calls else None
