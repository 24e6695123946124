"""Consensus: the per-message loop of the receive loop's ticks a call —
the sum of its `consensus.handle` spans (each message's WAL write and
handler: add_vote, the step transitions, and the commit when it comes,
`finalize_ms` inside it) over the window's calls.  Nothing to read where
the program has no such span."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "consensus.handle"]
    return sum(durs) / len(obs.calls) / 1e6 if durs and obs.calls else None
