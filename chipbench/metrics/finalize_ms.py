"""Consensus: the commit of a height — the mean `consensus.finalize`
span (finalize_commit: the block's validation, its save, the WAL barrier,
the kvstore apply, the state's save, the advance to the next height).
Nothing to read where the program has no such span."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "consensus.finalize"]
    return sum(durs) / len(durs) / 1e6 if durs else None
