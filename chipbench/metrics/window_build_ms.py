"""Blocksync window: mean `blocksync.window_jobs` span — what a step
spends before its first commit is looked at: the static-valset prefix, the
cut, the job list, and the newest taken block's part set and hash for the
pair check.  Nothing to read where the program has no such span."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "blocksync.window_jobs"]
    return sum(durs) / len(durs) / 1e6 if durs else None
