"""Device: how unevenly the chips of a sharded flush finish — per flush of
the traced slice, (longest - shortest `verify_core` program event among
the chips' planes) over the longest, mean over the flushes.  The slowest
chip sets the call's time.  Nothing to read where one plane ran the
program."""


def read(obs):
    if obs.trace is None:
        return None
    skews = [(max(f) - min(f)) / max(f) for f in obs.trace.flush_programs if len(f) > 1]
    return sum(skews) / len(skews) * 100.0 if skews else None
