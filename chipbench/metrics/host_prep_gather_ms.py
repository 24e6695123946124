"""Host prep: per flush, Σ `verify.host_prep.rows` (the length tests,
joins and copies of keys and signatures) + `verify.host_prep.msgs` (the
messages' lengths, offsets and join), mean over the window's flushes,
paired by `flush`.  None where host prep has no steps."""

from chipbench import flushes


def read(obs):
    return flushes.mean_ms([f["verify.host_prep.rows"] + f["verify.host_prep.msgs"]
                            for f in flushes.host_prep(obs.spans)])
