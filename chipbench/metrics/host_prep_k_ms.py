"""Host prep: the native SHA-512 and reduction mod L of a flush alone
(`verify.host_prep.k`, whose `threads` says how many threads the call
started), mean over the window's flushes.  None where host prep has no
steps."""

from chipbench import flushes


def read(obs):
    return flushes.mean_ms([f["verify.host_prep.k"] for f in flushes.host_prep(obs.spans)])
