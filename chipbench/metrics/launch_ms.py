"""Readback / resolve: a flush's launch — `verify.launch`, from the
enqueue's start to the return of the jitted call (on several chips, of
the sharded enqueue with one `device_put` a chip) —, mean over the
window's flushes, paired by `flush`.  None on a program without the
span."""

from chipbench import flushes


def read(obs):
    return flushes.mean_ms([f["verify.launch"]
                            for f in flushes.by_flush(obs.spans, ("verify.launch",)).values()])
