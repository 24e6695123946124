"""Host prep: per flush, `verify.host_prep` less its gather
(`host_prep_gather_ms`) and its k (`host_prep_k_ms`) — the s < L compare,
the padding to the rung and the span's own time between its steps —,
mean over the window's flushes, paired by `flush`.  The three readings
add up to the mean `verify.host_prep` span.  None where host prep has no
steps."""

from chipbench import flushes


def read(obs):
    return flushes.mean_ms([f["verify.host_prep"] - f["verify.host_prep.rows"]
                            - f["verify.host_prep.msgs"] - f["verify.host_prep.k"]
                            for f in flushes.host_prep(obs.spans)])
