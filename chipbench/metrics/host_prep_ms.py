"""Host prep: mean per flush of `verify_host_prep_seconds` (SHA-512, s < L,
packing, padding), delta over the window."""


def read(obs):
    return obs.hist_mean_ms("host_prep")
