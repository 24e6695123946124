"""Device program: device time of the whole verify program per real
(unpadded) signature, from the traced slice (see
Observation.kernel_s_per_sig)."""


def read(obs):
    s = obs.kernel_s_per_sig()
    return None if s is None else s * 1e6
