"""Device program: the least time the chip could take for one real
signature (the larger of operations over the published int8 peak and
bytes over the HBM peak, chipbench/work.py) over the verify program's
device time per signature.  The kernel multiplies on the VPU, so this
reads far below 1 %: the honest distance from the integer peak."""

from chipbench import work


def read(obs):
    s = obs.kernel_s_per_sig()
    if s is None:
        return None
    floor_s, _roof = work.floor_seconds_per_sig(obs.device["kind"])
    return floor_s / s * 100.0
