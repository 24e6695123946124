"""Device program: the least time the cell's chips could take for one real
signature (the larger of operations over the published int8 peak and
bytes over the HBM peak of one chip, chipbench/work.py, over the number
of chips: a flush is sharded over all of them) over the verify program's
device time per signature.  The kernel multiplies on the VPU, so this
reads far below 1 %: the honest distance from the integer peak."""

from chipbench import work


def read(obs):
    s = obs.kernel_s_per_sig()
    if s is None:
        return None
    floor_s, _roof = work.floor_seconds_per_sig(obs.device["kind"])
    return floor_s / obs.device["count"] / s * 100.0
