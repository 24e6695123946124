"""Device: share of the traced slice in which no operation ran on the
chip.  On several chips: 1 - the mean of the chips' busy time over the
slice (a chip of the cell that ran nothing in the slice counts as idle
throughout)."""


def read(obs):
    if obs.trace is None:
        return None
    return (1.0 - obs.trace.busy_s / obs.trace.window_s) * 100.0
