"""Device: share of the traced slice in which no operation ran on the
chip."""


def read(obs):
    if obs.trace is None:
        return None
    return (1.0 - obs.trace.busy_s / obs.trace.window_s) * 100.0
