"""Service / router: requested rows over padded rows shipped to the
device (utils/devmon), delta over the window."""


def read(obs):
    padded = obs.after["rows_padded"] - obs.before["rows_padded"]
    if padded <= 0:
        return None
    return (obs.after["rows_requested"] - obs.before["rows_requested"]) / padded * 100.0
