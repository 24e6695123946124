"""Service / router: flushes the service made in the window over the
window's calls (counter deltas).  One in the one-commit cells; a skipping
light client pays the fixed cost of a flush once a check."""


def read(obs):
    if not obs.calls:
        return None
    return (obs.after["flushes"] - obs.before["flushes"]) / len(obs.calls)
