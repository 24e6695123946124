"""Device program: compile events (utils/devmon, from JAX's own events)
that ended inside the window.  Expected 0."""


def read(obs):
    return float(obs.compiles_in_window)
