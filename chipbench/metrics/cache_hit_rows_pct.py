"""Service / router: of the rows handed to the service in the window, the
share the verified-signature cache answered: cache hits over hits plus the
fresh rows queued (`submitted` counts only those).  0 where a cell bypasses
the cache; in a skipping light client the rows a hop's two checks share."""


def read(obs):
    hits = obs.after["cache_hits"] - obs.before["cache_hits"]
    fresh = obs.after["submitted"] - obs.before["submitted"]
    return hits / (hits + fresh) * 100.0 if hits + fresh > 0 else None
