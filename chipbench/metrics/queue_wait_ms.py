"""Service / router: mean time a request sat in the submission queue
before its flush (`verify_queue_wait_seconds`, delta over the window)."""


def read(obs):
    return obs.hist_mean_ms("queue_wait")
