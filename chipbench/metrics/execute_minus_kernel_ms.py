"""Readback / resolve: the `verify.device_execute` span (enqueue to
readback, host clock) minus the program's device time, means over the
traced slice: transfer in, launch, readback.  On several chips the
program's time is the slowest chip's, and what is left holds one
`device_put` per chip and the fan-in of the verdicts."""


def read(obs):
    execs = obs.spans_in_slice("verify.device_execute")
    if obs.trace is None or not execs:
        return None
    ev = obs.trace.program_events
    return (sum(s["dur_ns"] for s in execs) / len(execs) / 1e6
            - sum(ev) / len(ev) * 1e3)
