"""What a per-layer reader is handed: one window's calls, counter deltas,
spans and (in a traced run) the reduced trace.  A reader is a file
chipbench/metrics/<metric>.py with `read(obs) -> float | None`; None means
"nothing to read here" and the metric is left out of the line."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class Observation:
    cell: dict
    device: dict                # as JAX reports it; `count` = the cell's chips
    calls: list                 # generator.Call, in start order; each knows its rows
    window_s: float
    before: dict                # system.counters() at the window's ends
    after: dict
    compiles_in_window: int
    spans: list[dict]           # utils/trace ring, window only (traced run)
    trace: object | None        # tracing.Reduced
    slice: tuple | None         # (t_on, t_off) on the perf_counter clock

    def hist_mean_ms(self, name: str) -> float | None:
        """Mean of a service histogram over the window, from (count, sum)
        deltas."""
        n0, s0 = self.before["hist"][name]
        n1, s1 = self.after["hist"][name]
        return (s1 - s0) / (n1 - n0) * 1e3 if n1 > n0 else None

    def spans_in_slice(self, name: str) -> list[dict]:
        """Spans of that name lying wholly inside the traced slice."""
        if self.slice is None:
            return []
        a, b = self.slice[0] * 1e9, self.slice[1] * 1e9
        return [s for s in self.spans if s["name"] == name
                and s["t0_ns"] >= a and s["t0_ns"] + s["dur_ns"] <= b]

    def rows(self) -> int:
        """Rows the window's calls consulted, all of them."""
        return sum(c.rows for c in self.calls)

    def kernel_s_per_sig(self) -> float | None:
        """Device seconds of the whole verify program per real (unpadded)
        signature: mean program time of the traced slice's flushes (on
        several chips a flush's time is its slowest chip's) over the mean
        flush size of the `verify.device_execute` spans in it."""
        if self.trace is None:
            return None
        execs = self.spans_in_slice("verify.device_execute")
        rows = (sum(s["attrs"]["n"] for s in execs) / len(execs) if execs
                else self.rows() / len(self.calls))
        ev = self.trace.program_events
        return sum(ev) / len(ev) / rows
