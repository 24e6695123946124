"""The traced slice: `jax.profiler` driven by the harness, the `.xplane.pb`
read with `jax.profiler.ProfileData`, and the reduction from its events to
what the per-layer readers take.

Only `capture` and `load_events` touch JAX; `reduce` works on plain lists,
so it is checked on the recorded trace in chipbench/tests/data/.
"""

from __future__ import annotations

import glob
import os
import shutil
import time
from dataclasses import dataclass, field

SYNC_NAME = "chipbench.sync"
PROGRAM_MARK = "verify_core"      # the jitted function's name (ops/ed25519_jax._jit_for)
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
MIN_LABELLED_GAP_S = 50e-6


class TraceUnreadable(Exception):
    pass


class Slice:
    """Starts the profiler `lead_s` into the window and stops it once
    `slice_s` have passed or `max_flushes` program executions were made in
    it, and never before `min_flushes` executions; driven from the caller's
    thread between calls.  The traffic mix counts executions because they
    are what a trace is made of (~150,000 events each, whatever the rung;
    on four chips stopping the profiler costs ~34 s and 2 s an execution,
    reading it 2 s an execution): a flush is one execution on each of the
    cell's `chips`, so the counts are turned into flushes here, as many as
    keep the executions between the two (3 to 6 on one chip, 1 on four)."""

    def __init__(self, out_dir: str, lead_s: float, slice_s: float,
                 min_flushes: int, max_flushes: int, chips: int = 1):
        self.out_dir, self.lead_s = out_dir, lead_s
        self.slice_s = slice_s
        self.min_flushes = -(-min_flushes // chips)
        self.max_flushes = max(self.min_flushes, max_flushes // chips)
        self.t_window = None
        self.t_on = self.t_off = None       # perf_counter, just inside the slice
        self.sync_perf_ns = None
        self.calls_at_on = 0

    def between(self, calls_done: int) -> None:
        import jax

        now = time.perf_counter()
        if self.t_window is None:
            self.t_window = now
        if self.t_on is None:
            if now - self.t_window >= self.lead_s:
                shutil.rmtree(self.out_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0   # per-call Python hooks distort the host path
                opts.host_tracer_level = 1     # TraceAnnotation (the sync mark) only
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(self.out_dir, profiler_options=opts)
                with jax.profiler.TraceAnnotation(SYNC_NAME):
                    self.sync_perf_ns = time.perf_counter_ns()
                self.t_on = time.perf_counter()
                self.calls_at_on = calls_done
        elif self.t_off is None:
            made = calls_done - self.calls_at_on
            if made >= self.min_flushes and (now - self.t_on >= self.slice_s
                                             or made >= self.max_flushes):
                self.stop()

    def stop(self) -> None:
        import jax

        if self.t_on is not None and self.t_off is None:
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()


REHEARSAL_PLANE = "/device:CPU-rehearsal"


def _rehearsal_plane(host_lines) -> dict:
    """XLA-CPU has no device plane: its thunks run on host threads.  A
    rehearsal folds those threads into a stand-in plane so that the whole
    traced path runs here before it runs on the chip.  Never used when a
    real device is present, and no number of it is a device metric."""
    ops, modules = [], []
    for ln in host_lines:
        if not ln.name.startswith(("tf_XLAEigen", "tf_XLAPjRtCpuClient")):
            continue
        for e in ln.events:
            row = [e.name, float(e.start_ns), float(e.duration_ns)]
            if e.name == "ThunkExecutor::Execute":
                if ln.name.startswith("tf_XLAPjRtCpuClient"):
                    modules.append([f"jit_{PROGRAM_MARK}(rehearsal)"] + row[1:])
            elif not e.name.startswith(("ThreadpoolListener", "end: ")):
                ops.append(row)
    return {"XLA Ops": ops, "XLA Modules": modules}


def short_name(name: str) -> str:
    """The profiler names a TPU operation by its whole HLO text; keep the
    result's name (`%multiply_add_fusion.4347 = ...` -> `multiply_add_fusion.4347`)."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def load_events(out_dir: str, rehearse: bool = False) -> dict:
    """{"planes": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "sync_ns": start of the sync annotation on the trace's clock}.
    Device planes are kept whole; of the host planes only the sync
    annotation is taken."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise TraceUnreadable(f"no .xplane.pb under {out_dir}")
    pd = ProfileData.from_file(files[-1])
    planes: dict = {}
    sync_ns = None
    inventory = []
    for plane in pd.planes:
        lines = list(plane.lines)
        inventory.append((plane.name, [ln.name for ln in lines]))
        if plane.name.startswith("/device:"):
            planes[plane.name] = {
                ln.name: [[short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                          for e in ln.events] for ln in lines
                if ln.name in MODULE_LINES + OP_LINES}
        elif rehearse and plane.name == "/host:CPU":
            planes[REHEARSAL_PLANE] = _rehearsal_plane(lines)
        if not plane.name.startswith("/device:") and sync_ns is None:
            for ln in lines:
                for e in ln.events:
                    if e.name == SYNC_NAME:
                        sync_ns = float(e.start_ns)
                        break
                if sync_ns is not None:
                    break
    return {"planes": planes, "sync_ns": sync_ns, "inventory": inventory}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                    # mean over the cell's chips
    program_events: list[float]      # per whole flush of the slice: device seconds of its verify
                                     # program, on several chips those of the slowest chip
    device_ops: list[list]           # [[name, seconds], ...] top 10, seconds summed over the chips
    flush_programs: list[list[float]] = field(default_factory=list)  # per whole flush: each
                                     # chip's program event, seconds
    gaps: list[tuple[float, float]] = field(default_factory=list)  # idle gaps, perf_counter seconds
    idle_gaps: list[list] = field(default_factory=list)            # [[label, seconds], ...] top 10


def reduce(events: dict, t_on: float, t_off: float, sync_perf_ns: int,
           spans: list[dict] | None = None, chips: int = 1) -> Reduced:
    """`t_on`/`t_off`: the slice on the perf_counter clock (seconds);
    `sync_perf_ns`: perf_counter_ns inside the sync annotation.  Device
    events are clipped to the slice.  Every device plane is walked: busy
    time is the mean over the `chips` of the cell, the idle gaps are the
    first plane's, and the program events of the planes are gathered into
    flushes (`_flushes`); a flush counts only when every chip's event of
    it lies wholly inside the slice."""
    def found() -> str:
        inv = events.get("inventory") or [(p, list(ls)) for p, ls in events["planes"].items()]
        names = sorted({e[0] for ls in events["planes"].values()
                        for ln, evs in ls.items() if ln in MODULE_LINES for e in evs})
        return f"planes and lines found: {inv}; module names: {names[:20]}"

    if events.get("sync_ns") is None:
        raise TraceUnreadable(f"no {SYNC_NAME!r} annotation on a host plane; " + found())
    device_planes = {p: ls for p, ls in events["planes"].items()
                     if p.startswith("/device:TPU:")} or {
        p: ls for p, ls in events["planes"].items() if p.startswith("/device:")}
    if not device_planes:
        raise TraceUnreadable("no device plane in the trace; " + found())
    # trace clock (ns) -> perf_counter (s)
    off = sync_perf_ns - events["sync_ns"]

    def perf(ns: float) -> float:
        return (ns + off) / 1e9

    busy_total = 0.0
    used = 0
    programs: list[tuple] = []       # (start, end, plane, whole, seconds), perf_counter clock
    op_seconds: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for plane, lines in sorted(device_planes.items()):
        op_line = next((lines[n] for n in OP_LINES if n in lines), None)
        mod_line = next((lines[n] for n in MODULE_LINES if n in lines), None)
        if op_line is None and mod_line is None:
            continue
        clipped = []
        for name, start, dur in (op_line if op_line is not None else mod_line):
            a, b = max(perf(start), t_on), min(perf(start + dur), t_off)
            if b > a:
                clipped.append((a, b))
                op_seconds[name] = op_seconds.get(name, 0.0) + (b - a)
        if not clipped:
            continue
        used += 1
        merged = _union(clipped)
        busy_total += sum(b - a for a, b in merged)
        if used == 1:
            edges = [t_on] + [x for ab in merged for x in ab] + [t_off]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
        for name, start, dur in (mod_line or []):
            a, b = perf(start), perf(start + dur)
            if PROGRAM_MARK in name and b > t_on and a < t_off:
                programs.append((a, b, plane, a >= t_on and b <= t_off, dur / 1e9))
    flushes = _flushes(programs)
    if not used:
        raise TraceUnreadable("no device operation inside the traced slice; " + found())
    if not flushes:
        raise TraceUnreadable(f"no whole {PROGRAM_MARK!r} program event inside "
                              "the traced slice; " + found())
    red = Reduced(window_s=t_off - t_on, busy_s=busy_total / max(used, chips),
                  program_events=[max(f) for f in flushes],
                  device_ops=[[n, s] for n, s in sorted(
                      op_seconds.items(), key=lambda kv: -kv[1])[:10]],
                  flush_programs=flushes, gaps=gaps)
    red.idle_gaps = label_gaps(gaps, spans or [])
    return red


def _flushes(programs: list[tuple]) -> list[list[float]]:
    """Program events (start, end, plane, whole, seconds) of all planes ->
    per flush the seconds of each chip's event.  One flush runs the
    program once on each chip at the same time, and flushes follow one
    another: an event belongs to the open flush if it starts before that
    flush's last end on a plane the flush does not hold yet.  A flush of
    which any event is cut by the slice's edge is left out."""
    out: list[list[float]] = []
    planes: set = set()
    end, whole = float("-inf"), True
    for a, b, plane, inside, seconds in sorted(programs):
        if a >= end or plane in planes:
            if not whole:
                out.pop()
            out.append([])
            planes, end, whole = set(), b, True
        out[-1].append(seconds)
        planes.add(plane)
        end, whole = max(end, b), whole and inside
    if out and not whole:
        out.pop()
    return out


def label_gaps(gaps: list[tuple[float, float]], spans: list[dict]) -> list[list]:
    """Idle seconds by what the host was doing: a gap is cut at the edges
    of the harness/service spans that reach into it, and each piece goes
    to the shortest span covering it (spans on the perf_counter clock, as
    utils/trace records them)."""
    by_label: dict[str, float] = {}

    def add(label: str, seconds: float) -> None:
        by_label[label] = by_label.get(label, 0.0) + seconds

    for a, b in gaps:
        if b - a < MIN_LABELLED_GAP_S:
            add("gaps under 50 us (between device operations)", b - a)
            continue
        near = [(s["t0_ns"] / 1e9, (s["t0_ns"] + s["dur_ns"]) / 1e9, s["name"])
                for s in spans
                if s["t0_ns"] / 1e9 < b and (s["t0_ns"] + s["dur_ns"]) / 1e9 > a]
        cuts = sorted({a, b, *(t for s0, s1, _ in near for t in (s0, s1) if a < t < b)})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            cover = [(s1 - s0, name) for s0, s1, name in near if s0 <= mid <= s1]
            add(min(cover)[1] if cover else "no span (between calls, or harness)",
                hi - lo)
    return [[n, s] for n, s in sorted(by_label.items(), key=lambda kv: -kv[1])[:10]]


def excerpt(events: dict, t_on: float, sync_perf_ns: int, programs: int = 1) -> dict:
    """A small recorded trace for chipbench/tests/data/: the first
    `programs` whole program events of the slice with the operations
    inside them, on the trace's own clock, and what `reduce` gives on it."""
    off = sync_perf_ns - events["sync_ns"]
    plane, lines = next((p, ls) for p, ls in sorted(events["planes"].items())
                        if any(PROGRAM_MARK in e[0] for e in ls.get(MODULE_LINES[0], [])))
    mods = [e for e in lines[MODULE_LINES[0]]
            if PROGRAM_MARK in e[0] and (e[1] + off) / 1e9 >= t_on][:programs]
    a, b = mods[0][1], mods[-1][1] + mods[-1][2]
    ops = [e for e in lines.get(OP_LINES[0], []) if e[1] >= a and e[1] + e[2] <= b]
    pad = 0.002
    cut = {"planes": {plane: {MODULE_LINES[0]: mods, OP_LINES[0]: ops}},
           "sync_ns": events["sync_ns"]}
    t0, t1 = (a + off) / 1e9 - pad, (b + off) / 1e9 + pad
    red = reduce(cut, t0, t1, sync_perf_ns)
    return {"events": cut, "t_on": t0, "t_off": t1, "sync_perf_ns": sync_perf_ns,
            "expect": {"programs": len(red.program_events), "busy_s": red.busy_s,
                       "window_s": red.window_s}}
