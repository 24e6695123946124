"""Continuous statistical profiler (ISSUE 18): the per-function layer
under the health watchdog and the SLO engine.

A daemon thread samples ``sys._current_frames()`` at ``TM_TPU_PROF_HZ``
(default ~19 Hz — off-beat, so the sampler never phase-locks with 1 Hz
tickers) and folds every thread's stack into bounded per-window
aggregates in collapsed/folded-stack format (``a;b;c count`` — the
flamegraph input format), attributed to a subsystem bucket (consensus /
verify-service / gateway / rpc / health / ...) by thread-name prefix
first and innermost-``tendermint_tpu``-frame second (the asyncio loop
runs consensus AND rpc on MainThread, so thread names alone cannot
split them).

Surfaces:

- a ring of recent windows plus a cumulative profile
  (``folded_recent()`` — the flight recorder's ``profile.folded``),
- on-demand delta captures (``capture(seconds)`` — the
  ``/debug/pprof/profile?seconds=N`` route; ``export_chrome()`` renders
  a capture as trace-event JSON for Perfetto, the trace.py idiom),
- rate-limited trigger captures (``trigger()`` — health critical
  transitions and fleet ``slo_burn`` records arm it),
- metric feeds (``subsystem_samples()`` / ``overhead_samples()``) and
  function tables (``function_table()`` / ``diff_folded()`` — the
  ``tendermint-tpu prof`` CLI and its ``--diff`` regression gate).

Env-gated per the sink idiom (PR 2): ``TM_TPU_PROF`` (default ON)
routes to ``NOP`` when off, so every call site costs one attribute
load + branch; ``from_env()`` is the only place the environment is
read.  The monotonic clock is injectable (``clock=``) so window/ring
units are deterministic under test; wall stamps flow through
``utils/clock.wall_ns()``.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time

from collections import deque

from tendermint_tpu.utils import clock as _clockmod

_log = logging.getLogger(__name__)

ENV_FLAG = "TM_TPU_PROF"

#: default sampling rate — deliberately off-beat (a prime ~19 Hz) so
#: samples never phase-lock with 1 Hz block intervals or 2 Hz health
#: ticks and silently over/under-count a periodic phase
DEFAULT_HZ = 19.0
DEFAULT_WINDOW_S = 10.0
DEFAULT_RING = 12          # ~2 minutes of pre-critical history
DEFAULT_TRIGGER_MIN_S = 30.0
MAX_STACK_DEPTH = 64
MAX_STACKS_PER_WINDOW = 512
MAX_CUMULATIVE_STACKS = 4096

#: thread-name prefix -> subsystem bucket (first match wins); threads
#: not listed here fall through to the frame scan below
_THREAD_BUCKETS = (
    ("tm-verify-service", "verify-service"),
    ("tm-threshold-measure", "verify-service"),
    ("tm-gateway-coalescer", "gateway"),
    ("tm-aot-warm", "device"),
    ("tm-device-warmup", "device"),
    ("health-", "health"),
    ("prof-", "prof"),
)

#: package-path fragment -> subsystem bucket, scanned innermost frame
#: first — MainThread runs the asyncio loop, so consensus vs rpc is
#: decided by which tendermint_tpu module the thread is executing
_FRAME_BUCKETS = (
    ("tendermint_tpu/consensus/", "consensus"),
    ("tendermint_tpu/rpc/", "rpc"),
    ("tendermint_tpu/gateway/", "gateway"),
    ("tendermint_tpu/mempool/", "mempool"),
    ("tendermint_tpu/p2p/", "p2p"),
    ("tendermint_tpu/crypto/", "verify-service"),
    ("tendermint_tpu/fleet/", "fleet"),
    ("tendermint_tpu/utils/profiler.py", "prof"),
    ("tendermint_tpu/utils/health.py", "health"),
)


# ---------------------------------------------------------------------------
# stack folding
# ---------------------------------------------------------------------------

_label_cache: dict[str, str] = {}


def _file_label(filename: str) -> str:
    """Stable short path for a frame: the tendermint_tpu-relative path
    when the frame is ours, the basename otherwise."""
    got = _label_cache.get(filename)
    if got is not None:
        return got
    norm = filename.replace("\\", "/")
    idx = norm.rfind("tendermint_tpu/")
    label = norm[idx:] if idx >= 0 else norm.rsplit("/", 1)[-1]
    if len(_label_cache) < 4096:
        _label_cache[filename] = label
    return label


def _frame_label(frame) -> str:
    code = frame.f_code
    return f"{_file_label(code.co_filename)}:{code.co_name}"


def classify(thread_name: str, frames: list) -> str:
    """Subsystem bucket for one sampled thread: name prefix first, then
    the innermost tendermint_tpu frame, else ``other``."""
    for prefix, bucket in _THREAD_BUCKETS:
        if thread_name.startswith(prefix):
            return bucket
    for frame in frames:          # innermost first
        norm = frame.f_code.co_filename.replace("\\", "/")
        for fragment, bucket in _FRAME_BUCKETS:
            if fragment in norm:
                return bucket
    return "other"


def render_folded(stacks: dict, header: str = "") -> str:
    """Collapsed-stack text (``key count`` per line, flamegraph-ready);
    ``header`` lines are emitted as ``#`` comments that
    ``parse_folded`` skips."""
    lines = [f"# {ln}" for ln in header.splitlines() if ln]
    lines.extend(f"{key} {count}" for key, count in sorted(stacks.items()))
    return "\n".join(lines) + "\n"


def parse_folded(text: str) -> dict:
    """Inverse of ``render_folded``: folded text -> {stack: count}."""
    out: dict[str, int] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        key, _, count = line.rpartition(" ")
        if not key:
            continue
        try:
            out[key] = out.get(key, 0) + int(count)
        except ValueError:
            continue
    return out


def merge_stacks(dicts) -> dict:
    out: dict[str, int] = {}
    for d in dicts:
        for key, count in d.items():
            out[key] = out.get(key, 0) + count
    return out


def function_table(stacks: dict) -> dict:
    """Per-subsystem function table from folded stacks:
    ``{subsystem: {"samples": n, "functions": {func: {"self", "cum"}}}}``
    — self = leaf-frame samples, cum = appears-anywhere samples
    (recursion counted once per stack)."""
    out: dict[str, dict] = {}
    for key, count in stacks.items():
        parts = key.split(";")
        if len(parts) < 3:
            continue
        sub, frames = parts[0], parts[2:]
        blk = out.setdefault(sub, {"samples": 0, "functions": {}})
        blk["samples"] += count
        seen = set()
        for f in frames:
            if f in seen:
                continue
            seen.add(f)
            row = blk["functions"].setdefault(f, {"self": 0, "cum": 0})
            row["cum"] += count
        blk["functions"][frames[-1]]["self"] += count
    return out


def self_shares(stacks: dict) -> dict:
    """Flat ``{func: fraction-of-samples-as-leaf}`` across subsystems —
    the quantity ``diff_folded`` compares."""
    total = 0
    counts: dict[str, int] = {}
    for key, count in stacks.items():
        parts = key.split(";")
        if len(parts) < 3:
            continue
        total += count
        leaf = parts[-1]
        counts[leaf] = counts.get(leaf, 0) + count
    if not total:
        return {}
    return {f: c / total for f, c in counts.items()}


def diff_folded(base: dict, new: dict, abs_threshold: float = 0.05,
                rel_threshold: float = 0.25) -> dict:
    """Function-level regression diff between two folded profiles, in
    benchdiff's direction-aware idiom: every function's class is
    *self-time share, lower is better*.  A function regresses when its
    share grew by more than ``abs_threshold`` (absolute percentage
    points) AND by more than ``rel_threshold`` relatively (both gates,
    so a 0.1% -> 0.2% blip and a 40% -> 41% drift are equally quiet);
    the mirror image is an improvement.  Self-diff is all-ok by
    construction."""
    sb, sn = self_shares(base), self_shares(new)
    rows = []
    for func in sorted(set(sb) | set(sn)):
        b, n = sb.get(func, 0.0), sn.get(func, 0.0)
        delta = n - b
        rel = (delta / b) if b else (float("inf") if n else 0.0)
        verdict = "ok"
        if delta > abs_threshold and (b == 0.0 or rel > rel_threshold):
            verdict = "regression"
        elif -delta > abs_threshold and (n == 0.0 or -rel > rel_threshold):
            verdict = "improvement"
        rows.append({"func": func, "base": round(b, 4), "new": round(n, 4),
                     "delta": round(delta, 4), "verdict": verdict})
    rows.sort(key=lambda r: -abs(r["delta"]))
    regressions = [r["func"] for r in rows if r["verdict"] == "regression"]
    return {"rows": rows, "regressions": regressions,
            "ok": not regressions,
            "abs_threshold": abs_threshold, "rel_threshold": rel_threshold}


def export_chrome(cap: dict) -> str:
    """A capture as chrome://tracing / Perfetto trace-event JSON (the
    trace.py exporter idiom): one complete ("X") event per distinct
    folded stack, duration = samples x sample period, lanes per
    thread, category = subsystem."""
    hz = float(cap.get("hz") or DEFAULT_HZ)
    dur_us = 1e6 / hz
    pid = os.getpid()
    tids: dict[str, int] = {}
    events = []
    cursors: dict[int, float] = {}
    for key, count in sorted(cap.get("stacks", {}).items()):
        parts = key.split(";")
        if len(parts) < 3:
            continue
        sub, thread, frames = parts[0], parts[1], parts[2:]
        tid = tids.setdefault(thread, len(tids) + 1)
        ts = cursors.get(tid, 0.0)
        dur = count * dur_us
        events.append({
            "ph": "X",
            "name": frames[-1],
            "cat": sub,
            "ts": round(ts, 1),
            "dur": round(dur, 1),
            "pid": pid,
            "tid": tid,
            "args": {"stack": ";".join(frames), "samples": count},
        })
        cursors[tid] = ts + dur
    return json.dumps({"traceEvents": events, "displayTimeUnit": "ms"})


# ---------------------------------------------------------------------------
# the sampler
# ---------------------------------------------------------------------------

class _Window:
    __slots__ = ("start", "sweeps", "samples", "stacks", "by_subsystem")

    def __init__(self, start: float):
        self.start = start
        self.sweeps = 0
        self.samples = 0
        self.stacks: dict[str, int] = {}
        self.by_subsystem: dict[str, int] = {}


def _bounded_add(stacks: dict, key: str, count: int, cap: int) -> None:
    """Add to a bounded stack dict; once full, new stacks collapse into
    a per-subsystem ``(other)`` bucket so totals stay exact."""
    if key in stacks or len(stacks) < cap:
        stacks[key] = stacks.get(key, 0) + count
        return
    sub = key.split(";", 1)[0]
    over = f"{sub};(overflow);(other)"
    stacks[over] = stacks.get(over, 0) + count


class Profiler:
    """One node's continuous sampler.  ``enabled`` is True so the
    one-branch guard at call sites passes; ``NOP`` is the disabled
    twin.  ``sample()`` folds one sweep of every live thread (the
    background thread is just a loop over it — same shape as the
    health monitor); ``capture(seconds)`` runs a blocking delta
    capture at the configured rate."""

    enabled = True

    def __init__(self, node: str = "", hz: float = DEFAULT_HZ,
                 window_s: float = DEFAULT_WINDOW_S, ring: int = DEFAULT_RING,
                 trigger_min_s: float = DEFAULT_TRIGGER_MIN_S,
                 max_stacks: int = MAX_STACKS_PER_WINDOW,
                 clock=time.monotonic):
        self.node = node
        self.hz = min(200.0, max(0.1, hz))
        self.window_s = max(0.1, window_s)
        self.trigger_min_s = max(0.0, trigger_min_s)
        self.max_stacks = max(16, max_stacks)
        self._clock = clock
        self._lock = threading.Lock()
        self._win = _Window(clock())
        self._ring: deque = deque(maxlen=max(1, ring))
        self._cum_stacks: dict[str, int] = {}
        self._by_subsystem: dict[str, int] = {}
        self.sweeps = 0
        self.samples = 0
        self.overhead_s = 0.0
        self.triggers = 0
        self.trigger_suppressed = 0
        self._last_trigger: float | None = None
        self._last_trigger_reason = ""
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    # -- sampling -------------------------------------------------------

    def sample(self) -> list:
        """One sweep over every live thread (except the caller —
        sampling the sampler mid-fold is pure noise): fold each stack,
        roll the window, feed ring + cumulative + counters.  Returns
        the sweep's ``(subsystem, thread, folded_key)`` entries so
        ``capture`` can aggregate a delta window locally.  Public:
        tests and the ``prof-overhead`` bench stage call it directly."""
        t0 = time.perf_counter()
        me = threading.get_ident()
        names = {t.ident: t.name for t in threading.enumerate()}
        entries = []
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            frames = []
            f = frame
            while f is not None and len(frames) < MAX_STACK_DEPTH:
                frames.append(f)
                f = f.f_back
            name = names.get(tid, f"tid-{tid}")
            sub = classify(name, frames)     # frames: innermost first
            labels = [_frame_label(fr) for fr in reversed(frames)]
            entries.append((sub, name, ";".join([sub, name] + labels)))
        now = self._clock()
        with self._lock:
            if now - self._win.start >= self.window_s:
                self._ring.append(self._win)
                self._win = _Window(now)
            w = self._win
            w.sweeps += 1
            self.sweeps += 1
            for sub, _name, key in entries:
                w.samples += 1
                self.samples += 1
                w.by_subsystem[sub] = w.by_subsystem.get(sub, 0) + 1
                self._by_subsystem[sub] = self._by_subsystem.get(sub, 0) + 1
                _bounded_add(w.stacks, key, 1, self.max_stacks)
                _bounded_add(self._cum_stacks, key, 1,
                             MAX_CUMULATIVE_STACKS)
            self.overhead_s += time.perf_counter() - t0
        return entries

    def capture(self, seconds: float = 2.0) -> dict:
        """Blocking delta capture: sweep at the configured rate for
        ``seconds`` and return the aggregate (the windows and
        cumulative profile are fed too — capture samples are real
        samples).  Callers off the event loop only (the pprof route
        runs it via ``asyncio.to_thread``)."""
        seconds = min(120.0, max(0.05, float(seconds)))
        n = max(1, int(round(seconds * self.hz)))
        interval = 1.0 / self.hz
        stacks: dict[str, int] = {}
        by_sub: dict[str, int] = {}
        sweeps = 0
        for i in range(n):
            for sub, _name, key in self.sample():
                stacks[key] = stacks.get(key, 0) + 1
                by_sub[sub] = by_sub.get(sub, 0) + 1
            sweeps += 1
            if i < n - 1:
                time.sleep(interval)
        return {
            "enabled": True,
            "node": self.node,
            "hz": self.hz,
            "seconds": seconds,
            "sweeps": sweeps,
            "samples": sum(by_sub.values()),
            "by_subsystem": by_sub,
            "stacks": stacks,
            "w": _clockmod.wall_ns(),
        }

    # -- trigger-driven capture (health critical / fleet slo_burn) ------

    def trigger(self, reason: str = "") -> bool:
        """A degradation event wants a profile.  Rate-limited
        (``trigger_min_s`` between accepts — escalation storms must
        not turn the profiler into the load).  The host-side profile
        itself rides the flight-recorder bundle (``folded_recent``), so
        accepting is just bookkeeping."""
        now = self._clock()
        with self._lock:
            if (self._last_trigger is not None
                    and now - self._last_trigger < self.trigger_min_s):
                self.trigger_suppressed += 1
                return False
            self._last_trigger = now
            self.triggers += 1
            self._last_trigger_reason = reason
        return True

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        """Spawn the sampling daemon thread (idempotent)."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        interval = 1.0 / self.hz

        def loop():
            while not self._stop.wait(interval):
                try:
                    self.sample()
                except Exception as e:  # noqa: BLE001 — sampler survives
                    _log.warning("profile sample failed: %r", e)

        self._thread = threading.Thread(  # tmsan: shared=owner-thread lifecycle handle; sampler never reads _thread
            target=loop, daemon=True, name=f"prof-{self.node or 'node'}")
        self._thread.start()

    def stop(self, timeout: float = 1.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)
        self._thread = None  # tmsan: shared=owner-thread lifecycle handle; sampler never reads _thread

    # -- views ----------------------------------------------------------

    def folded_recent(self) -> str:
        """Folded text covering the ring + the open window — the
        pre-critical history the flight recorder bundles as
        ``profile.folded``."""
        with self._lock:
            windows = list(self._ring) + [self._win]
            stacks = merge_stacks(w.stacks for w in windows)
            header = (f"tendermint-tpu profile node={self.node or 'node'} "
                      f"enabled=1 hz={self.hz:g} windows={len(windows)} "
                      f"sweeps={self.sweeps} samples={self.samples}")
        return render_folded(stacks, header=header)

    def cumulative_stacks(self) -> dict:
        with self._lock:
            return dict(self._cum_stacks)

    def subsystem_samples(self) -> list:
        """[(labels, value)] rows for tendermint_prof_samples_total."""
        with self._lock:
            return [({"subsystem": sub}, float(c))
                    for sub, c in sorted(self._by_subsystem.items())]

    def overhead_samples(self) -> list:
        """[(labels, value)] rows for
        tendermint_prof_overhead_seconds_total."""
        with self._lock:
            return [({}, self.overhead_s)] if self.sweeps else []

    def status_block(self) -> dict:
        """Compact block for RPC `status` / `top` / the prof CLI."""
        with self._lock:
            return {
                "enabled": True,
                "node": self.node,
                "hz": self.hz,
                "running": (self._thread is not None
                            and self._thread.is_alive()),
                "sweeps": self.sweeps,
                "samples": self.samples,
                "by_subsystem": dict(sorted(self._by_subsystem.items())),
                "overhead_s": round(self.overhead_s, 6),
                "windows": len(self._ring) + 1,
                "triggers": self.triggers,
                "trigger_suppressed": self.trigger_suppressed,
            }

    def report(self) -> dict:
        """Status + top functions by self-time + the dominant subsystem
        — the simnet verdict's per-node profile input."""
        out = self.status_block()
        table = function_table(self.cumulative_stacks())
        top = []
        for sub, blk in table.items():
            for func, row in blk["functions"].items():
                if row["self"]:
                    top.append({"func": func, "subsystem": sub,
                                "self": row["self"], "cum": row["cum"]})
        top.sort(key=lambda r: (-r["self"], r["func"]))
        out["top"] = top[:10]
        by_sub = out["by_subsystem"]
        out["top_subsystem"] = (max(sorted(by_sub), key=by_sub.get)
                                if by_sub else None)
        with self._lock:
            reason = self._last_trigger_reason
        if reason:
            out["last_trigger"] = reason
        return out


# ---------------------------------------------------------------------------
# NOP twin + env gate
# ---------------------------------------------------------------------------

class _NopProfiler:
    """Disabled sampler: `.enabled` is False and every (never-taken)
    path is a no-op, so a call site costs one attribute load + branch."""

    enabled = False

    def sample(self) -> list:
        return []

    def capture(self, seconds: float = 2.0) -> dict:
        return {"enabled": False, "stacks": {}, "by_subsystem": {},
                "samples": 0}

    def trigger(self, reason: str = "") -> bool:
        return False

    def start(self) -> None:
        pass

    def stop(self, timeout: float = 1.0) -> None:
        pass

    def folded_recent(self) -> str:
        return "# tendermint-tpu profile enabled=0\n"

    def cumulative_stacks(self) -> dict:
        return {}

    def subsystem_samples(self) -> list:
        return []

    def overhead_samples(self) -> list:
        return []

    def status_block(self) -> dict:
        return {"enabled": False}

    def report(self) -> dict:
        return {"enabled": False}


NOP = _NopProfiler()


def from_env(node: str = "", clock=None) -> "Profiler | _NopProfiler":
    """Build a sampler per TM_TPU_PROF (default ON), or return the NOP
    singleton when disabled.  ``clock`` overrides the monotonic clock
    (simnet wall-time scenarios pass theirs; default wall)."""
    raw = os.environ.get(ENV_FLAG, "1").lower()
    if raw in ("0", "false", "off"):
        return NOP
    try:
        hz = float(os.environ.get("TM_TPU_PROF_HZ", DEFAULT_HZ))
    except ValueError:
        hz = DEFAULT_HZ
    try:
        trigger_min_s = float(os.environ.get("TM_TPU_PROF_TRIGGER_MIN_S",
                                             DEFAULT_TRIGGER_MIN_S))
    except ValueError:
        trigger_min_s = DEFAULT_TRIGGER_MIN_S
    try:
        window_s = float(os.environ.get("TM_TPU_PROF_WINDOW_S",
                                        DEFAULT_WINDOW_S))
    except ValueError:
        window_s = DEFAULT_WINDOW_S
    return Profiler(
        node=node,
        hz=hz,
        window_s=window_s,
        trigger_min_s=trigger_min_s,
        clock=clock if clock is not None else time.monotonic,
    )
