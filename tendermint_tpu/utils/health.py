"""Node health watchdog: anomaly detectors over the signals the stack
already emits, plus crash-time forensics.

PRs 2-4 and 8-9 built deep *passive* observability — spans, the event
journal, device/cost gauges, tx-lifecycle waterfalls — but nothing in
the process ever *looked* at those signals: a stalled height, a
verify-queue pileup, a compile storm or steady RSS growth was only
visible if an operator happened to be watching `top` (or replayed
journals after the fact, the r05 lesson: a watchdog-killed bench stage
silently lost its tail).  This module is the active layer:

  * `HealthMonitor` — a per-node background daemon thread sampling on a
    configurable cadence: process vitals (RSS, fd count, threads, GC),
    consensus progress (height/round), verify-service queue depth and
    cache-hit ratio, per-peer send-queue depth and flap counts, and
    devmon compile counters;
  * a small set of explicit, individually-testable detectors —
    height-stall, round-thrash, verify-queue saturation, compile-storm
    (the PR 7 zero-cold invariant as a live alarm), memory-growth,
    peer-flap and metric-drift (current counter rates vs the node's own
    recorded baseline, utils/history.py) — each with
    escalate-immediately / clear-after-N hysteresis so a single noisy
    sample cannot flap the alarm;
  * on each detector transition: a `tendermint_health_status{detector}`
    gauge step (0 ok / 1 warn / 2 critical),
    `tendermint_health_transitions_total{detector}`, a `health_*`
    journal event when the journal is on, and — on escalation to
    critical, rate-limited and size-bounded on disk — a forensic
    `FlightRecorder` bundle (trace ring, journal tail, devmon
    device_stats, verify service_stats, all-thread stack dump, detector
    history) written atomically under `<node root>/health/`.

Fault-window awareness (the simnet verdict's rule, live): the monitor
does not *suppress* alarms inside a declared fault window — a
partitioned node IS unhealthy and the acceptance path wants the alarm —
but every transition records whether it happened inside a window
(`excused`), so soak verdicts can separate injected adversity from a
real regression.  `fault_begin()`/`fault_end()` are fed by the simnet
runner's fault schedule.

Cost contract (the PR 2 sink idiom, enforced by tmlint's
`ungated-observability` for `*health.sample`/`*health.record` receivers
and by bench's `health-overhead` stage): call sites guard with
`if <health>.enabled:` so the disabled path costs one attribute load +
branch against the module `NOP` singleton.  The enabled per-sample cost
is dict merges plus seven detector updates — budgeted at <=50us/sample,
at a default cadence of one sample per 2 s.

Clocks: all detector logic runs on an injectable MONOTONIC clock
(`clock=time.monotonic`) so tests drive synthetic timelines; wall-clock
stamps appear only on transition records (`w`, for cross-node ordering
in the simnet verdict) and bundle names.

Env knobs (resolved in `from_env`, never at import — tmlint
`import-time-env`):
  TM_TPU_HEALTH              default on; "0"/"false"/"off" disables
                             (every call site collapses to the NOP
                             branch; no thread, no bundles)
  TM_TPU_HEALTH_INTERVAL_S   sample cadence (default 2.0)
  TM_TPU_HEALTH_STALL_S      expected block interval fed to the
                             height-stall detector (default: the
                             caller's, usually derived from
                             timeout_commit)
  TM_TPU_HEALTH_QUEUE_HW     verify-queue high-water rows (default 512)
  TM_TPU_HEALTH_BUNDLE_KEEP  flight-recorder bundles kept (default 5)
  TM_TPU_HEALTH_BUNDLE_MIN_S minimum seconds between bundles
                             (default 60)
"""

from __future__ import annotations

import gc
import json
import logging
import os
import sys
import threading
import time
import traceback
from collections import deque

from tendermint_tpu.utils import clock as _clockmod

_log = logging.getLogger("tendermint_tpu.health")

ENV_FLAG = "TM_TPU_HEALTH"

OK, WARN, CRITICAL = 0, 1, 2
LEVEL_NAMES = ("ok", "warn", "critical")

MAX_TRANSITIONS = 256   # transition history kept in memory / report()
MAX_HISTORY = 128       # recent samples kept for detectors/forensics


# ---------------------------------------------------------------------------
# probes — sample sources (each contained: a failing probe degrades to
# absent fields, never a failed sample)
# ---------------------------------------------------------------------------

def process_vitals() -> dict:
    """RSS / fd count / thread count / GC pressure for this process.
    Linux-first (/proc); every field degrades to absence elsewhere."""
    out: dict = {"thread_count": threading.active_count()}
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    out["rss_bytes"] = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    try:
        out["fd_count"] = len(os.listdir("/proc/self/fd"))
    except OSError:
        pass
    try:
        stats = gc.get_stats()
        out["gc_collections"] = sum(s.get("collections", 0) for s in stats)
        out["gc_uncollectable"] = sum(s.get("uncollectable", 0)
                                      for s in stats)
    except Exception:  # noqa: BLE001 — non-CPython gc
        pass
    return out


def verify_probe() -> dict:
    """Verify-service queue depth + cache hit ratio (never instantiates
    the service — zeros before first use, like the metrics scrape)."""
    from tendermint_tpu.crypto import async_verify as _av

    st = _av.service_stats()
    lookups = st["cache_hits"] + st["cache_misses"]
    return {
        "verify_queue_depth": st["queue_depth"],
        "verify_submitted": st["submitted"],
        "verify_cache_hit_ratio": (st["cache_hits"] / lookups
                                   if lookups else None),
    }


def device_probe() -> dict:
    """Devmon compile counters — the compile-storm detector's input."""
    from tendermint_tpu.utils import devmon as _dm

    tracker = _dm.TRACKER
    return {
        "cold_compiles": tracker.cold_compiles(),
        "jit_compiles_total": sum(tracker.compiles.values()),
        "jit_recompiles": tracker.recompiles,
    }


def format_thread_stacks() -> str:
    """All-thread Python stack dump (named), `faulthandler`-style —
    shared by the flight recorder and /debug/pprof/stacks (the
    live-wedge counterpart to the crash-time bundle)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = [f"== {len(sys._current_frames())} threads =="]
    for tid, frame in sys._current_frames().items():
        out.append(f"\n-- thread {tid} ({names.get(tid, '?')}) --")
        out.extend(ln.rstrip() for ln in traceback.format_stack(frame))
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# detectors
# ---------------------------------------------------------------------------

class Detector:
    """Base: escalate immediately on a worse raw reading, de-escalate
    only after `clear_after` consecutive better readings (hysteresis —
    one noisy sample must not flap the alarm).  Subclasses implement
    `observe(sample) -> (raw_level, detail)` over the merged sample dict
    and tolerate absent fields (a dead probe reads as no-data, OK)."""

    name = "?"

    def __init__(self, clear_after: int = 2):
        self.clear_after = max(1, clear_after)
        self.level = OK
        self.detail = ""
        self.since: float | None = None   # monotonic time of last change
        self._better = 0

    def observe(self, sample: dict) -> tuple[int, str]:
        raise NotImplementedError

    def update(self, sample: dict) -> None:
        raw, detail = self.observe(sample)
        if raw > self.level:
            self.level = raw
            self.detail = detail
            self.since = sample["t"]
            self._better = 0
        elif raw < self.level:
            self._better += 1
            if self._better >= self.clear_after:
                self.level = raw
                self.detail = detail
                self.since = sample["t"]
                self._better = 0
        else:
            self._better = 0
            if raw > OK:
                self.detail = detail   # refresh the live description


class HeightStallDetector(Detector):
    """No commit for N x the expected block interval.  warn_factor /
    crit_factor scale `expected_interval_s`; a single height advance
    clears immediately (clear_after=1) so recovery after a heal reads
    back as ok on the next sample."""

    name = "height_stall"

    def __init__(self, expected_interval_s: float = 1.0,
                 warn_factor: float = 5.0, crit_factor: float = 10.0):
        super().__init__(clear_after=1)
        self.expected_interval_s = max(0.001, expected_interval_s)
        self.warn_s = warn_factor * self.expected_interval_s
        self.crit_s = crit_factor * self.expected_interval_s
        self._height: int | None = None
        self._changed_at: float | None = None

    def observe(self, sample: dict) -> tuple[int, str]:
        h = sample.get("height")
        if h is None:
            return OK, ""
        now = sample["t"]
        if self._height is None or h != self._height:
            self._height = h
            self._changed_at = now
            return OK, ""
        age = now - self._changed_at
        if age >= self.crit_s:
            return CRITICAL, (f"height {h} unchanged for {age:.1f}s "
                              f"(critical >= {self.crit_s:.1f}s)")
        if age >= self.warn_s:
            return WARN, (f"height {h} unchanged for {age:.1f}s "
                          f"(warn >= {self.warn_s:.1f}s)")
        return OK, ""


class RoundThrashDetector(Detector):
    """Consensus burning rounds: the current round itself past a bound,
    or rounds>0 persisting across many consecutive samples (a net that
    keeps failing its first round without ever reaching a high one)."""

    name = "round_thrash"

    def __init__(self, warn_round: int = 2, crit_round: int = 5,
                 warn_streak: int = 5, crit_streak: int = 15,
                 clear_after: int = 2):
        super().__init__(clear_after=clear_after)
        self.warn_round = warn_round
        self.crit_round = crit_round
        self.warn_streak = warn_streak
        self.crit_streak = crit_streak
        self._streak = 0

    def observe(self, sample: dict) -> tuple[int, str]:
        r = sample.get("round")
        if r is None:
            return OK, ""
        self._streak = self._streak + 1 if r > 0 else 0
        if r >= self.crit_round or self._streak >= self.crit_streak:
            return CRITICAL, (f"round {r}, rounds>0 for {self._streak} "
                              f"consecutive samples")
        if r >= self.warn_round or self._streak >= self.warn_streak:
            return WARN, (f"round {r}, rounds>0 for {self._streak} "
                          f"consecutive samples")
        return OK, ""


class QueueSaturationDetector(Detector):
    """Verify-service submission queue above high-water for a sustained
    window (`sustain` consecutive samples); `crit_factor` x high-water
    sustained is critical.  A one-sample spike (a big commit flush)
    never fires."""

    name = "verify_queue_saturation"

    def __init__(self, high_water: int = 512, sustain: int = 3,
                 crit_factor: float = 4.0, clear_after: int = 2):
        super().__init__(clear_after=clear_after)
        self.high_water = max(1, high_water)
        self.sustain = max(1, sustain)
        self.crit_water = crit_factor * self.high_water
        self._above = 0
        self._above_crit = 0

    def observe(self, sample: dict) -> tuple[int, str]:
        depth = sample.get("verify_queue_depth")
        if depth is None:
            return OK, ""
        self._above = self._above + 1 if depth >= self.high_water else 0
        self._above_crit = (self._above_crit + 1
                            if depth >= self.crit_water else 0)
        if self._above_crit >= self.sustain:
            return CRITICAL, (f"verify queue {depth} rows >= "
                              f"{self.crit_water:.0f} for "
                              f"{self._above_crit} samples")
        if self._above >= self.sustain:
            return WARN, (f"verify queue {depth} rows >= "
                          f"{self.high_water} for {self._above} samples")
        return OK, ""


class CompileStormDetector(Detector):
    """Cold `jit_compile_total` growth after the warm-up grace — the PR 7
    post-warm zero-cold invariant as a live alarm.  A node legitimately
    cold-compiles while warming (grace_s); after that, ANY new cold
    compile inside the sliding window is a warn, `crit_growth`+ is a
    storm (cold compiles, seconds to minutes each, eating the node)."""

    name = "compile_storm"

    def __init__(self, grace_s: float = 180.0, window_s: float = 300.0,
                 warn_growth: int = 1, crit_growth: int = 3,
                 clear_after: int = 2):
        super().__init__(clear_after=clear_after)
        self.grace_s = grace_s
        self.window_s = window_s
        self.warn_growth = warn_growth
        self.crit_growth = crit_growth
        self._t0: float | None = None
        self._points: deque = deque()   # (t, cold_count)

    def observe(self, sample: dict) -> tuple[int, str]:
        cold = sample.get("cold_compiles")
        if cold is None:
            return OK, ""
        now = sample["t"]
        if self._t0 is None:
            self._t0 = now
        self._points.append((now, cold))
        while self._points and now - self._points[0][0] > self.window_s:
            self._points.popleft()
        if now - self._t0 < self.grace_s:
            return OK, ""
        growth = cold - self._points[0][1]
        if growth >= self.crit_growth:
            return CRITICAL, (f"{growth} cold compiles in the last "
                              f"{self.window_s:.0f}s (post-warm must be 0)")
        if growth >= self.warn_growth:
            return WARN, (f"{growth} cold compile(s) in the last "
                          f"{self.window_s:.0f}s (post-warm must be 0)")
        return OK, ""


class MemoryGrowthDetector(Detector):
    """RSS slope over a sliding window: (last - first) / span, once the
    window spans at least `min_span_s`.  Thresholds are deliberately
    conservative (device warm-up legitimately allocates in bursts); the
    signal is a soak-run leak, not a spike."""

    name = "memory_growth"

    def __init__(self, window_s: float = 120.0, min_span_s: float = 30.0,
                 warn_bps: float = 4 * 1024 * 1024,
                 crit_bps: float = 32 * 1024 * 1024, clear_after: int = 3):
        super().__init__(clear_after=clear_after)
        self.window_s = window_s
        self.min_span_s = min_span_s
        self.warn_bps = warn_bps
        self.crit_bps = crit_bps
        self._points: deque = deque()   # (t, rss)

    def observe(self, sample: dict) -> tuple[int, str]:
        rss = sample.get("rss_bytes")
        if rss is None:
            return OK, ""
        now = sample["t"]
        self._points.append((now, rss))
        while self._points and now - self._points[0][0] > self.window_s:
            self._points.popleft()
        t0, r0 = self._points[0]
        span = now - t0
        if span < self.min_span_s:
            return OK, ""
        slope = (rss - r0) / span
        mib_min = slope * 60 / (1024 * 1024)
        if slope >= self.crit_bps:
            return CRITICAL, (f"RSS growing {mib_min:.1f} MiB/min over "
                              f"{span:.0f}s (rss {rss >> 20} MiB)")
        if slope >= self.warn_bps:
            return WARN, (f"RSS growing {mib_min:.1f} MiB/min over "
                          f"{span:.0f}s (rss {rss >> 20} MiB)")
        return OK, ""


class PeerFlapDetector(Detector):
    """Peer churn rate from the router's cumulative disconnect counter
    (the DialBackoff ladder's view: a flapping peer keeps reconnecting
    and dying).  Rate is disconnects/min over the sliding window, once
    the window spans `min_span_s`."""

    name = "peer_flap"

    def __init__(self, window_s: float = 60.0, min_span_s: float = 30.0,
                 warn_per_min: float = 10.0, crit_per_min: float = 40.0,
                 clear_after: int = 3):
        super().__init__(clear_after=clear_after)
        self.window_s = window_s
        self.min_span_s = min_span_s
        self.warn_per_min = warn_per_min
        self.crit_per_min = crit_per_min
        self._points: deque = deque()   # (t, disconnect_total)

    def observe(self, sample: dict) -> tuple[int, str]:
        total = sample.get("peer_disconnects")
        if total is None:
            return OK, ""
        now = sample["t"]
        self._points.append((now, total))
        while self._points and now - self._points[0][0] > self.window_s:
            self._points.popleft()
        t0, c0 = self._points[0]
        span = now - t0
        if span < self.min_span_s:
            return OK, ""
        per_min = (total - c0) * 60.0 / span
        if per_min >= self.crit_per_min:
            return CRITICAL, (f"{per_min:.1f} peer disconnects/min over "
                              f"{span:.0f}s")
        if per_min >= self.warn_per_min:
            return WARN, (f"{per_min:.1f} peer disconnects/min over "
                          f"{span:.0f}s")
        return OK, ""


class MetricDriftDetector(Detector):
    """Counter-rate drift against the node's own recorded baseline
    (utils/history.py): the recorder's `drift_probe` feeds the worst
    series' robust z-score — current fixed-width rate window vs the
    median of the trailing baseline windows, MAD-scaled.  Severity is
    one-sided on purpose: only a DOWNWARD drift (a rate collapsing —
    the commit counter stalling, verifies drying up) alarms, warning at
    `warn_z` and escalating to critical at `crit_z`.  An UPWARD drift
    never fires at all: a rate surging past its baseline is catch-up
    after a healed fault or a legitimate load increase, and alarming on
    it would punish exactly the runs that recovered."""

    name = "metric_drift"

    def __init__(self, warn_z: float = 4.0, crit_z: float = 8.0,
                 clear_after: int = 2):
        super().__init__(clear_after=clear_after)
        self.warn_z = warn_z
        self.crit_z = crit_z

    def observe(self, sample: dict) -> tuple[int, str]:
        d = sample.get("history_drift")
        if not d:
            return OK, ""
        z = d.get("z", 0.0)
        cur = d.get("current_per_s", 0.0)
        base = d.get("baseline_per_s", 0.0)
        if z < self.warn_z or cur >= base:
            return OK, ""
        detail = (f"{d.get('series', '?')} rate {cur:g}/s vs baseline "
                  f"{base:g}/s over {d.get('windows', '?')} windows "
                  f"(z={z:g})")
        if z >= self.crit_z:
            return CRITICAL, detail
        return WARN, detail


def default_detectors(expected_block_s: float = 1.0,
                      queue_high_water: int = 512,
                      compile_grace_s: float | None = None,
                      compile_window_s: float | None = None,
                      flap_window_s: float | None = None,
                      flap_min_span_s: float | None = None) -> list[Detector]:
    """The seven standard detectors.  The optional window overrides exist
    for fast-cadence monitors (simnet's 0.25s sampling): the production
    compile-storm grace (180s) and peer-flap minimum span (30s) would
    otherwise mask any fault a test-scale run can inject."""
    storm_kw = {}
    if compile_grace_s is not None:
        storm_kw["grace_s"] = compile_grace_s
    if compile_window_s is not None:
        storm_kw["window_s"] = compile_window_s
    flap_kw = {}
    if flap_window_s is not None:
        flap_kw["window_s"] = flap_window_s
    if flap_min_span_s is not None:
        flap_kw["min_span_s"] = flap_min_span_s
    return [
        HeightStallDetector(expected_interval_s=expected_block_s),
        RoundThrashDetector(),
        QueueSaturationDetector(high_water=queue_high_water),
        CompileStormDetector(**storm_kw),
        MemoryGrowthDetector(),
        PeerFlapDetector(**flap_kw),
        MetricDriftDetector(),
    ]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Forensic bundle writer: on a critical escalation, snapshot the
    observable state of the node into one directory under
    `<root>/health/` — trace ring, journal tail, device stats, verify
    stats, all-thread stacks, detector history.

    Bounded by construction: rate-limited (min_interval_s between
    bundles), size-bounded (journal tail capped at max_tail_bytes; the
    trace ring and transition history are already bounded), and rotated
    (keep-last-K bundle directories).  Written atomically: the bundle is
    built in a dot-prefixed temp dir and renamed into place, so a reader
    (or a crash mid-write) never sees a half bundle."""

    def __init__(self, root: str, keep: int = 5, min_interval_s: float = 60.0,
                 journal_path: str = "", max_tail_bytes: int = 64 * 1024,
                 clock=time.monotonic):
        self.dir = os.path.join(root, "health")
        self.keep = max(1, keep)
        self.min_interval_s = min_interval_s
        self.journal_path = journal_path
        self.max_tail_bytes = max_tail_bytes
        self._clock = clock
        self._last: float | None = None
        self._seq = 0
        self.written = 0
        self.suppressed = 0

    # -- sources (each contained; a failing source becomes a manifest
    # error entry, never a failed bundle) -------------------------------

    def _sources(self, monitor: "HealthMonitor") -> list[tuple[str, object]]:
        def _trace():
            from tendermint_tpu.utils import trace as _tr

            return (f"# trace ring enabled={int(_tr.enabled())} "
                    f"spans={len(_tr.spans())}\n" + _tr.export_jsonl() + "\n")

        def _device():
            from tendermint_tpu.utils import devmon as _dm

            return json.dumps(_dm.device_stats(), indent=2, default=str)

        def _service():
            from tendermint_tpu.crypto import async_verify as _av

            return json.dumps(_av.service_stats(), indent=2, default=str)

        sources = [
            ("stacks.txt", format_thread_stacks),
            ("health.json", lambda: json.dumps(monitor.report(), indent=2,
                                               default=str)),
            ("trace.jsonl", _trace),
            ("device_stats.json", _device),
            ("service_stats.json", _service),
        ]
        # continuous-profiler window (utils/profiler.py): the folded
        # pre-critical ring, next to the one-shot stack dump — same
        # per-source containment as every other member
        prof = getattr(monitor, "prof", None)
        if prof is not None and prof.enabled:
            sources.append(("profile.folded", prof.folded_recent))
        # metric-history window (utils/history.py): the last-N-minutes
        # flight data next to the journal tail — the bundle finally
        # carries the series, not just the events
        history = getattr(monitor, "history", None)
        if history is not None and history.enabled:
            sources.append(("history.jsonl", history.window_text))
        return sources

    def _journal_tail(self) -> bytes | None:
        if not self.journal_path or not os.path.exists(self.journal_path):
            return None
        size = os.path.getsize(self.journal_path)
        with open(self.journal_path, "rb") as fh:
            if size > self.max_tail_bytes:
                fh.seek(size - self.max_tail_bytes)
                fh.readline()   # drop the torn first line
            return fh.read()

    def record(self, monitor: "HealthMonitor", detector: Detector,
               transition: dict | None = None) -> str | None:
        """Write one bundle for `detector`'s critical escalation; None
        when rate-limited.  Never raises: forensics must not take down
        the node they are diagnosing."""
        now = self._clock()
        if self._last is not None and now - self._last < self.min_interval_s:
            self.suppressed += 1
            return None
        self._last = now
        self._seq += 1
        name = (f"bundle-{time.strftime('%Y%m%d-%H%M%S')}-"
                f"{self._seq:03d}-{detector.name}")
        final = os.path.join(self.dir, name)
        tmp = os.path.join(self.dir, "." + name + ".tmp")
        errors: dict[str, str] = {}
        try:
            os.makedirs(tmp, exist_ok=True)
            for fname, fn in self._sources(monitor):
                try:
                    body = fn()
                    with open(os.path.join(tmp, fname), "w") as fh:
                        fh.write(body if body.endswith("\n") else body + "\n")
                except Exception as e:  # noqa: BLE001 — contain per source
                    errors[fname] = repr(e)
            try:
                tail = self._journal_tail()
                if tail is not None:
                    with open(os.path.join(tmp, "journal_tail.jsonl"),
                              "wb") as fh:
                        fh.write(tail)
            except Exception as e:  # noqa: BLE001
                errors["journal_tail.jsonl"] = repr(e)
            manifest = {
                "detector": detector.name,
                "level": detector.level,
                "detail": detector.detail,
                "node": monitor.node,
                "w": _clockmod.wall_ns(),
                "transition": transition,
                "errors": errors,
            }
            with open(os.path.join(tmp, "manifest.json"), "w") as fh:
                json.dump(manifest, fh, indent=2, default=str)
            os.replace(tmp, final)
        except Exception as e:  # noqa: BLE001 — disk full / perms
            _log.warning("flight-recorder bundle failed: %r", e)
            return None
        self.written += 1
        self._rotate()
        return final

    def _rotate(self) -> None:
        try:
            bundles = sorted(n for n in os.listdir(self.dir)
                             if n.startswith("bundle-"))
        except OSError:
            return
        import shutil

        for name in bundles[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, name), ignore_errors=True)

    def stats(self) -> dict:
        return {"dir": self.dir, "keep": self.keep,
                "min_interval_s": self.min_interval_s,
                "written": self.written, "suppressed": self.suppressed}


# ---------------------------------------------------------------------------
# monitor
# ---------------------------------------------------------------------------

class _NopJournal:
    enabled = False

    def log(self, event: str, **fields) -> None:
        pass


_NOP_JOURNAL = _NopJournal()


class _NopRemediate:
    """Default transition sink: disabled.  The node/SimNode assigns a
    real `utils/remediate.RemediationController` (defined there, not
    here, so health carries no remediation imports); the monitor pays
    one branch per TRANSITION when off."""

    enabled = False

    def act(self, tr: dict) -> None:
        pass


_NOP_REMEDIATE = _NopRemediate()


class _NopProfSink:
    """Default profiler sink: disabled.  The node/SimNode assigns a
    real `utils/profiler.Profiler` (defined there, not here, so health
    carries no profiler imports); critical escalations and slo_burn
    records pay one branch when off."""

    enabled = False

    def trigger(self, reason: str = "") -> bool:
        return False

    def folded_recent(self) -> str:
        return ""


_NOP_PROF = _NopProfSink()


class _NopHistorySink:
    """Default history sink: disabled.  The node/SimNode assigns a
    real `utils/history.HistoryRecorder` (defined there, not here, so
    health carries no history imports); the flight recorder bundles
    the recorded window when on, and the `metric_drift` detector's
    probe is wired by the owner, not the monitor."""

    enabled = False

    def window_text(self, seconds: float = 900.0) -> str:
        return ""


_NOP_HISTORY = _NopHistorySink()


class HealthMonitor:
    """One node's watchdog.  `enabled` is True so the one-branch guard
    at call sites passes; `NOP` is the disabled twin.

    `probes` is a name -> callable map; each callable returns a dict of
    sample fields (see process_vitals/verify_probe/device_probe — the
    node wires consensus/peer lambdas in).  `sample()` merges one
    reading, runs every detector, and handles transitions (journal,
    metrics counters, flight recorder); `start()` drives it from a
    daemon thread on `interval_s`."""

    enabled = True

    def __init__(self, node: str = "", probes: dict | None = None,
                 detectors: list[Detector] | None = None,
                 interval_s: float = 2.0, journal=None,
                 recorder: FlightRecorder | None = None,
                 fault_grace_s: float = 2.0, clock=time.monotonic):
        self.node = node
        self.probes = dict(probes) if probes is not None else {
            "process": process_vitals,
            "verify": verify_probe,
            "device": device_probe,
        }
        self.detectors = (detectors if detectors is not None
                          else default_detectors())
        self.interval_s = max(0.05, interval_s)
        self.journal = journal if journal is not None else _NOP_JOURNAL
        self.recorder = recorder
        # remediation sink (utils/remediate.py): the node assigns its
        # RemediationController after construction; transitions flow
        # through `.act()` under the one-branch guard below
        self.remediate = _NOP_REMEDIATE
        # profiler sink (utils/profiler.py): the node assigns its
        # Profiler after construction; critical escalations and
        # slo_burn records arm a rate-limited trigger capture, and the
        # flight recorder bundles the folded pre-critical ring
        self.prof = _NOP_PROF
        # history sink (utils/history.py): the node assigns its
        # HistoryRecorder after construction; the flight recorder
        # embeds the last-N-minutes window next to the journal tail
        self.history = _NOP_HISTORY
        self.fault_grace_s = fault_grace_s
        self._clock = clock
        self._lock = threading.Lock()
        self._history: deque = deque(maxlen=MAX_HISTORY)
        self._transitions: deque = deque(maxlen=MAX_TRANSITIONS)
        self._transitions_total: dict[str, int] = {}
        self._extras: dict = {}
        self._fault_depth = 0
        self._fault_clear_at: float | None = None
        self.samples = 0
        self.probe_errors = 0
        self.slo_burns = 0
        self._last_slo_burn = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()

    # -- fault windows (simnet schedule feed) ---------------------------

    def fault_begin(self) -> None:
        """An injected fault (partition/slow/crash window) is now open:
        transitions until `fault_end` + grace are recorded as excused."""
        with self._lock:
            self._fault_depth += 1

    def fault_end(self) -> None:
        with self._lock:
            self._fault_depth = max(0, self._fault_depth - 1)
            if self._fault_depth == 0:
                self._fault_clear_at = self._clock() + self.fault_grace_s

    def _in_fault(self, now: float) -> bool:
        if self._fault_depth > 0:
            return True
        return (self._fault_clear_at is not None
                and now <= self._fault_clear_at)

    # -- event-push hook (guard call sites with `if health.enabled:`) ---

    def record(self, name: str, value) -> None:
        """Push an out-of-band observation into the NEXT sample (e.g. a
        restart marker); hook sites guard on `.enabled` like every
        other sink.  `slo_burn` records — the fleet layer telling THIS
        node its deployment is burning an objective's error budget
        (fleet/slo.py; the simnet runner's sampler is the feed) — are
        additionally counted and kept, so the node's own status block,
        journal forensics and `tendermint_health_slo_burn_total` show
        fleet-scope pressure next to the local detectors."""
        with self._lock:
            self._extras[name] = value
            if name == "slo_burn":
                self.slo_burns += 1
                self._last_slo_burn = value
        # fleet-scope pressure wants a profile: arm a rate-limited
        # trigger capture (outside the lock — the profiler has its own)
        if name == "slo_burn" and self.prof.enabled:
            self.prof.trigger("slo_burn")

    # -- sampling -------------------------------------------------------

    def sample(self) -> dict:
        """Collect one sample and run every detector.  Public: tests,
        the `health-overhead` bench stage and one-shot tooling call it
        directly; the background thread is just a loop over it."""
        now = self._clock()
        s: dict = {"t": now}
        new_errors = 0
        for pname, probe in self.probes.items():
            try:
                got = probe()
                if got:
                    s.update(got)
            except Exception as e:  # noqa: BLE001 — dead probe != dead node
                new_errors += 1
                s.setdefault("probe_errors", {})[pname] = repr(e)
        fired: list[tuple[Detector, dict]] = []
        with self._lock:
            self.probe_errors += new_errors
            if self._extras:
                s.update(self._extras)
                self._extras = {}
            s["in_fault_window"] = self._in_fault(now)
            for d in self.detectors:
                prev = d.level
                d.update(s)
                if d.level != prev:
                    tr = {
                        "t": now,
                        "w": _clockmod.wall_ns(),
                        "detector": d.name,
                        "from": prev,
                        "to": d.level,
                        "detail": d.detail,
                        "excused": s["in_fault_window"],
                    }
                    self._transitions.append(tr)
                    self._transitions_total[d.name] = (
                        self._transitions_total.get(d.name, 0) + 1)
                    fired.append((d, tr))
            self.samples += 1
            self._history.append({k: v for k, v in s.items()
                                  if k != "probe_errors"})
            # steady re-delivery while unhealthy: a detector that STAYS
            # at warn/critical produces no transition, but remediations
            # are reconcilers (idempotent shed, rate-limited rewarm,
            # quarantine-deduped evict) — the controller must keep
            # seeing the live level so e.g. a flap score that crosses
            # its threshold AFTER the escalation still gets acted on
            steady: list[tuple[str, int]] = []
            if self.remediate.enabled:
                fired_names = {d.name for d, _tr in fired}
                steady = [(d.name, d.level) for d in self.detectors
                          if d.level > OK and d.name not in fired_names]
        # journal + remediation + forensics OUTSIDE the lock: the
        # recorder snapshots report() (which takes the lock), journal
        # writes are I/O, and remediations call into other subsystems
        for d, tr in fired:
            if self.journal.enabled:
                ev = ("health_critical" if tr["to"] == CRITICAL
                      else "health_warn" if tr["to"] == WARN
                      else "health_ok")
                self.journal.log(ev, detector=d.name,
                                 prev=LEVEL_NAMES[tr["from"]],
                                 detail=tr["detail"],
                                 excused=tr["excused"])
            if self.remediate.enabled:
                try:
                    self.remediate.act(tr)
                except Exception as e:  # noqa: BLE001 — watchdog survives
                    _log.warning("remediation act failed: %r", e)
            if tr["to"] == CRITICAL and tr["from"] < CRITICAL:
                # profile the escalation: arm the (rate-limited)
                # trigger BEFORE the bundle snapshot so the bundle's
                # profile.folded and any device capture share the event
                if self.prof.enabled:
                    self.prof.trigger(f"health-critical:{d.name}")
                if self.recorder is not None:
                    tr["bundle"] = self.recorder.record(self, d,
                                                        transition=tr)
        if self.remediate.enabled:
            for name, level in steady:
                try:
                    self.remediate.act({
                        "detector": name, "from": level, "to": level,
                        "detail": "", "excused": s["in_fault_window"],
                        "steady": True,
                    })
                except Exception as e:  # noqa: BLE001 — watchdog survives
                    _log.warning("remediation act failed: %r", e)
        return s

    # -- lifecycle ------------------------------------------------------

    def start(self) -> None:
        """Spawn the sampling daemon thread (idempotent)."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()

        def loop():
            while not self._stop.wait(self.interval_s):
                try:
                    self.sample()
                except Exception as e:  # noqa: BLE001 — watchdog survives
                    _log.warning("health sample failed: %r", e)

        self._thread = threading.Thread(  # tmsan: shared=owner-thread lifecycle handle; sampler never reads _thread
            target=loop, daemon=True,
            name=f"health-{self.node or 'node'}")
        self._thread.start()

    def stop(self, timeout: float = 1.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)
        self._thread = None  # tmsan: shared=owner-thread lifecycle handle; sampler never reads _thread

    # -- views ----------------------------------------------------------

    def level(self) -> int:
        with self._lock:
            return max((d.level for d in self.detectors), default=OK)

    def status_samples(self) -> list:
        """[(labels, value)] rows for tendermint_health_status."""
        with self._lock:
            return [({"detector": d.name}, float(d.level))
                    for d in self.detectors]

    def transition_samples(self) -> list:
        """[(labels, value)] rows for tendermint_health_transitions_total."""
        with self._lock:
            return [({"detector": name}, float(c))
                    for name, c in sorted(self._transitions_total.items())]

    def slo_burn_samples(self) -> list:
        """[(labels, value)] rows for tendermint_health_slo_burn_total."""
        with self._lock:
            return [({}, float(self.slo_burns))] if self.slo_burns else []

    def status_block(self) -> dict:
        """Compact block for RPC `status` / the health CLI."""
        now = self._clock()
        with self._lock:
            detectors = {
                d.name: {
                    "level": d.level,
                    "state": LEVEL_NAMES[d.level],
                    "detail": d.detail,
                    "since_s": (round(now - d.since, 3)
                                if d.since is not None else None),
                }
                for d in self.detectors
            }
            level = max((d.level for d in self.detectors), default=OK)
            out = {
                "enabled": True,
                "node": self.node,
                "level": level,
                "state": LEVEL_NAMES[level],
                "critical": [d.name for d in self.detectors
                             if d.level == CRITICAL],
                "detectors": detectors,
                "samples": self.samples,
                "transitions_total": sum(self._transitions_total.values()),
                "in_fault_window": self._in_fault(now),
            }
            if self.slo_burns:
                out["slo_burns"] = self.slo_burns
                out["last_slo_burn"] = self._last_slo_burn
            return out

    def report(self) -> dict:
        """Full forensic view: status + transition history + the last
        sample + recorder stats (health.json in the bundle; the simnet
        verdict's per-node health input)."""
        out = self.status_block()
        with self._lock:
            out["transitions"] = [dict(tr) for tr in self._transitions]
            out["last_sample"] = dict(self._history[-1]) \
                if self._history else {}
            out["probe_errors"] = self.probe_errors
            out["interval_s"] = self.interval_s
        if self.recorder is not None:
            out["recorder"] = self.recorder.stats()
        return out

    def render_text(self) -> str:
        """Plain-text dump for /debug/pprof/health."""
        rep = self.report()
        lines = [
            f"== health ({rep['node'] or 'node'}) level={rep['state']} "
            f"samples={rep['samples']} "
            f"in_fault_window={int(rep['in_fault_window'])} ==",
        ]
        for name, d in rep["detectors"].items():
            since = (f" for {d['since_s']:.1f}s"
                     if d["since_s"] is not None and d["level"] > OK else "")
            detail = f"  {d['detail']}" if d["detail"] else ""
            lines.append(f"  {name:<24} {d['state'].upper() if d['level'] else 'ok':<10}"
                         f"{since}{detail}")
        if rep.get("recorder"):
            r = rep["recorder"]
            lines.append(f"bundles: {r['written']} written, "
                         f"{r['suppressed']} rate-limited -> {r['dir']}")
        trs = rep["transitions"][-8:]
        if trs:
            lines.append(f"transitions (last {len(trs)}):")
            for tr in trs:
                lines.append(
                    f"  {tr['detector']}: {LEVEL_NAMES[tr['from']]} -> "
                    f"{LEVEL_NAMES[tr['to']]}"
                    f"{' [excused]' if tr.get('excused') else ''}"
                    f"  {tr['detail']}")
        return "\n".join(lines) + "\n"


class _NopMonitor:
    """Disabled watchdog: `.enabled` is False and every (never-taken)
    path is a no-op, so a call site costs one attribute load + branch."""

    enabled = False
    detectors: tuple = ()
    recorder = None
    prof = _NOP_PROF
    history = _NOP_HISTORY

    def sample(self) -> dict:
        return {}

    def record(self, name: str, value) -> None:
        pass

    def start(self) -> None:
        pass

    def stop(self, timeout: float = 1.0) -> None:
        pass

    def fault_begin(self) -> None:
        pass

    def fault_end(self) -> None:
        pass

    def level(self) -> int:
        return OK

    def status_samples(self) -> list:
        return []

    def transition_samples(self) -> list:
        return []

    def slo_burn_samples(self) -> list:
        return []

    def status_block(self) -> dict:
        return {"enabled": False}

    def report(self) -> dict:
        return {"enabled": False}

    def render_text(self) -> str:
        return "health monitor disabled (TM_TPU_HEALTH=0)\n"


NOP = _NopMonitor()


def from_env(node: str = "", root: str = "", probes: dict | None = None,
             journal=None, journal_path: str = "",
             expected_block_s: float = 1.0,
             interval_s: float | None = None,
             compile_grace_s: float | None = None,
             compile_window_s: float | None = None,
             flap_window_s: float | None = None,
             flap_min_span_s: float | None = None,
             clock=None,
             ) -> "HealthMonitor | _NopMonitor":
    """Build a monitor per TM_TPU_HEALTH (default ON), or return the NOP
    singleton when disabled.  `root` hosts the flight-recorder bundles
    (`<root>/health/`); no root = no recorder (pure in-memory monitor).
    `clock` overrides the monotonic clock for monitor AND recorder (the
    virtual-time simnet passes its virtual clock; default wall)."""
    raw = os.environ.get(ENV_FLAG, "1").lower()
    if raw in ("0", "false", "off"):
        return NOP
    if clock is None:
        clock = time.monotonic
    try:
        interval = float(os.environ.get("TM_TPU_HEALTH_INTERVAL_S",
                                        interval_s if interval_s is not None
                                        else 2.0))
    except ValueError:
        interval = 2.0
    try:
        expected = float(os.environ.get("TM_TPU_HEALTH_STALL_S",
                                        expected_block_s))
    except ValueError:
        expected = expected_block_s
    try:
        queue_hw = int(os.environ.get("TM_TPU_HEALTH_QUEUE_HW", 512))
    except ValueError:
        queue_hw = 512
    recorder = None
    if root:
        try:
            keep = int(os.environ.get("TM_TPU_HEALTH_BUNDLE_KEEP", 5))
        except ValueError:
            keep = 5
        try:
            min_s = float(os.environ.get("TM_TPU_HEALTH_BUNDLE_MIN_S", 60.0))
        except ValueError:
            min_s = 60.0
        recorder = FlightRecorder(root, keep=keep, min_interval_s=min_s,
                                  journal_path=journal_path, clock=clock)
    all_probes = {
        "process": process_vitals,
        "verify": verify_probe,
        "device": device_probe,
    }
    if probes:
        all_probes.update(probes)
    return HealthMonitor(
        node=node,
        probes=all_probes,
        detectors=default_detectors(expected_block_s=expected,
                                    queue_high_water=queue_hw,
                                    compile_grace_s=compile_grace_s,
                                    compile_window_s=compile_window_s,
                                    flap_window_s=flap_window_s,
                                    flap_min_span_s=flap_min_span_s),
        interval_s=interval,
        journal=journal,
        recorder=recorder,
        clock=clock,
    )
