"""Device-layer observability: JIT compile tracking, batch-occupancy /
padding-waste accounting, and device memory introspection.

The verify pipeline's throughput is decided at the device boundary, and
until this module that boundary was a black box: a cold XLA compile of a
new bucket rung costs seconds to minutes (utils/jaxcache.py keeps the
persistent cache that avoids it), the bucket ladder pads every batch
(measured worst-case 1.49x at n=129→192 — ops/ed25519_jax._bucket), and
nothing reported what the verifier holds in device memory.  Three
trackers close that gap:

  * `TRACKER` (CompileTracker): every jit entry point in
    ops/ed25519_jax (`_compiled`) and parallel/sharding is
    wrapped by `track_jit`, so the FIRST call per bucket rung — the
    call that pays trace+compile — records a compile event (rung, impl,
    flags, wall duration, persistent-cache hit vs cold compile) into a
    bounded event list plus per-(rung, impl) counters.  A rung compiled
    TWICE (the in-memory program cache was cleared and the same cache
    key re-traced) is an unexpected recompile: dedicated counter + warn
    log, because steady-state consensus must reuse a handful of
    steady-state buckets.
  * `STATS` (DeviceStats): every device flush site records requested
    rows vs the padded bucket rung — occupancy histogram
    `verify_batch_occupancy_ratio{rung}`, cumulative
    `verify_padding_rows_total`, per-rung flush counts, and the
    host→device transfer bytes actually shipped (padded row widths).
    Gated by TM_TPU_DEVSTATS (default on); when off, each flush site
    pays exactly one branch (`if STATS.enabled:` — the bench
    `device-observability` stage enforces both paths' budgets).
  * `device_memory()`: per-device `memory_stats()` / live-buffer bytes,
    WITHOUT ever initializing a backend — a /metrics scrape or pprof
    request against a node whose device path never woke must not be the
    process's first device contact (backend init can take arbitrarily
    long and belongs to the warm-up worker).

`device_stats()` snapshots all three; node/metrics.py exposes the
counters/gauges, node/pprof.py serves the text dump at
/debug/pprof/device, and `tendermint-tpu top` renders the live view.

Timing caveat, stated once: JAX dispatch is async, so the first-call
wall duration covers trace + compile + enqueue, not device execution —
for compile accounting that is the right quantity.  Classification of
persistent-cache hit vs cold compile comes from JAX's own monitoring
events observed on the calling thread during that first call (every
compile request records `backend_compile_duration`; a request served
from the persistent cache also records `compilation_cache/cache_hits`):
all requests hit → "persistent-cache", any miss → "cold".  Only when
the call compiled nothing at all (a stub, or a program already in jit's
in-memory cache) does the old duration heuristic decide
(TM_TPU_COMPILE_COLD_S, default 5.0 s).  Ahead-of-time programs
(ops/shape_plan) record their events with an explicit source ("aot" /
"deserialized"), and `jit_compile_total` carries the source as a label
so zero `source="cold"` after a warm is provable from /metrics alone.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time
from collections import deque

from tendermint_tpu.utils.metrics import Histogram

_log = logging.getLogger("tendermint_tpu.devmon")

MAX_COMPILE_EVENTS = 256

# Bucket-ladder occupancy is bounded below by 1/1.49 ≈ 0.67 for n>128
# (module header of ops/ed25519_jax), so the grid is dense there; the
# low buckets catch tiny batches landing in the n=8 floor bucket.
OCCUPANCY_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.672, 0.75,
                     0.8, 0.875, 0.9375, 1.0)

VERIFY_BATCH_OCCUPANCY = Histogram(
    "verify_batch_occupancy_ratio",
    "Requested rows / padded bucket rows per device flush, by rung",
    namespace="tendermint", subsystem="crypto", label_names=("rung",),
    buckets=OCCUPANCY_BUCKETS)


def _cold_compile_threshold_s() -> float:
    try:
        return float(os.environ.get("TM_TPU_COMPILE_COLD_S", "5.0"))
    except ValueError:
        return 5.0


# ---------------------------------------------------------------------------
# Batch-efficiency accounting
# ---------------------------------------------------------------------------

class DeviceStats:
    """Cumulative per-process accounting of device flushes: requested vs
    padded rows, per-rung flush counts, transfer bytes.  All updates are
    per flush (per batch, never per signature) and lock-protected; the
    disabled path is the caller's single `if STATS.enabled:` branch."""

    def __init__(self, enabled: bool | None = None,
                 hist: Histogram | None = None):
        self.enabled = (os.environ.get("TM_TPU_DEVSTATS", "1") != "0"
                        if enabled is None else enabled)
        self._hist = hist if hist is not None else VERIFY_BATCH_OCCUPANCY
        self._lock = threading.Lock()
        self.flushes = 0
        self.rows_requested = 0
        self.rows_padded = 0      # total rows shipped (sum of rungs)
        self.padding_rows = 0     # rows_padded - rows_requested
        self.transfer_bytes = 0   # host→device bytes, padded widths
        # (kind, rung) -> [flushes, rows_requested, padding_rows]
        self.rung_flushes: dict[tuple[str, int], list] = {}
        # device id -> [flushes, padded rows placed, transfer bytes] —
        # the mesh dispatcher (crypto/mesh_dispatch) attributes each
        # flush to the devices it actually landed on: a pinned flush is
        # one device's rows, a sharded flush is rung/n_dev rows per chip
        self.device_flushes: dict[int, list] = {}

    def record_flush(self, kind: str, n: int, rung: int,
                     nbytes: int = 0, devices: tuple | None = None) -> None:
        with self._lock:
            self.flushes += 1
            self.rows_requested += n
            self.rows_padded += rung
            self.padding_rows += rung - n
            self.transfer_bytes += nbytes
            cell = self.rung_flushes.get((kind, rung))
            if cell is None:
                cell = self.rung_flushes[(kind, rung)] = [0, 0, 0]
            cell[0] += 1
            cell[1] += n
            cell[2] += rung - n
            if devices:
                share_rows = rung // len(devices)
                share_bytes = nbytes // len(devices)
                for did in devices:
                    dcell = self.device_flushes.get(did)
                    if dcell is None:
                        dcell = self.device_flushes[did] = [0, 0, 0]
                    dcell[0] += 1
                    dcell[1] += share_rows
                    dcell[2] += share_bytes
        self._hist.observe(n / rung if rung else 1.0, rung=rung)

    def snapshot(self) -> dict:
        with self._lock:
            rungs = [
                {"kind": k, "rung": r, "flushes": f, "rows": rows,
                 "padding_rows": pad,
                 "mean_occupancy": round(rows / (rows + pad), 4)
                 if rows + pad else 1.0}
                for (k, r), (f, rows, pad) in sorted(self.rung_flushes.items())
            ]
            devices = [
                {"device": d, "flushes": f, "rows": rows, "bytes": nb}
                for d, (f, rows, nb) in sorted(self.device_flushes.items())
            ]
            return {
                "enabled": self.enabled,
                "flushes_total": self.flushes,
                "rows_requested_total": self.rows_requested,
                "rows_padded_total": self.rows_padded,
                "padding_rows_total": self.padding_rows,
                "transfer_bytes_total": self.transfer_bytes,
                "rungs": rungs,
                "devices": devices,
            }

    # -- scrape-time sample helpers (node/metrics.py) -------------------

    def rung_flush_samples(self) -> list:
        with self._lock:
            return [({"kind": k, "rung": str(r)}, float(f))
                    for (k, r), (f, _rows, _pad)
                    in sorted(self.rung_flushes.items())]

    def device_flush_samples(self) -> list:
        with self._lock:
            return [({"device": str(d)}, float(f))
                    for d, (f, _rows, _nb)
                    in sorted(self.device_flushes.items())]

    def device_rows_samples(self) -> list:
        with self._lock:
            return [({"device": str(d)}, float(rows))
                    for d, (_f, rows, _nb)
                    in sorted(self.device_flushes.items())]


# ---------------------------------------------------------------------------
# Compile tracking
# ---------------------------------------------------------------------------

class CompileTracker:
    """Records one event per (kind, rung, impl, flags) first call; a
    second recording of the same key (the functools.cache was cleared
    and the program re-traced) is an unexpected recompile.

    Every event carries a `source` — where the program came from:
      * "aot"              compiled ahead of traffic (shape-plan warm)
      * "deserialized"     loaded from a serialized executable artifact
      * "persistent-cache" first-call compile that hit jax's persistent
                           cache
      * "cold"             a real compile — seconds to minutes, the
                           term a warmed deployment must never record
    The warm paths (ops/shape_plan) pass their source explicitly; lazy
    first calls classify from jax's compile/cache-hit events (see
    _TrackedJit), falling back to the duration heuristic only when the
    call compiled nothing."""

    def __init__(self, max_events: int = MAX_COMPILE_EVENTS):
        self._lock = threading.Lock()
        self._keys: dict[tuple, int] = {}
        self.events: deque = deque(maxlen=max_events)
        self.compiles: dict[tuple[str, str], int] = {}        # (rung, impl)
        self.compile_seconds: dict[tuple[str, str], float] = {}
        # (rung, impl, source) -> count; feeds jit_compile_total{source=}
        self.source_counts: dict[tuple[str, str, str], int] = {}
        self.recompiles = 0

    def _begin(self, proxy: "_TrackedJit", rung: int) -> bool:
        """Atomically claim the first call for `rung` on this proxy so
        concurrent first calls record exactly one event."""
        with self._lock:
            if rung in proxy._seen:
                return False
            proxy._seen.add(rung)
            return True

    def record(self, kind: str, rung: int, impl: str, flags: tuple,
               duration_s: float, source: str | None = None) -> None:
        if source is None:
            source = ("persistent-cache"
                      if duration_s < _cold_compile_threshold_s() else "cold")
        cache_hit = source != "cold"
        key = (kind, rung, impl) + flags
        with self._lock:
            recompile = key in self._keys
            self._keys[key] = self._keys.get(key, 0) + 1
            ck = (str(rung), impl)
            self.compiles[ck] = self.compiles.get(ck, 0) + 1
            self.compile_seconds[ck] = (self.compile_seconds.get(ck, 0.0)
                                        + duration_s)
            sk = (str(rung), impl, source)
            self.source_counts[sk] = self.source_counts.get(sk, 0) + 1
            if recompile:
                self.recompiles += 1
            self.events.append({
                "t": time.time(),
                "kind": kind,
                "rung": rung,
                "impl": impl,
                "flags": dict(flags),
                "seconds": round(duration_s, 4),
                "source": source,
                "cache_hit": cache_hit,
                "recompile": recompile,
            })
        if recompile:
            _log.warning(
                "unexpected jit recompile: kind=%s rung=%s impl=%s flags=%s "
                "(%.1fs) — the same cache key was compiled twice; steady-state "
                "consensus should reuse compiled buckets",
                kind, rung, impl, dict(flags), duration_s)

    def snapshot(self) -> dict:
        with self._lock:
            sources: dict[str, int] = {}
            for (_r, _i, s), c in self.source_counts.items():
                sources[s] = sources.get(s, 0) + c
            return {
                "total": sum(self.compiles.values()),
                "seconds_total": round(sum(self.compile_seconds.values()), 3),
                "recompiles": self.recompiles,
                "sources": sources,
                "by_rung": {f"{r}/{i}": c
                            for (r, i), c in sorted(self.compiles.items())},
                "events": list(self.events),
            }

    def cold_compiles(self) -> int:
        """Programs that paid a REAL compile (source="cold") — the
        number a post-warm standard run must keep at zero."""
        with self._lock:
            return sum(c for (_r, _i, s), c in self.source_counts.items()
                       if s == "cold")

    # -- scrape-time sample helpers (node/metrics.py) -------------------

    def compile_count_samples(self) -> list:
        with self._lock:
            return [({"rung": r, "impl": i, "source": s}, float(c))
                    for (r, i, s), c in sorted(self.source_counts.items())]

    def compile_seconds_samples(self) -> list:
        with self._lock:
            return [({"rung": r, "impl": i}, s)
                    for (r, i), s in sorted(self.compile_seconds.items())]


_COMPILE_REQUEST_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_listen_lock = threading.Lock()
_listening = False


class _CompileCounts(threading.local):
    """Per-thread compile requests / persistent-cache hits seen so far."""

    requests = 0
    hits = 0


_counts = _CompileCounts()


def _ensure_compile_listeners() -> None:
    """Register (once per process) the jax.monitoring listeners that
    count, per thread, compile requests and persistent-cache hits.  jit
    compiles synchronously on the calling thread, so the deltas across
    one first call belong to that call's program."""
    global _listening
    with _listen_lock:  # first calls only: once per program, never hot
        if _listening:
            return
        from jax import monitoring

        def on_event(event: str, **_kw) -> None:
            if event == _CACHE_HIT_EVENT:
                _counts.hits += 1

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == _COMPILE_REQUEST_EVENT:
                _counts.requests += 1

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
        _listening = True


class _TrackedJit:
    """Thin first-call-timing proxy over a jitted callable.  Steady
    state costs one set-membership test per call (per batch).
    `prerecorded` proxies (AOT/deserialized executables — the warm path
    already recorded their compile event with the true source) skip the
    first-call timing entirely."""

    __slots__ = ("fn", "_tracker", "_kind", "_impl", "_flags", "_rung",
                 "_seen", "_prerecorded")

    def __init__(self, fn, tracker: CompileTracker, kind: str, impl: str,
                 rung: int | None, flags: tuple, prerecorded: bool = False):
        self.fn = fn
        self._tracker = tracker
        self._kind = kind
        self._impl = impl
        self._flags = flags
        self._rung = rung        # None: derive per call (sharded jits
        self._seen: set = set()  # compile once per input shape)
        self._prerecorded = prerecorded

    def __call__(self, *args, **kw):
        if self._prerecorded:
            return self.fn(*args, **kw)
        rung = self._rung
        if rung is None:
            try:
                rung = int(args[0].shape[0])
            except Exception:  # noqa: BLE001 — untypical args: still verify
                rung = -1
        if rung in self._seen or not self._tracker._begin(self, rung):
            return self.fn(*args, **kw)
        _ensure_compile_listeners()
        req0, hit0 = _counts.requests, _counts.hits
        t0 = time.perf_counter()
        out = self.fn(*args, **kw)
        dt = time.perf_counter() - t0
        requests, hits = _counts.requests - req0, _counts.hits - hit0
        source = None  # nothing compiled: the duration heuristic decides
        if requests:
            source = "persistent-cache" if hits >= requests else "cold"
        self._tracker.record(self._kind, rung, self._impl, self._flags, dt,
                             source=source)
        return out


def track_jit(fn, *, kind: str, impl: str, rung: int | None = None,
              tracker: CompileTracker | None = None,
              prerecorded: bool = False, **flags):
    """Wrap a jitted callable so its first call per bucket rung records
    a compile event.  `rung=None` derives the rung from the leading axis
    of the first argument per call (the sharded jits compile one program
    per input shape under a single jit).  `prerecorded=True` is for
    ahead-of-time executables whose compile event the warm path already
    recorded (source aot/deserialized) — the proxy then never times."""
    return _TrackedJit(fn, tracker if tracker is not None else TRACKER,
                       kind, impl, rung, tuple(sorted(flags.items())),
                       prerecorded)


# ---------------------------------------------------------------------------
# Device memory
# ---------------------------------------------------------------------------

def device_memory() -> list[dict]:
    """Per-device memory snapshot.  NEVER initializes a backend: if jax
    was not imported or no backend exists yet, returns [] — a metrics
    scrape must not be the process's first (possibly hanging) device
    contact."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not xb._backends:
        return []
    try:
        import jax

        devices = jax.devices()
    except Exception:  # noqa: BLE001 — backend died mid-flight
        return []
    out = []
    for d in devices:
        entry = {"id": int(d.id), "platform": str(d.platform),
                 "device_kind": str(d.device_kind)}
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001 — unsupported on this backend
            ms = None
        if ms:
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                      "largest_alloc_size"):
                if k in ms:
                    entry[k] = int(ms[k])
        bufs = d.live_buffers()
        entry["live_buffers"] = len(bufs)
        entry["live_buffer_bytes"] = int(sum(b.nbytes for b in bufs))
        out.append(entry)
    return out


def memory_gauge_samples() -> list:
    """[(labels, value)] rows for the device_memory_bytes gauge."""
    out = []
    for e in device_memory():
        lbl = {"device": str(e["id"]), "platform": e["platform"]}
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                  "live_buffer_bytes"):
            if k in e:
                out.append(({**lbl, "kind": k}, float(e[k])))
    return out


# ---------------------------------------------------------------------------
# Process-wide instances + snapshot
# ---------------------------------------------------------------------------

STATS = DeviceStats()
TRACKER = CompileTracker()


def reset(enabled: bool | None = None) -> None:
    """Fresh STATS/TRACKER (tests/benchmarks).  Existing _TrackedJit
    proxies keep their per-proxy seen sets, so already-compiled buckets
    are not re-reported into the new tracker."""
    global STATS, TRACKER
    STATS = DeviceStats(enabled=enabled)
    TRACKER = CompileTracker()


def device_stats() -> dict:
    """One snapshot of the device layer: batch efficiency, compile
    events, device memory, and per-program HLO costs/roofline (the
    `costs` block — utils/costmodel; cheap: only already-harvested
    records, a snapshot never lowers or compiles anything)."""
    out = STATS.snapshot()
    out["compile"] = TRACKER.snapshot()
    out["device_memory"] = device_memory()
    try:
        from tendermint_tpu.utils import costmodel

        out["costs"] = costmodel.costs_block()
    except Exception:  # noqa: BLE001 — cost harvest must never break a scrape
        out["costs"] = {"enabled": False, "pending": 0, "records": [],
                        "peak_flops_per_s": None}
    return out


def render_text() -> str:
    """Plain-text dump for /debug/pprof/device."""
    snap = device_stats()
    lines = [
        f"== device flushes (accounting {'on' if snap['enabled'] else 'OFF'}) ==",
        f"flushes={snap['flushes_total']} rows={snap['rows_requested_total']} "
        f"padding_rows={snap['padding_rows_total']} "
        f"transfer_bytes={snap['transfer_bytes_total']}",
    ]
    for r in snap["rungs"]:
        lines.append(
            f"  {r['kind']:>14} rung {r['rung']:>6}: {r['flushes']} flushes, "
            f"{r['rows']} rows, {r['padding_rows']} padded, "
            f"occupancy {r['mean_occupancy']:.3f}")
    for d in snap.get("devices", []):
        lines.append(
            f"  dev{d['device']}: {d['flushes']} flushes, "
            f"{d['rows']} rows placed, {d['bytes']} bytes")
    comp = snap["compile"]
    stxt = " ".join(f"{k}={v}" for k, v in sorted(comp["sources"].items()))
    lines.append(
        f"== jit compiles ==\ntotal={comp['total']} "
        f"seconds_total={comp['seconds_total']} recompiles={comp['recompiles']}"
        + (f" [{stxt}]" if stxt else ""))
    for ev in comp["events"]:
        src = ev.get("source") or ("cache-hit" if ev["cache_hit"] else "cold")
        lines.append(
            f"  {ev['kind']:>14} rung {ev['rung']:>6} impl={ev['impl']} "
            f"{ev['seconds']:.3f}s "
            f"{src.upper() if src == 'cold' else src}"
            f"{' RECOMPILE' if ev['recompile'] else ''}")
    lines.append("== device memory ==")
    mem = snap["device_memory"]
    if not mem:
        lines.append("  (no initialized backend)")
    for e in mem:
        detail = " ".join(f"{k}={e[k]}" for k in
                          ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                           "live_buffers", "live_buffer_bytes") if k in e)
        lines.append(f"  dev{e['id']} {e['platform']} {e['device_kind']} "
                     f"{detail}".rstrip())
    costs = snap.get("costs") or {}
    recs = costs.get("records") or []
    lines.append(
        f"== program costs (harvested {len(recs)}, "
        f"pending {costs.get('pending', 0)}) ==")
    for r in recs:

        def _f(key, fmt="{:.3g}"):
            v = r.get(key)
            return fmt.format(v) if v is not None else "n/a"

        lines.append(
            f"  {r['kind']:>14} rung {r['rung']:>6} impl={r['impl']} "
            f"flops={_f('flops')} bytes={_f('bytes_accessed')} "
            f"AI={_f('arithmetic_intensity')} "
            f"peak_mem={_f('peak_memory_bytes')} "
            f"util={_f('flops_utilization', '{:.2%}')} [{r['source']}]")
    return "\n".join(lines) + "\n"
