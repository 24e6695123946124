"""Structured span tracing: monotonic-clock spans with parent/child
links, a bounded in-memory ring buffer, JSONL export, and a Chrome
trace-event (Perfetto-loadable) dump.

In the spirit of Dapper-style tracing scoped to one process: the verify
pipeline (crypto/async_verify.py), the consensus state machine
(consensus/state.py), blocksync and the RPC server drop spans here so
"where does the time go" (queue wait vs. linger vs. host prep vs. device
execute vs. consensus step) is answerable from a running node — via
`GET /debug/pprof/trace` on the PprofServer, or the bench's per-stage
summary.

Cost contract: with tracing off (the default), every span site pays ONE
branch — `span()` returns a shared no-op singleton and `record()` /
`instant()` return immediately, so the consensus and verify hot paths
stay clean (the same rule node/metrics.py states for metrics).

Env knobs:
  TM_TPU_TRACE        1 enables tracing (default 0).  Resolved lazily at
                      the FIRST span site (not at import — tmlint
                      import-time-env), so setting it after import still
                      takes effect; tests/benches pin it with
                      set_enabled(), long-lived CLIs re-read with
                      reload_env().
  TM_TPU_TRACE_RING   ring-buffer capacity in spans (default 4096).
                      Oldest spans are dropped first, and counted:
                      dropped() is the number pushed out since the last
                      clear().  Applied when the enable flag first
                      resolves true, or explicitly via
                      set_ring_size()/reload_env().

All timestamps come from time.perf_counter_ns() — perf_counter() floats
handed to record() share the same clock origin, so externally measured
durations (cross-thread device drains, blocksync round trips) land on
the same timeline as context-manager spans.

Parent links: a `span()` is parented under the calling thread's
innermost open span, and so is a `record()` — the span open on the
thread that CALLS record(), whatever thread the measured work ran on
(`verify.submit` hangs under the caller's `commit.verify`; the worker
thread holds no open span, so its records stay roots); a `record()`
given `parent=` hangs there instead (`verify.submit.keys` under
`verify.submit`, both recorded when the submit ends).  Spans of one
flush on different threads are tied by attrs instead (`flush`,
`oldest_submit_ns`; docs/observability.md).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque

ENV_FLAG = "TM_TPU_TRACE"
ENV_RING = "TM_TPU_TRACE_RING"
DEFAULT_RING_SIZE = 4096

_PID = os.getpid()


def _env_ring_size() -> int:
    try:
        return max(1, int(os.environ.get(ENV_RING, DEFAULT_RING_SIZE)))
    except ValueError:
        return DEFAULT_RING_SIZE


# None = not yet resolved from the environment: the first span site (or
# enabled() call) reads TM_TPU_TRACE then, so env vars set after import
# still take effect.  set_enabled()/reload_env() pin a real bool.
_enabled: bool | None = None
_lock = threading.Lock()
_ring: deque = deque(maxlen=DEFAULT_RING_SIZE)
_dropped = 0   # spans pushed out of the ring since the last clear()
_ids = itertools.count(1)
_tls = threading.local()


def _resolve_enabled() -> bool:
    global _enabled
    _enabled = os.environ.get(ENV_FLAG, "0") not in ("", "0")
    if _enabled:
        # size the ring from the env only when tracing actually turns
        # on; an explicit earlier set_ring_size() is preserved when
        # TM_TPU_TRACE_RING is unset (deque keeps the default otherwise)
        if os.environ.get(ENV_RING):
            set_ring_size(_env_ring_size())
    return _enabled


def enabled() -> bool:
    en = _enabled
    return en if en is not None else _resolve_enabled()


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def refresh_from_env() -> None:
    """Re-read TM_TPU_TRACE / TM_TPU_TRACE_RING (tests, long-lived CLIs)."""
    set_enabled(os.environ.get(ENV_FLAG, "0") not in ("", "0"))
    set_ring_size(_env_ring_size())


#: the lazy-env contract name shared with crypto.batch and
#: ops.ed25519_jax (docs/linting.md, import-time-env)
reload_env = refresh_from_env


def set_ring_size(n: int) -> None:
    """Resize the ring, keeping the most recent spans that still fit;
    those that do not are counted as dropped."""
    global _ring, _dropped
    n = max(1, int(n))
    with _lock:
        _dropped += max(0, len(_ring) - n)
        _ring = deque(_ring, maxlen=n)


def ring_size() -> int:
    return _ring.maxlen or DEFAULT_RING_SIZE


def dropped() -> int:
    """Spans a full ring pushed out since the last clear()."""
    return _dropped


def clear() -> None:
    global _dropped
    with _lock:
        _ring.clear()
        _dropped = 0


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _append(name: str, span_id: int, parent_id, t0_ns: int, dur_ns: int,
            attrs: dict) -> None:
    global _dropped
    span = {
        "name": name,
        "id": span_id,
        "parent": parent_id,
        "t0_ns": t0_ns,
        "dur_ns": dur_ns,
        "tid": threading.get_ident(),
        "attrs": attrs,
    }
    with _lock:
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _ring.append(span)


class _SpanCtx:
    """A live span: parented under the thread's current span, recorded
    into the ring on exit (exceptions still record — the span's duration
    up to the raise is exactly what a trace reader wants to see)."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "t0")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Attrs only known once the block ran (a count it produced)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "_SpanCtx":
        stack = _stack()
        self.parent_id = stack[-1] if stack else None
        self.span_id = next(_ids)
        stack.append(self.span_id)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter_ns() - self.t0
        stack = _stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        _append(self.name, self.span_id, self.parent_id, self.t0, dur,
                self.attrs)
        return False


class _NopSpan:
    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_NopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOP_SPAN = _NopSpan()


def span(name: str, **attrs) -> "_SpanCtx | _NopSpan":
    """Context manager measuring the enclosed block.  Disabled tracing
    returns a shared no-op singleton: one branch, zero allocation."""
    en = _enabled
    if not (en if en is not None else _resolve_enabled()):
        return _NOP_SPAN
    return _SpanCtx(name, attrs)


def record(name: str, t0: float, dur: float, parent: int | None = None,
           **attrs) -> int | None:
    """A complete span with externally measured timing — t0/dur in
    seconds on the time.perf_counter() clock.  For work whose start and
    end live on different threads (device enqueue → verdict drain) or
    whose duration was measured on another monotonic clock.  Parented
    under the calling thread's innermost open span, like `span()`, or
    under `parent`: the id an earlier record() returned, for the parts
    of a span that is itself recorded at its end.  Returns the span's
    id (None with tracing off)."""
    en = _enabled
    if not (en if en is not None else _resolve_enabled()):
        return None
    span_id = next(_ids)
    # both ends truncated to the ns: a part that ends where its parent
    # ends (the same float) ends on the same ns, never one past it
    t0_ns = int(t0 * 1e9)
    _append(name, span_id,
            parent if parent is not None else current_span_id(),
            t0_ns, max(0, int((t0 + dur) * 1e9) - t0_ns), attrs)
    return span_id


def instant(name: str, **attrs) -> None:
    """Zero-duration marker (height/round transitions and the like)."""
    en = _enabled
    if not (en if en is not None else _resolve_enabled()):
        return
    _append(name, next(_ids), None, time.perf_counter_ns(), 0, attrs)


def current_span_id() -> int | None:
    """The id of this thread's innermost open span, or None.  Lets other
    structured sinks (the consensus event journal) stamp their records
    with the span that produced them, so a journal line and its trace
    span correlate offline."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


# -- export -----------------------------------------------------------------


def spans() -> list[dict]:
    """Snapshot of the ring, oldest first."""
    with _lock:
        return list(_ring)


def export_jsonl() -> str:
    """One JSON object per span per line (text dump of the ring)."""
    return "\n".join(json.dumps(s, default=str) for s in spans())


def export_chrome() -> str:
    """Chrome trace-event JSON: load at ui.perfetto.dev (or
    chrome://tracing).  Complete ("X") events; nesting renders from
    same-tid containment, parent ids ride along in args."""
    events = []
    for s in spans():
        args = dict(s["attrs"])
        args["span_id"] = s["id"]
        if s["parent"] is not None:
            args["parent_id"] = s["parent"]
        events.append({
            "ph": "X",
            "name": s["name"],
            "cat": s["name"].split(".", 1)[0],
            "ts": s["t0_ns"] / 1e3,   # trace-event timestamps are in us
            "dur": s["dur_ns"] / 1e3,
            "pid": _PID,
            "tid": s["tid"],
            "args": args,
        })
    return json.dumps({"traceEvents": events, "displayTimeUnit": "ms"},
                      default=str)


def _pct(sorted_ns: list[int], q: float) -> float:
    """Nearest-rank percentile of a sorted sample, in milliseconds."""
    idx = min(len(sorted_ns) - 1, max(0, int(q * len(sorted_ns))))
    return sorted_ns[idx] / 1e6


def summary() -> dict[str, dict]:
    """Per-span-name latency summary over the current ring:
    {name: {count, p50_ms, p95_ms, p99_ms, total_ms}} — the bench's
    per-stage trace table comes straight from this."""
    by_name: dict[str, list[int]] = {}
    for s in spans():
        by_name.setdefault(s["name"], []).append(s["dur_ns"])
    out = {}
    for name, ds in sorted(by_name.items()):
        ds.sort()
        out[name] = {
            "count": len(ds),
            "p50_ms": round(_pct(ds, 0.50), 4),
            "p95_ms": round(_pct(ds, 0.95), 4),
            "p99_ms": round(_pct(ds, 0.99), 4),
            "total_ms": round(sum(ds) / 1e6, 4),
        }
    return out
