"""Diagnostics HTTP listener — the pprof analog.

Parity: reference node/node.go:858-863 serves net/http/pprof when
config.RPC.PprofListenAddress is set; `tendermint debug` scrapes it.
The Python equivalents of goroutine/heap profiles:

    GET /debug/pprof/          index
    GET /debug/pprof/goroutine all thread stacks + live asyncio tasks
    GET /debug/pprof/stacks    all-thread Python stack dump (named
                               threads, the flight-recorder formatter —
                               the live-wedge counterpart to the
                               crash-time bundle)
    GET /debug/pprof/heap      gc object counts by type (top 50)
    GET /debug/pprof/trace     recent span ring (utils.trace) as JSONL;
                               ?fmt=chrome returns the Perfetto-loadable
                               Chrome trace-event JSON
    GET /debug/pprof/device    device-layer accounting (utils.devmon):
                               jit compile events, batch occupancy and
                               padding waste, device memory
    GET /debug/pprof/health    the health watchdog's per-detector
                               status + recent transitions
                               (utils.health)
    GET /debug/pprof/profile   statistical CPU profile (utils.profiler):
                               ?seconds=N runs a blocking delta capture
                               (default 2s, folded/collapsed-stack
                               text); ?fmt=chrome returns the capture
                               as Perfetto-loadable trace-event JSON;
                               without ?seconds the continuous ring is
                               returned immediately
    GET /debug/pprof/history   recorded metric history (utils.history)
                               as JSON: ?metric=NAME returns one
                               series' points + rates, without it the
                               delta-codec lines for the whole range
                               (the fleet scraper's backfill food);
                               ?since=UNIX_SECONDS bounds the range

Plain text responses, stdlib only.
"""

from __future__ import annotations

import asyncio
import gc
import sys
import time
import traceback
import urllib.parse
from collections import Counter

from tendermint_tpu.utils.log import Logger, nop_logger

# _heap_dump scans at most this many gc objects per request: walking the
# full heap is unbounded on large nodes, and this endpoint gets scraped
# exactly when the node is loaded.
HEAP_SCAN_LIMIT = 200_000


def _goroutine_dump() -> str:
    out = []
    out.append("== threads ==")
    for tid, frame in sys._current_frames().items():
        out.append(f"\n-- thread {tid} --")
        out.extend(ln.rstrip() for ln in traceback.format_stack(frame))
    out.append("\n== asyncio tasks ==")
    try:
        for task in asyncio.all_tasks():
            out.append(f"\n-- {task.get_name()} ({'done' if task.done() else 'live'}) --")
            stack = task.get_stack(limit=8)
            for frame in stack:
                out.extend(
                    ln.rstrip()
                    for ln in traceback.format_stack(frame)[-1:]
                )
    except RuntimeError:
        out.append("(no running loop)")
    return "\n".join(out) + "\n"


def _heap_dump(top: int = 50, max_objects: int = HEAP_SCAN_LIMIT) -> str:
    t0 = time.perf_counter()
    objs = gc.get_objects()
    total = len(objs)
    scanned = min(total, max_objects)
    counts = Counter(type(o).__name__ for o in objs[:scanned])
    del objs
    dt_ms = (time.perf_counter() - t0) * 1e3
    lines = [f"{n:>10}  {name}" for name, n in counts.most_common(top)]
    return (
        f"gc objects by type (top {top}; scanned {scanned}/{total} "
        f"objects in {dt_ms:.1f}ms):\n" + "\n".join(lines) + "\n"
    )


def _trace_dump(fmt: str) -> tuple[str, str]:
    """(content_type, body) for the span-ring dump."""
    from tendermint_tpu.utils import trace as tmtrace

    if fmt == "chrome":
        return "application/json", tmtrace.export_chrome()
    head = (
        f"# trace ring: enabled={int(tmtrace.enabled())} "
        f"spans={len(tmtrace.spans())} capacity={tmtrace.ring_size()} "
        f"dropped={tmtrace.dropped()} "
        f"(TM_TPU_TRACE / TM_TPU_TRACE_RING; ?fmt=chrome for Perfetto)\n"
    )
    return "text/plain", head + tmtrace.export_jsonl() + "\n"


class PprofServer:
    """Diagnostics listener on the shared TextHTTPServer (independent of
    the RPC server: must answer when the RPC stack is wedged)."""

    def __init__(self, logger: Logger | None = None, health=None,
                 prof=None, history=None):
        from tendermint_tpu.utils.httpserv import TextHTTPServer

        self.logger = logger or nop_logger()
        # the node's HealthMonitor (utils/health.py); defaults to the
        # NOP singleton so /debug/pprof/health always answers
        if health is None:
            from tendermint_tpu.utils import health as _health

            health = _health.NOP
        self.health = health
        # the node's continuous Profiler (utils/profiler.py); defaults
        # to the NOP singleton so /debug/pprof/profile always answers
        if prof is None:
            from tendermint_tpu.utils import profiler as _profiler

            prof = _profiler.NOP
        self.prof = prof
        # the node's HistoryRecorder (utils/history.py); defaults to
        # the NOP singleton so /debug/pprof/history always answers
        if history is None:
            from tendermint_tpu.utils import history as _history

            history = _history.NOP
        self.history = history
        self._http = TextHTTPServer(self._route)

    async def start(self, host: str, port: int) -> tuple[str, int]:
        addr = await self._http.start(host, port)
        self.logger.info("pprof listener up", addr=f"{addr[0]}:{addr[1]}")
        return addr

    async def stop(self) -> None:
        await self._http.stop()

    async def _route(self, path: str):
        parsed = urllib.parse.urlsplit(path)
        route = parsed.path
        if route.startswith("/debug/pprof/goroutine"):
            body = _goroutine_dump()
        elif route.startswith("/debug/pprof/stacks"):
            # named all-thread stack dump via the flight recorder's
            # formatter (utils/health) — what a wedged node looks like
            # RIGHT NOW, without waiting for a detector to bundle it
            from tendermint_tpu.utils.health import format_thread_stacks

            body = format_thread_stacks()
        elif route.startswith("/debug/pprof/health"):
            body = self.health.render_text()
        elif route.startswith("/debug/pprof/heap"):
            # off the event loop: walking the gc heap can take seconds on
            # a loaded node, exactly when this endpoint gets scraped
            body = await asyncio.to_thread(_heap_dump)
        elif route.startswith("/debug/pprof/trace"):
            fmt = urllib.parse.parse_qs(parsed.query).get("fmt", [""])[0]
            ctype, body = _trace_dump(fmt)
            return 200, ctype, body.encode()
        elif route.startswith("/debug/pprof/profile"):
            q = urllib.parse.parse_qs(parsed.query)
            fmt = q.get("fmt", [""])[0]
            raw = q.get("seconds", [""])[0]
            if not self.prof.enabled:
                body = "# tendermint-tpu profile enabled=0\n"
                return 200, "text/plain", body.encode()
            if raw or fmt == "chrome":
                try:
                    seconds = float(raw) if raw else 2.0
                except ValueError:
                    return 400, "text/plain", b"bad seconds\n"
                # blocking delta capture, off the event loop: capture
                # sleeps for `seconds` and the loop must keep serving
                cap = await asyncio.to_thread(self.prof.capture, seconds)
                from tendermint_tpu.utils import profiler as _profiler

                if fmt == "chrome":
                    return (200, "application/json",
                            _profiler.export_chrome(cap).encode())
                header = (f"tendermint-tpu profile capture "
                          f"node={cap['node'] or 'node'} enabled=1 "
                          f"hz={cap['hz']:g} seconds={cap['seconds']:g} "
                          f"sweeps={cap['sweeps']} "
                          f"samples={cap['samples']}")
                body = _profiler.render_folded(cap["stacks"],
                                               header=header)
            else:
                body = self.prof.folded_recent()
        elif route.startswith("/debug/pprof/history"):
            q = urllib.parse.parse_qs(parsed.query)
            metric = q.get("metric", [""])[0]
            raw = q.get("since", [""])[0]
            try:
                since_w = int(float(raw) * 1e9) if raw else 0
            except ValueError:
                return 400, "text/plain", b"bad since\n"
            # reading the range decodes on-disk segments: off the loop
            doc = await asyncio.to_thread(self.history.export, metric,
                                          since_w)
            import json as _json

            return 200, "application/json", _json.dumps(doc).encode()
        elif route.startswith("/debug/pprof/device"):
            # device-layer accounting (utils/devmon): compile events,
            # batch occupancy/padding, device memory.  Never initializes
            # a backend — safe to scrape a node whose device never woke.
            from tendermint_tpu.utils import devmon

            body = devmon.render_text()
        elif route.startswith("/debug/pprof"):
            body = ("pprof analog endpoints:\n"
                    "/debug/pprof/goroutine\n/debug/pprof/stacks\n"
                    "/debug/pprof/heap\n"
                    "/debug/pprof/trace[?fmt=chrome]\n"
                    "/debug/pprof/profile[?seconds=N&fmt=chrome]\n"
                    "/debug/pprof/history[?metric=NAME&since=UNIX_S]\n"
                    "/debug/pprof/device\n/debug/pprof/health\n")
        else:
            return None
        return 200, "text/plain", body.encode()
