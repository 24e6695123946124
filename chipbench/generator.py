"""The one traffic generator: it reads a mix's parameters
(chipbench/traffic/<name>.json) and drives the cell's entry point, bound
by its module (chipbench/entries/<entry>.py `bind`), over a pool of items:
whatever one call takes, each knowing its consulted rows (`n_rows`).

    generator   "closed_loop": each caller sends its next call when its
                last returned (a slow system receives less load)
    callers     threads calling; the pool is dealt out among them so that
                no two ever walk the same item
    think_ms    a caller's pause between a return and its next call
    pool        the pool-size rule (see data.pool_size)

A window runs `seconds` from its first call; no call starts after that,
and the last ones are waited for, so the window ends when the last call
returned.  Every call of the window is recorded: latencies are over all
of them, the rate over all the work and all the time.
"""

from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass

_WRONG_SIG = re.compile(r"wrong signature \(#(\d+)\)")


@dataclass
class Call:
    item: int            # index into the pool
    t_start: float       # time.perf_counter()
    seconds: float
    outcome: tuple       # ("accept", None) | ("wrong_signature", row) | ...
    rows: int            # rows the call consulted (the item's `n_rows`)


def outcome_of(exc: BaseException | None, said=None) -> tuple:
    """What a call said: it returned None (accept) or raised ValueError
    naming the failing row; anything else it raised is an error of the
    call itself.  A bound call whose program answers in another shape
    returns the outcome as a tuple of its own (`said`)."""
    if exc is None:
        return ("accept", None) if said is None else said
    if isinstance(exc, ValueError):
        m = _WRONG_SIG.search(str(exc))
        if m:
            return ("wrong_signature", int(m.group(1)))
        if "insufficient voting power" in str(exc):
            return ("insufficient_power", None)
    return ("error", f"{type(exc).__name__}: {exc}"[:200])


def timed(call, idx: int, item) -> Call:
    """One call of the bound entry point on `item`, timed and its answer read."""
    said = exc = None
    t = time.perf_counter()
    try:
        said = call(item)
    except Exception as e:  # noqa: BLE001 — the answer, or the call's error
        exc = e
    return Call(idx, t, time.perf_counter() - t, outcome_of(exc, said), item.n_rows)


def _caller(k: int, traffic: dict, pool: list, call, t_end: float,
            out: list, between, min_calls: int) -> None:
    mine = list(range(k, len(pool), traffic["callers"]))
    think = traffic["think_ms"] / 1e3
    i = 0
    while True:
        if between is not None:
            between(len(out))
        if time.perf_counter() >= t_end and i >= min_calls:
            return
        idx = mine[i % len(mine)]
        out.append(timed(call, idx, pool[idx]))
        i += 1
        if think:
            time.sleep(think)


def run_window(traffic: dict, pool: list, call, seconds: float,
               between=None, min_calls: int = 0) -> tuple[list[Call], float, float]:
    """Drive `call(item)`; returns (calls in start order, t0, t1)
    on the perf_counter clock.  `between(n_done)` runs on caller 0 between
    its calls (the traced run starts and stops the profiler there).
    `min_calls` keeps each caller going past `seconds` until it made that
    many calls (the many-seeds prover walks the whole pool once; a run of
    the benchmark passes 0)."""
    if traffic["generator"] != "closed_loop":
        raise ValueError(f"unknown generator {traffic['generator']!r}")
    callers = traffic["callers"]
    t0 = time.perf_counter()
    t_end = t0 + seconds
    outs = [[] for _ in range(callers)]
    threads = [threading.Thread(
        target=_caller, name=f"chipbench-caller-{k}",
        args=(k, traffic, pool, call, t_end, outs[k], None, min_calls))
        for k in range(1, callers)]
    for th in threads:
        th.start()
    _caller(0, traffic, pool, call, t_end, outs[0], between, min_calls)
    for th in threads:
        th.join()
    t1 = time.perf_counter()
    calls = sorted((c for o in outs for c in o), key=lambda c: c.t_start)
    return calls, t0, t1
