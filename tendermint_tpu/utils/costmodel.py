"""Kernel cost model: per-program HLO cost/roofline accounting.

PR 7's AOT pipeline compiles every (kind, rung, impl, flags) program via
``jit().lower().compile()`` but never read what XLA already knows about
each executable: ``cost_analysis()`` (FLOPs, bytes accessed at the HLO
level) and ``memory_analysis()`` (argument/output/temp/code bytes — the
peak device footprint).  Without those numbers the verify kernel is a
black box to optimize against: the r04→r05 throughput regression
(38.7k → 36.9k sigs/s) shipped with no way to say whether the kernel is
compute- or memory-bound, and ROADMAP item 2's MXU round needs exactly
that roofline picture to pick targets.

This module is the harvest point:

  * ``COSTS`` (CostModel) — one record per (kind, rung, impl).  The AOT
    warm path (ops/shape_plan.warm_entry) harvests COMPILED executables
    (cost + memory analysis, source "compiled"); the lazy jit caches
    (ops/ed25519_jax._compiled) register a PENDING entry
    whose resolver lowers the program and reads the lowering's cost
    analysis (source "lowered" — tracing only, never an XLA compile:
    resolving costs seconds of Python, not a compile).  Pending
    entries resolve only when explicitly asked (``resolve_pending`` —
    the `tendermint-tpu profile` CLI, never a metrics scrape).
  * Roofline derivation — arithmetic intensity (FLOPs / HLO bytes
    accessed), achieved FLOPs/s from the verify pipeline's measured
    device-execute histogram (crypto/async_verify), utilization against
    ``peak_flops_per_s()`` (TM_TPU_PEAK_FLOPS override, else a
    device-kind table, else unknown → reported as None, never guessed),
    and bytes/row at both levels: the HLO's working-set bytes vs the
    129 B/row host→device transfer devmon measured.
  * Exports — ``COSTS.flops_samples()`` etc. feed the
    ``verify_rung_flops`` / ``verify_rung_bytes_accessed`` /
    ``verify_rung_peak_memory_bytes`` gauges in node/metrics.py, and
    ``costs_block()`` is the ``costs`` block in devmon snapshots and
    the `top` dashboard.

Backend sparsity, stated once: XLA-CPU returns sparse cost dicts,
``memory_analysis()`` may be absent or raise, and a deserialized
executable may expose neither.
Every parser here therefore maps "absent" to None and every harvest is
exception-contained — a missing analysis field degrades a report to
"n/a", it never breaks the caller (the acceptance bar for
`tendermint-tpu profile` on XLA-CPU).
"""

from __future__ import annotations

import logging
import os
import threading

_log = logging.getLogger("tendermint_tpu.costmodel")

# Host→device transfer bytes per row, by program kind: packed 32-byte
# rows plus the valid bit (devmon's measured 129 B/row).
ROW_TRANSFER_BYTES = {"verify": 4 * 32 + 1}

# Peak dense-FLOP/s keyed by the EXACT `jax.devices()[0].device_kind`
# string (an upper bound: the int64-limb kernel runs on the VPU, so
# utilization against an MXU peak reads LOW by construction).  Exact
# keys, not substrings: the chip calls itself "TPU v5 lite" — there is
# no "v5e" in it — and a substring table silently reads such a device as
# unknown, or worse, matches the wrong generation.  One entry, for the
# one device kind this code has been run on (chip_smoke.py prints the
# string); a device that is not in the table is unknown, never a
# default.  TM_TPU_PEAK_FLOPS overrides.
_PEAK_FLOPS_BY_KIND = {
    # TPU v5e, one chip: 197 TFLOP/s bf16 — Google Cloud documentation,
    # "TPU v5e" system architecture page
    "TPU v5 lite": 197e12,
}


def row_transfer_bytes(kind: str) -> int | None:
    return ROW_TRANSFER_BYTES.get(kind)


def peak_flops_per_s() -> float | None:
    """Peak device FLOPs/s for utilization math: TM_TPU_PEAK_FLOPS wins;
    else the device-kind table (read via devmon.device_memory(), which
    never initializes a backend); else None — callers report n/a rather
    than divide by a guess."""
    raw = os.environ.get("TM_TPU_PEAK_FLOPS", "")
    if raw:
        try:
            return float(raw)
        except ValueError:
            _log.warning("ignoring malformed TM_TPU_PEAK_FLOPS=%r", raw)
    kind = device_kind()
    return _PEAK_FLOPS_BY_KIND.get(kind) if kind else None


def device_kind() -> str | None:
    """The first device's `device_kind` as JAX reports it, read via
    devmon.device_memory() (which never initializes a backend); None
    before any backend exists."""
    from tendermint_tpu.utils import devmon

    for e in devmon.device_memory():
        return e["device_kind"]
    return None


# ---------------------------------------------------------------------------
# Backend-analysis parsers (sparse-tolerant)
# ---------------------------------------------------------------------------

def _num(v) -> float | None:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if f == f else None  # NaN → unknown


def parse_cost_analysis(ca) -> dict:
    """Normalize a backend cost_analysis() result (a dict), or
    None/garbage.  Absent fields come back None — sparse dicts are the
    XLA-CPU norm."""
    out = {"flops": None, "bytes_accessed": None, "transcendentals": None}
    if not isinstance(ca, dict):
        return out
    for field, keys in (("flops", ("flops",)),
                        ("bytes_accessed", ("bytes accessed",
                                            "bytes_accessed")),
                        ("transcendentals", ("transcendentals",))):
        for k in keys:
            n = _num(ca.get(k))
            if n is not None:
                out[field] = n
                break
    return out


_MEM_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("code_bytes", "generated_code_size_in_bytes"),
)


def parse_memory_analysis(ma) -> dict:
    """Normalize a CompiledMemoryStats (attribute access) or a plain
    dict.  peak_memory_bytes is the resident footprint one execution
    needs: arguments + outputs + temps + generated code (aliased bytes
    excluded — they overlap arguments)."""
    out = {k: None for k, _src in _MEM_FIELDS}
    out["peak_memory_bytes"] = None
    if ma is None:
        return out
    get = ma.get if isinstance(ma, dict) else lambda k: getattr(ma, k, None)
    known = False
    for field, src in _MEM_FIELDS:
        n = _num(get(src))
        if n is not None:
            out[field] = n
            known = True
    if known:
        out["peak_memory_bytes"] = sum(
            out[f] or 0.0 for f in
            ("argument_bytes", "output_bytes", "temp_bytes", "code_bytes"))
    return out


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

_REC_FIELDS = ("flops", "bytes_accessed", "transcendentals",
               "peak_memory_bytes", "argument_bytes", "output_bytes",
               "temp_bytes", "alias_bytes", "code_bytes")


class CostRecord:
    __slots__ = ("kind", "rung", "impl", "flags", "source",
                 "error") + _REC_FIELDS

    def __init__(self, kind: str, rung: int, impl: str, flags: dict,
                 source: str):
        self.kind = kind
        self.rung = int(rung)
        self.impl = impl
        self.flags = dict(flags or {})
        self.source = source  # "compiled" | "lowered"
        self.error = None
        for f in _REC_FIELDS:
            setattr(self, f, None)

    def to_dict(self) -> dict:
        d = {"kind": self.kind, "rung": self.rung, "impl": self.impl,
             "flags": self.flags, "source": self.source}
        for f in _REC_FIELDS:
            v = getattr(self, f)
            if v is not None:
                d[f] = v
        if self.error:
            d["error"] = self.error
        return d


class CostModel:
    """Per-(kind, rung, impl) cost records plus a pending queue of lazy
    programs awaiting harvest.  All mutation is lock-protected; the
    disabled path is the caller's single `if COSTS.enabled:` branch
    (same contract as devmon.STATS)."""

    def __init__(self, enabled: bool | None = None):
        self.enabled = (os.environ.get("TM_TPU_COSTMODEL", "1") != "0"
                        if enabled is None else enabled)
        self._lock = threading.Lock()
        self._records: dict[tuple, CostRecord] = {}
        self._pending: dict[tuple, object] = {}  # key -> lower thunk

    @staticmethod
    def _key(kind: str, rung: int, impl: str) -> tuple:
        return (kind, int(rung), impl)

    # -- harvesting -----------------------------------------------------

    def record_compiled(self, kind: str, rung: int, impl: str, flags: dict,
                        executable) -> CostRecord:
        """Harvest a COMPILED executable (the AOT registry hook) —
        cost_analysis + memory_analysis, each independently best-effort.
        Never raises."""
        rec = CostRecord(kind, rung, impl, flags, "compiled")
        try:
            _rec_update(rec, parse_cost_analysis(executable.cost_analysis()))
        except Exception as e:  # noqa: BLE001 — absent on this backend
            rec.error = f"cost_analysis: {str(e)[:120]}"
        try:
            _rec_update(rec, parse_memory_analysis(
                executable.memory_analysis()))
        except Exception as e:  # noqa: BLE001
            rec.error = ((rec.error + "; ") if rec.error else "") + \
                f"memory_analysis: {str(e)[:120]}"
        self._install(rec)
        return rec

    def record_lowered(self, kind: str, rung: int, impl: str, flags: dict,
                       lowered) -> CostRecord:
        """Harvest a LOWERED (traced, not compiled) program — cost
        analysis only; memory analysis needs a compile, so those fields
        stay None.  Never raises."""
        rec = CostRecord(kind, rung, impl, flags, "lowered")
        try:
            _rec_update(rec, parse_cost_analysis(lowered.cost_analysis()))
        except Exception as e:  # noqa: BLE001
            rec.error = f"cost_analysis: {str(e)[:120]}"
        self._install(rec)
        return rec

    def _install(self, rec: CostRecord) -> None:
        key = self._key(rec.kind, rec.rung, rec.impl)
        with self._lock:
            old = self._records.get(key)
            # a compiled harvest (cost AND memory) never downgrades to a
            # lowered one (cost only) — unless the compiled harvest came
            # back empty (broken backend), in which case any data wins
            if old is not None and old.source == "compiled" \
                    and rec.source == "lowered" \
                    and any(getattr(old, f) is not None
                            for f in _REC_FIELDS):
                return
            self._records[key] = rec
            self._pending.pop(key, None)

    # -- lazy programs --------------------------------------------------

    def record_pending(self, kind: str, rung: int, impl: str, flags: dict,
                       lower_thunk) -> None:
        """Register a lazily-jitted program for later harvest:
        `lower_thunk()` must return an object with cost_analysis()
        (a jax Lowered).  Resolving costs a TRACE (seconds), so it only
        happens via resolve_pending() — never at registration, never at
        scrape."""
        key = self._key(kind, rung, impl)
        with self._lock:
            if key in self._records:
                return
            self._pending[key] = (dict(flags or {}), lower_thunk)

    def resolve_pending(self, budget_s: float | None = None) -> int:
        """Harvest pending programs (ascending rung) until done or the
        budget runs out.  Returns how many resolved; a thunk failing
        records an error entry instead of raising."""
        import time

        t0 = time.perf_counter()
        done = 0
        while True:
            if budget_s is not None and time.perf_counter() - t0 > budget_s:
                break
            with self._lock:
                if not self._pending:
                    break
                key = min(self._pending, key=lambda k: (k[1], k[0], k[2]))
                flags, thunk = self._pending.pop(key)
            kind, rung, impl = key
            try:
                self.record_lowered(kind, rung, impl, flags, thunk())
            except Exception as e:  # noqa: BLE001 — trace failed
                rec = CostRecord(kind, rung, impl, flags, "lowered")
                rec.error = f"lower: {str(e)[:200]}"
                self._install(rec)
            done += 1
        return done

    # -- views ----------------------------------------------------------

    def lookup(self, kind: str, rung: int, impl: str) -> CostRecord | None:
        with self._lock:
            return self._records.get(self._key(kind, rung, impl))

    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    def records(self) -> list[CostRecord]:
        with self._lock:
            return [self._records[k] for k in sorted(self._records)]

    def _samples(self, field: str) -> list:
        out = []
        for rec in self.records():
            v = getattr(rec, field)
            if v is not None:
                out.append(({"kind": rec.kind, "rung": str(rec.rung),
                             "impl": rec.impl}, float(v)))
        return out

    # scrape-time sample helpers (node/metrics.py)
    def flops_samples(self) -> list:
        return self._samples("flops")

    def bytes_samples(self) -> list:
        return self._samples("bytes_accessed")

    def peak_memory_samples(self) -> list:
        return self._samples("peak_memory_bytes")


def _rec_update(rec: CostRecord, parsed: dict) -> None:
    for k, v in parsed.items():
        if v is not None and k in _REC_FIELDS:
            setattr(rec, k, v)


# ---------------------------------------------------------------------------
# Roofline derivation
# ---------------------------------------------------------------------------

def measured_execute_seconds(hist=None) -> dict[str, dict]:
    """Per-rung mean device-execute seconds from the verify pipeline's
    histogram (crypto/async_verify VERIFY_DEVICE_EXECUTE_SECONDS) —
    the MEASURED denominator for achieved FLOPs/s.  Empty when nothing
    has flushed (or the crypto stack is unimportable)."""
    if hist is None:
        try:
            from tendermint_tpu.crypto.async_verify import (
                VERIFY_DEVICE_EXECUTE_SECONDS as hist,
            )
        except Exception:  # noqa: BLE001 — optional deps absent
            return {}
    out = {}
    for key, (count, total) in hist.label_stats().items():
        rung = str(key[0]) if key else ""
        if count and total > 0:
            out[rung] = {"count": int(count), "mean_s": total / count}
    return out


def roofline(rec: CostRecord, *, exec_by_rung: dict | None = None,
             peak: float | None = None) -> dict:
    """Derived metrics for one record; every field absent-tolerant."""
    if exec_by_rung is None:
        exec_by_rung = measured_execute_seconds()
    out: dict = {}
    if rec.flops is not None and rec.bytes_accessed:
        out["arithmetic_intensity"] = rec.flops / rec.bytes_accessed
    if rec.rung:
        if rec.flops is not None:
            out["flops_per_row"] = rec.flops / rec.rung
        if rec.bytes_accessed is not None:
            out["hlo_bytes_per_row"] = rec.bytes_accessed / rec.rung
    tb = row_transfer_bytes(rec.kind)
    if tb is not None:
        out["transfer_bytes_per_row"] = tb
        out["transfer_bytes"] = tb * rec.rung
    m = exec_by_rung.get(str(rec.rung))
    if m and rec.flops is not None:
        out["measured_execute_mean_s"] = round(m["mean_s"], 6)
        out["measured_flushes"] = m["count"]
        achieved = rec.flops / m["mean_s"]
        out["achieved_flops_per_s"] = achieved
        if peak:
            out["flops_utilization"] = achieved / peak
    return out


# ---------------------------------------------------------------------------
# Process-wide instance + snapshot blocks
# ---------------------------------------------------------------------------

COSTS = CostModel()


def reset(enabled: bool | None = None) -> None:
    """Fresh model (tests/benchmarks)."""
    global COSTS
    COSTS = CostModel(enabled=enabled)


def costs_block() -> dict:
    """The `costs` block devmon.device_stats() embeds (and `top`
    renders): harvested records with roofline derivations folded in.
    Cheap — only already-harvested records; pending programs are a
    count, never resolved from a snapshot path."""
    peak = peak_flops_per_s()
    exec_by_rung = measured_execute_seconds()
    records = []
    for rec in COSTS.records():
        d = rec.to_dict()
        d.update(roofline(rec, exec_by_rung=exec_by_rung, peak=peak))
        records.append(d)
    return {"enabled": COSTS.enabled, "peak_flops_per_s": peak,
            "pending": COSTS.pending_count(), "records": records}
