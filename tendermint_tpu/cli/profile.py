"""`tendermint-tpu profile` — per-rung kernel performance profiling.

For every (kind, rung, impl) in the selected shape plan this command
produces the roofline-grade row ROADMAP item 2's MXU round is steered
by:

  * **HLO costs** — FLOPs, bytes accessed (via the cost model's
    lowered-program harvest: a TRACE, never an XLA compile, so cost
    rows for the full plan are affordable where a compile per program
    is not) and, when the program is already in
    the AOT registry, peak device memory from ``memory_analysis()``.
  * **A timed window** — the compiled program executed on synthetic
    full-rung inputs (placed per run, so donated buffers behave exactly
    as in production), reporting wall p50, sigs/s, achieved FLOPs/s and
    FLOPs-utilization against ``costmodel.peak_flops_per_s()``.
    Execution is budgeted (`--budget`, bench.py's shrink-don't-overrun
    idiom): when the budget runs out — on XLA-CPU usually inside the
    first cold compile — the remaining rungs keep their cost rows and
    mark the timed columns ``n/a``.  `--cost-only` skips execution
    entirely.
  * **Profiler capture** — with `--perfetto OUT` the timed windows run
    under ``jax.profiler.trace()`` and the Perfetto-loadable trace is
    written to OUT; an unavailable profiler degrades to a warning,
    never a crash.

Selection flags (`--rungs/--impls/--kinds`) mirror `tendermint-tpu
warm`; the default is the ACTIVE shape plan, so a consolidated-plan
deployment profiles exactly the programs it runs.  With 2+ impls
selected (`--impls int64,packed`) the output ends with a
side-by-side **impl comparison table** — per (kind, rung): HLO
bytes/row, FLOPs, wall p50 and sigs/s per impl plus ratios against the
first impl — so a representation round (ISSUE 12) steers from one
profile invocation instead of a bench re-run.  Exit codes follow the
house contract: 0 = every entry reported, 1 = some entries errored,
2 = usage error.
"""

from __future__ import annotations

import json
import logging
import statistics
import sys
import time

_log = logging.getLogger("tendermint_tpu.profile")


def _now() -> float:
    """Monotonic clock behind one seam so the budget logic is testable
    without patching the stdlib time module process-wide."""
    return time.monotonic()


# ---------------------------------------------------------------------------
# Harvest + timed window (module-level so tests can stub them)
# ---------------------------------------------------------------------------

def backend_info() -> dict:
    """Platform/device summary, best-effort (jax may be unusable)."""
    try:
        import jax

        devs = jax.devices()
        return {"backend": devs[0].platform, "devices": len(devs),
                "device_kind": str(getattr(devs[0], "device_kind", ""))}
    except Exception as e:  # noqa: BLE001 — profile still reports costs
        return {"backend": "unavailable", "error": str(e)[-200:]}


def harvest_entry(kind: str, rung: int, impl: str) -> dict:
    """Cost-analysis row for one program: an existing costmodel record
    (AOT harvest) wins; otherwise lower the program (trace only) and
    harvest the lowering.  Returns the record as a dict; raises only on
    a failed trace (the caller contains it per entry)."""
    from tendermint_tpu.ops import ed25519_jax as dev
    from tendermint_tpu.ops import shape_plan
    from tendermint_tpu.utils import costmodel

    rec = costmodel.COSTS.lookup(kind, rung, impl)
    if rec is not None and rec.flops is not None:
        return rec.to_dict()
    flags = shape_plan._entry_flags(kind, impl)
    kw = dict(flags)
    donate = kw.pop("donate", None)
    jitted = dev._jit_for(kind, impl, donate=donate, **kw)
    t0 = time.perf_counter()
    lowered = jitted.lower(*shape_plan.abstract_rows(kind, rung))
    rec = costmodel.COSTS.record_lowered(kind, rung, impl, flags, lowered)
    out = rec.to_dict()
    out["harvest_s"] = round(time.perf_counter() - t0, 3)
    return out


def _synth_rows(kind: str, rung: int):
    """Full-rung synthetic inputs matching shape_plan.abstract_rows —
    zero rows with every valid bit set, so the kernel does the complete
    per-row work (the math is branch-free; verdicts are ignored)."""
    import numpy as np

    u8 = np.zeros((rung, 32), dtype=np.uint8)
    valid = np.ones(rung, dtype=bool)
    return (u8, u8.copy(), u8.copy(), u8.copy(), valid)


def timed_window(kind: str, rung: int, impl: str, *, runs: int,
                 deadline: float) -> dict:
    """Execute one program `runs` times on synthetic inputs: inputs are
    re-placed per run (donation deletes consumed buffers) and each run
    times enqueue→verdict-readback — the same device-execute semantics
    the flush sites measure.  The first call (warm) is timed separately:
    on a cold cache it IS the compile."""
    import numpy as np

    import jax

    from tendermint_tpu.ops import ed25519_jax as dev

    if kind != "verify":
        raise ValueError(f"unknown program kind {kind!r}")
    fn = dev._compiled(rung, impl)
    rows = _synth_rows(kind, rung)

    def _place():
        return [jax.device_put(r) for r in rows]

    t0 = time.perf_counter()
    np.asarray(fn(*_place()))
    warm_s = time.perf_counter() - t0

    wall = []
    for _ in range(max(1, runs)):
        if _now() > deadline:
            break
        inputs = _place()
        t0 = time.perf_counter()
        out = fn(*inputs)
        np.asarray(out)
        wall.append(time.perf_counter() - t0)
    res = {"warm_s": round(warm_s, 4), "runs": len(wall)}
    if wall:
        p50 = statistics.median(wall)
        res["wall_p50_ms"] = round(p50 * 1e3, 3)
        res["sigs_per_sec"] = round(rung / p50, 1)
    return res


class _ProfilerCapture:
    """Context manager around jax.profiler.trace → one Perfetto trace
    file; every failure mode degrades to an `errors` entry."""

    def __init__(self, out_path: str, errors: list):
        self.out = out_path
        self.errors = errors
        self._dir = None

    def __enter__(self):
        if not self.out:
            return self
        try:
            import tempfile

            import jax

            self._dir = tempfile.mkdtemp(prefix="tmtpu_profile_")
            jax.profiler.start_trace(self._dir, create_perfetto_trace=True)
        except Exception as e:  # noqa: BLE001 — profiler optional
            self.errors.append(f"profiler unavailable: {str(e)[-200:]}")
            self._dir = None
        return self

    def __exit__(self, *exc):
        if self._dir is None:
            return False
        try:
            import glob
            import os
            import shutil

            import jax

            jax.profiler.stop_trace()
            hits = sorted(glob.glob(
                os.path.join(self._dir, "**", "*.perfetto-trace*"),
                recursive=True))
            if hits:
                shutil.copyfile(hits[-1], self.out)
            else:
                self.errors.append("profiler produced no perfetto trace")
        except Exception as e:  # noqa: BLE001
            self.errors.append(f"profiler export failed: {str(e)[-200:]}")
        return False


# ---------------------------------------------------------------------------
# The command
# ---------------------------------------------------------------------------

def _resolve_plan(rungs: str):
    from tendermint_tpu.ops import shape_plan

    if rungs:
        return shape_plan.ShapePlan(
            [int(x) for x in rungs.split(",") if x.strip()],
            name="cli-rungs")
    return shape_plan.active_plan()


def _fmt(v, fmt="{:.3g}"):
    return fmt.format(v) if v is not None else "n/a"


def run_profile(*, rungs: str = "", impls: str = "", kinds: str = "",
                runs: int = 3, budget: float = 120.0,
                cost_only: bool = False, as_json: bool = False,
                perfetto: str = "") -> int:
    from tendermint_tpu.utils import costmodel

    try:
        plan = _resolve_plan(rungs)
    except (ValueError, OSError) as e:
        print(f"could not resolve a shape plan: {e}", file=sys.stderr)
        return 2
    impl_sel = tuple(x.strip() for x in impls.split(",") if x.strip()) or None
    kind_sel = tuple(x.strip() for x in kinds.split(",") if x.strip()) or None
    entries = plan.entries(kinds=kind_sel, impls=impl_sel)

    try:
        import jax

        from tendermint_tpu.utils import jaxcache

        jaxcache.enable(jax)
    except Exception as e:  # noqa: BLE001 — cost rows still possible
        _log.info("jax cache setup skipped: %s", e)

    errors: list[str] = []
    deadline = _now() + max(0.0, budget)
    run_windows = not cost_only and budget > 0
    peak = costmodel.peak_flops_per_s()
    exec_hist = costmodel.measured_execute_seconds()
    rows = []
    with _ProfilerCapture(perfetto if run_windows else "", errors):
        for kind, rung, impl in entries:
            row = {"kind": kind, "rung": rung, "impl": impl}
            try:
                row.update(harvest_entry(kind, rung, impl))
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                row["error"] = f"harvest: {str(e)[-200:]}"
            if run_windows:
                if _now() > deadline:
                    row["timed"] = "skipped: budget"
                else:
                    try:
                        row.update(timed_window(kind, rung, impl, runs=runs,
                                                deadline=deadline))
                    except Exception as e:  # noqa: BLE001
                        row["timed_error"] = str(e)[-200:]
            rows.append(row)

    # fold in roofline derivations (post-run, so this process's own
    # flush measurements — if any — participate)
    exec_hist = costmodel.measured_execute_seconds() or exec_hist
    occ = _live_occupancy()
    for row in rows:
        row["occupancy"] = occ.get((row["kind"], row["rung"]))
        rec = costmodel.COSTS.lookup(row["kind"], row["rung"], row["impl"])
        if rec is not None:
            row.update(costmodel.roofline(rec, exec_by_rung=exec_hist,
                                          peak=peak))
        # direct-timing utilization: the profile's own window is the
        # freshest measurement when the live histogram has nothing
        if row.get("flops") is not None and row.get("wall_p50_ms"):
            achieved = row["flops"] / (row["wall_p50_ms"] / 1e3)
            row["achieved_flops_per_s"] = achieved
            if peak:
                row["flops_utilization"] = achieved / peak

    comparison = impl_comparison(rows)
    report = {
        "plan": plan.to_dict(),
        "peak_flops_per_s": peak,
        "budget_s": budget,
        "cost_only": not run_windows,
        "entries": rows,
        "impl_comparison": comparison,
        "errors": errors,
    }
    report.update(backend_info())
    # an unknown peak is SAID, not left as a silent null: the util
    # column is empty because this device kind has no entry in
    # costmodel._PEAK_FLOPS_BY_KIND (and TM_TPU_PEAK_FLOPS is unset)
    peak_txt = (f"{_fmt(peak)} FLOP/s" if peak is not None else
                f"unknown device {report.get('device_kind', '')!r}")
    report["peak_source"] = "known" if peak is not None else peak_txt
    failed = sum(1 for r in rows if r.get("error"))

    if as_json:
        print(json.dumps(report))
        return 1 if failed else 0

    print(f"profile: plan {plan.name!r} ({len(rows)} programs) "
          f"backend={report.get('backend')} "
          f"peak={peak_txt} budget={budget}s")
    hdr = (f"{'kind':>8} {'rung':>6} {'impl':>6} {'flops':>10} "
           f"{'bytes':>10} {'AI':>7} {'B/row':>9} {'wall p50':>10} "
           f"{'sigs/s':>10} {'util':>7} {'occ':>6}")
    print(hdr)
    for r in rows:
        if r.get("error"):
            print(f"{r['kind']:>8} {r['rung']:>6} {r['impl']:>6} "
                  f"ERROR: {r['error']}")
            continue
        print(
            f"{r['kind']:>8} {r['rung']:>6} {r['impl']:>6} "
            f"{_fmt(r.get('flops')):>10} "
            f"{_fmt(r.get('bytes_accessed')):>10} "
            f"{_fmt(r.get('arithmetic_intensity'), '{:.2f}'):>7} "
            f"{_fmt(r.get('hlo_bytes_per_row')):>9} "
            f"{_fmt(r.get('wall_p50_ms'), '{:.2f}ms'):>10} "
            f"{_fmt(r.get('sigs_per_sec'), '{:.0f}'):>10} "
            f"{_fmt(r.get('flops_utilization'), '{:.2%}'):>7} "
            f"{_fmt(r.get('occupancy'), '{:.2f}'):>6}")
    for line in render_impl_comparison(comparison):
        print(line)
    for e in errors:
        print(f"! {e}", file=sys.stderr)
    return 1 if failed else 0


def impl_comparison(rows: list) -> list:
    """Side-by-side per-(kind, rung) impl comparison — present only when
    2+ impls produced rows for the same program shape.  The baseline is
    the first impl in selection order; every other impl carries
    bytes/FLOPs ratios and a sigs/s speedup against it, so a round can
    steer the representation (ISSUE 12) from one `profile --impls`
    invocation instead of re-running bench."""
    by: dict = {}
    order: list = []
    for r in rows:
        if r.get("error"):
            continue
        by.setdefault((r["kind"], r["rung"]), {})[r["impl"]] = r
        if r["impl"] not in order:
            order.append(r["impl"])
    if len(order) < 2:
        return []
    out = []
    for (kind, rung), impls in sorted(by.items()):
        if len(impls) < 2:
            continue
        base = impls.get(order[0])
        row = {"kind": kind, "rung": rung, "baseline": order[0], "impls": {}}
        for impl in order:
            r = impls.get(impl)
            if r is None:
                continue
            cell = {
                "hlo_bytes_per_row": r.get("hlo_bytes_per_row"),
                "flops": r.get("flops"),
                "wall_p50_ms": r.get("wall_p50_ms"),
                "sigs_per_sec": r.get("sigs_per_sec"),
            }
            if base is not None and impl != order[0]:
                b, v = base.get("hlo_bytes_per_row"), cell["hlo_bytes_per_row"]
                if b and v:
                    cell["bytes_ratio"] = round(v / b, 3)
                b, v = base.get("flops"), cell["flops"]
                if b and v:
                    cell["flops_ratio"] = round(v / b, 3)
                b, v = base.get("sigs_per_sec"), cell["sigs_per_sec"]
                if b and v:
                    cell["speedup"] = round(v / b, 3)
            row["impls"][impl] = cell
        out.append(row)
    return out


def render_impl_comparison(comparison: list) -> list[str]:
    """Text table for the side-by-side block (one line per impl per
    program shape; ratio columns are vs the baseline impl)."""
    if not comparison:
        return []
    base = comparison[0]["baseline"]
    lines = [f"impl comparison (baseline {base}):",
             (f"{'kind':>8} {'rung':>6} {'impl':>6} {'B/row':>9} "
              f"{'flops':>10} {'wall p50':>10} {'sigs/s':>10} "
              f"{'B/row x':>8} {'sigs/s x':>9}")]
    for row in comparison:
        for impl, cell in row["impls"].items():
            lines.append(
                f"{row['kind']:>8} {row['rung']:>6} {impl:>6} "
                f"{_fmt(cell.get('hlo_bytes_per_row')):>9} "
                f"{_fmt(cell.get('flops')):>10} "
                f"{_fmt(cell.get('wall_p50_ms'), '{:.2f}ms'):>10} "
                f"{_fmt(cell.get('sigs_per_sec'), '{:.0f}'):>10} "
                f"{_fmt(cell.get('bytes_ratio'), '{:.2f}x'):>8} "
                f"{_fmt(cell.get('speedup'), '{:.2f}x'):>9}")
    return lines


def _live_occupancy() -> dict:
    """(kind, rung) -> mean occupancy from this process's devmon
    accounting (blank for rungs production traffic never flushed)."""
    try:
        from tendermint_tpu.utils import devmon

        return {(c["kind"], c["rung"]): c["mean_occupancy"]
                for c in devmon.STATS.snapshot()["rungs"]}
    except Exception:  # noqa: BLE001
        return {}
