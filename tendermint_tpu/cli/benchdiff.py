"""`tendermint-tpu benchdiff A.json B.json` — BENCH artifact regression
diffing.

The r04→r05 regression (38,710 → 36,877 sigs/s, -4.7%) shipped unflagged
because nothing compares BENCH artifacts round to round — and r05's
watchdog overrun silently DROPPED the rlc/commit-latency stages, which
no one noticed either.  This module makes both failure modes loud:

  * **Normalization** — the checked-in artifacts come in three shapes:
    the driver wrapper ``{cmd, rc, tail, parsed: {...}}`` (``parsed`` is
    None when the bench crashed before emitting, e.g. r01), the flat
    bench.py JSON line itself, and the BENCH_BASELINE ``results`` list.
    ``normalize()`` maps all of them to one flat metric dict.
  * **Direction-aware classification** — every shared numeric key is
    classed by name (throughput/ratio: higher is better; latency/timing
    and defect counts: lower is better; booleans: False is worse;
    everything else informational), each class carrying a default
    relative threshold.  A ``--thresholds`` file (TOML via the config
    loader's tomllib/tomli fallback, or JSON) overrides per metric or
    per class.
  * **Verdict + exit code** — regressions past threshold exit 1 (the
    0/1/2 contract every subcommand uses); metrics present in A but
    missing from B — the lost-tail-stages case — are reported in
    ``missing_in_b`` and fail only under ``--fail-on-missing`` (key
    renames between rounds must not wedge CI by default).

bench.py runs this as its final stage against the newest prior
``BENCH_r*.json`` and embeds the verdict in the artifact it emits.
"""

from __future__ import annotations

import json
import os
import re
import sys

# Default relative thresholds per metric class.  "throughput" is 3%, not
# 5%: the motivating r04→r05 headline drop is -4.7%, i.e. a ≥5% gate
# would have let the exact regression this tool exists for pass again.
DEFAULT_THRESHOLDS = {
    "throughput": 0.03,
    "ratio": 0.03,
    "latency": 0.10,
    "timing": 0.25,
    "count": 0.25,
    "boolean": 0.0,
    # per-row HLO resource costs (round 9): deterministic functions of
    # the compiled representation, so even a small rise means the
    # program's shape actually regressed — tighter than latency
    "resource": 0.05,
}

# Keys that describe the run rather than measure it.
META_KEYS = {
    "metric", "unit", "backend", "n", "stage", "error", "elapsed_s",
    "baseline_sampling", "production_path", "field_impl", "cmd", "rc",
    "tail", "note", "async_streams",
    "async_stream_rounds", "simnet_nodes", "simnet_validator_slots",
    "benchdiff_base", "benchdiff_regressions", "benchdiff_missing",
    "benchdiff_ok", "shootout_rung", "shootout_n", "shootout_runs",
    "gateway_clients", "fleet_nodes",
    "simnet_virtual_nodes", "simnet_virtual_slots",
    "simnet_virtual_heights",
    # the device is run context, not a measurement: a different device
    # kind or count between rounds must read as context, not regression
    "device_kind", "n_devices",
    # sampling rate is run context: comparing a 19 Hz round against a
    # 97 Hz round must not read the rate change itself as a regression
    "prof_hz",
    # history cadence is run context for the same reason: a different
    # TM_TPU_HISTORY_INTERVAL_S changes bytes/hour by construction
    "history_interval_s",
}

# Ordered (pattern, class, direction) — first match wins.  direction
# "higher" means a DROP is the regression; "lower" means a RISE is.
_CLASS_RULES = (
    (re.compile(r"(_sigs_per_sec|_per_sec|_per_s|_per_min|_blocks_per_s"
                r"|_speedup|heights_per_min)$"), "throughput", "higher"),
    # efficiency ratios where higher is better: the gateway's
    # cross-client verify dedup and cache hit ratios, batch occupancy
    (re.compile(r"_ratio$"), "ratio", "higher"),
    # fleet-scope serving fraction (fleet-scrape stage / SLO layer):
    # a drop means nodes stopped answering their RPC — same class and
    # direction as the ratios above, named per the SLO vocabulary
    (re.compile(r"_availability$"), "ratio", "higher"),
    (re.compile(r"^(value|vs_baseline)$"), "throughput", "higher"),
    # virtual-time simnet (simnet-virtual stage): simulated seconds per
    # wall second — the whole point of the discrete-event scheduler, so
    # a drop is a straight throughput regression
    (re.compile(r"_time_compression$"), "throughput", "higher"),
    (re.compile(r"(_ok|_within_budget|_deterministic)$"),
     "boolean", "higher"),
    (re.compile(r"(_p50_ms|_ms)$"), "latency", "lower"),
    (re.compile(r"(_bytes_per_row|_flops_per_row|_bytes_per_hour)$"),
     "resource", "lower"),
    (re.compile(r"(_ns_per_event|_us_per_event|_ns_per_flush"
                r"|_us_per_flush|_ns_per_stamp|_us_per_stamp"
                r"|_ns_per_sample|_us_per_sample"
                r"|_ns_per_attr|_us_per_attr"
                r"|_ns_per_transition|_us_per_transition)$"),
     "latency", "lower"),
    (re.compile(r"(_seconds|_s)$"), "timing", "lower"),
    (re.compile(r"(recompiles|_findings|frames_dropped"
                r"|padding_rows_total|wal_replays|_violations"
                r"|_soak_criticals)$"),
     "count", "lower"),
)


def classify(key: str) -> tuple[str | None, str | None]:
    """(class, direction) for a metric key; (None, None) means
    informational — compared and reported but never a verdict."""
    for pat, cls, direction in _CLASS_RULES:
        if pat.search(key):
            return cls, direction
    return None, None


# ---------------------------------------------------------------------------
# Artifact loading / normalization
# ---------------------------------------------------------------------------

def load_artifact(path: str) -> dict:
    with open(path) as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: artifact root is not a JSON object")
    return doc


def normalize(doc: dict) -> tuple[dict, dict]:
    """(metrics, meta) from any checked-in artifact shape.  A wrapper
    with ``parsed: null`` (the bench crashed pre-emit) normalizes to an
    empty metric dict with the wrapper's rc/tail kept as meta."""
    if "parsed" in doc:
        meta = {k: doc.get(k) for k in ("cmd", "rc", "n") if k in doc}
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            return dict(parsed), meta
        meta["parse_failed"] = True
        return {}, meta
    if isinstance(doc.get("results"), list):
        metrics = {}
        for entry in doc["results"]:
            if isinstance(entry, dict) and "metric" in entry:
                metrics[str(entry["metric"])] = entry.get("value")
        return metrics, {"shape": "results-list"}
    return dict(doc), {}


def _numeric(v) -> float | None:
    if isinstance(v, bool):
        return 1.0 if v else 0.0
    if isinstance(v, (int, float)):
        return float(v)
    return None


# ---------------------------------------------------------------------------
# Thresholds
# ---------------------------------------------------------------------------

def load_thresholds(path: str) -> dict:
    """``{"thresholds": {metric: rel}, "defaults": {class: rel}}`` from
    a TOML or JSON file.  TOML goes through the tomllib→tomli fallback
    (config/config.py idiom); on py3.10 without tomli, use JSON."""
    if path.endswith(".json"):
        with open(path) as fh:
            doc = json.load(fh)
    else:
        try:
            import tomllib
        except ImportError:
            try:
                import tomli as tomllib
            except ImportError as e:
                raise ValueError(
                    "reading a TOML thresholds file requires tomllib "
                    "(Python >= 3.11) or the tomli backport; neither is "
                    "installed — use a .json thresholds file") from e
        with open(path, "rb") as fh:
            doc = tomllib.load(fh)
    out = {"thresholds": {}, "defaults": {}}
    for section in ("thresholds", "defaults"):
        sec = doc.get(section, {})
        if not isinstance(sec, dict):
            raise ValueError(f"[{section}] must be a table of metric = rel")
        for k, v in sec.items():
            out[section][str(k)] = float(v)
    return out


def _threshold_for(key: str, cls: str | None, overrides: dict) -> float:
    if key in overrides.get("thresholds", {}):
        return overrides["thresholds"][key]
    if cls is not None and cls in overrides.get("defaults", {}):
        return overrides["defaults"][cls]
    return DEFAULT_THRESHOLDS.get(cls, 0.0)


# ---------------------------------------------------------------------------
# The diff
# ---------------------------------------------------------------------------

def diff(a: dict, b: dict, thresholds: dict | None = None) -> dict:
    """Stage-by-stage comparison of two normalized metric dicts.
    Returns rows (shared numeric keys), missing_in_b / new_in_b key
    lists, and the regression verdict."""
    overrides = thresholds or {}
    rows = []
    for key in sorted(set(a) & set(b)):
        if key in META_KEYS:
            continue
        av, bv = _numeric(a[key]), _numeric(b[key])
        if av is None or bv is None:
            continue
        cls, direction = classify(key)
        thr = _threshold_for(key, cls, overrides)
        if av == 0.0:
            rel = 0.0 if bv == 0.0 else float("inf") * (1 if bv > 0 else -1)
        else:
            rel = (bv - av) / abs(av)
        status = "info"
        if direction is not None:
            # "worse" is a drop for higher-better, a rise for lower-better
            worse = -rel if direction == "higher" else rel
            if worse > thr:
                status = "regression"
            elif worse < -thr:
                status = "improvement"
            else:
                status = "ok"
        rows.append({"key": key, "class": cls, "direction": direction,
                     "a": av, "b": bv,
                     "rel_change": round(rel, 6) if rel == rel
                     and abs(rel) != float("inf") else rel,
                     "threshold": thr, "status": status})
    tracked = {k for k in a if k not in META_KEYS
               and _numeric(a[k]) is not None and classify(k)[1] is not None}
    missing = sorted(tracked - set(b))
    new = sorted(k for k in b if k not in META_KEYS and k not in a
                 and _numeric(b[k]) is not None)
    regressions = [r["key"] for r in rows if r["status"] == "regression"]
    return {
        "rows": rows,
        "missing_in_b": missing,
        "new_in_b": new,
        "regressions": regressions,
        "ok": not regressions,
    }


def latest_artifact(dirpath: str, pattern: str = r"BENCH_r(\d+)\.json$"
                    ) -> str | None:
    """Newest checked-in round artifact (highest round number) — the
    auto-diff base for bench.py's final stage."""
    best, best_n = None, -1
    try:
        names = os.listdir(dirpath)
    except OSError:
        return None
    rx = re.compile(pattern)
    for name in names:
        m = rx.match(name)
        if m and int(m.group(1)) > best_n:
            best_n = int(m.group(1))
            best = os.path.join(dirpath, name)
    return best


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _fmt_rel(rel: float) -> str:
    if rel != rel or abs(rel) == float("inf"):
        return "inf" if rel > 0 else "-inf"
    return f"{100 * rel:+.1f}%"


def render_text(report: dict, a_name: str, b_name: str) -> str:
    lines = [f"benchdiff {a_name} -> {b_name}"]
    order = {"regression": 0, "improvement": 1, "ok": 2, "info": 3}
    for r in sorted(report["rows"],
                    key=lambda r: (order[r["status"]], r["key"])):
        mark = {"regression": "!!", "improvement": "++",
                "ok": "  ", "info": " ."}[r["status"]]
        thr = (f" (thr {100 * r['threshold']:.0f}%)"
               if r["status"] in ("regression", "improvement") else "")
        lines.append(
            f" {mark} {r['key']:<40} {r['a']:>12.6g} -> {r['b']:>12.6g}  "
            f"{_fmt_rel(r['rel_change']):>8} {r['status']}{thr}")
    if report["missing_in_b"]:
        lines.append(" !! missing in B (stage lost?): "
                     + ", ".join(report["missing_in_b"]))
    if report["new_in_b"]:
        lines.append(" ++ new in B: " + ", ".join(report["new_in_b"]))
    lines.append(
        f"verdict: {'OK' if report['ok'] else 'REGRESSION'} "
        f"({len(report['regressions'])} regression(s), "
        f"{len(report['missing_in_b'])} missing)")
    return "\n".join(lines)


def run_cli(a_path: str, b_path: str, *, thresholds_path: str = "",
            as_json: bool = False, fail_on_missing: bool = False) -> int:
    try:
        a_metrics, a_meta = normalize(load_artifact(a_path))
        b_metrics, b_meta = normalize(load_artifact(b_path))
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"benchdiff: cannot load artifact: {e}", file=sys.stderr)
        return 2
    overrides = None
    if thresholds_path:
        try:
            overrides = load_thresholds(thresholds_path)
        except (OSError, ValueError, TypeError) as e:
            print(f"benchdiff: bad thresholds file: {e}", file=sys.stderr)
            return 2
    report = diff(a_metrics, b_metrics, thresholds=overrides)
    report["a"] = {"path": a_path, **a_meta}
    report["b"] = {"path": b_path, **b_meta}
    if as_json:
        print(json.dumps(report))
    else:
        print(render_text(report, os.path.basename(a_path),
                          os.path.basename(b_path)))
    failed = bool(report["regressions"]) or (
        fail_on_missing and report["missing_in_b"])
    return 1 if failed else 0
