"""Central registry of every TM_TPU_* environment knob.

The tree grew 60+ env knobs across five PR generations, several of them
documented nowhere but the module that reads them.  This registry is the
single source of truth: every knob's name, default, one-line doc and
subsystem live here, the consolidated table in docs/observability.md is
GENERATED from here (``render_table()``; a test diffs the committed doc
block against the renderer), and tmlint's `env-knob-registry` rule fails
the build when a module reads a literal ``TM_TPU_*`` name that is not
registered.

Scope and honesty about limits:
  * the lint rule sees *literal* keys (``os.environ.get("TM_TPU_X")``,
    ``os.environ["TM_TPU_X"]``, ``os.getenv``, ``in os.environ``).
    Reads through a module constant (the ``ENV_FLAG = "TM_TPU_TRACE"``
    idiom) are matched by the constant's literal definition instead —
    the string appears exactly once either way;
  * registration is intentionally cheap (one line) so the rule never
    becomes a reason not to add a knob — it is a reason not to add an
    UNDOCUMENTED knob.

This module must stay import-light (lint imports it).
"""

from __future__ import annotations

import dataclasses
import os


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str        # full TM_TPU_* env var name
    default: str     # default as the reading site interprets "unset"
    doc: str         # one line: what it controls
    subsystem: str   # table grouping key


#: every knob the package reads, grouped by subsystem, alphabetical
#: within the group.  Keep the one-line docs in sync with the module
#: docstrings that explain the full semantics.
KNOBS: tuple[Knob, ...] = (
    # -- crypto / verify path ------------------------------------------
    Knob("TM_TPU_ASYNC_VERIFY", "1",
         "async verify service (coalescing worker); 0 = synchronous", "crypto"),
    Knob("TM_TPU_CPU_THRESHOLD", "auto",
         "batch size below which host ed25519 wins; auto = measured", "crypto"),
    Knob("TM_TPU_CRYPTO_BACKEND", "auto",
         "ed25519 backend: auto/jax/pure", "crypto"),
    Knob("TM_TPU_LINGER_MS", "1.0",
         "verify coalescing window in milliseconds", "crypto"),
    Knob("TM_TPU_VERIFY_CACHE", "65536",
         "verified-signature cache capacity in entries; 0 disables", "crypto"),
    Knob("TM_TPU_MESH", "auto",
         "pod-slice sharded verification: auto/1/0", "crypto"),
    Knob("TM_TPU_MESH_MIN_SHARD", "0",
         "minimum rows per shard before the mesh path engages", "crypto"),
    # -- ops / kernels --------------------------------------------------
    Knob("TM_TPU_AOT", "1",
         "ahead-of-time shape-plan warm compile", "ops"),
    Knob("TM_TPU_DONATE", "auto",
         "XLA buffer donation mode: auto/1/0", "ops"),
    Knob("TM_TPU_FIELD_IMPL", "auto",
         "field arithmetic implementation: auto/int64/packed", "ops"),
    Knob("TM_TPU_RUNGS", "",
         "explicit shape-plan rung ladder (comma ints)", "ops"),
    Knob("TM_TPU_SHAPE_PLAN", "",
         "shape-plan override: off/exact/ladder spec", "ops"),
    # -- gateway --------------------------------------------------------
    Knob("TM_TPU_GATEWAY", "0",
         "crypto gateway service (shared device across processes)", "gateway"),
    Knob("TM_TPU_GATEWAY_CACHE_BYTES", "67108864",
         "gateway response-cache byte budget", "gateway"),
    Knob("TM_TPU_GATEWAY_CACHE_ENTRIES", "4096",
         "gateway response-cache entry cap", "gateway"),
    Knob("TM_TPU_GATEWAY_LINGER_MS", "2.0",
         "gateway coalescer linger window (ms)", "gateway"),
    Knob("TM_TPU_GATEWAY_RETRY_AFTER_MS", "1000",
         "backpressure retry hint returned to shed clients (ms)", "gateway"),
    # -- p2p / consensus / node ----------------------------------------
    Knob("TM_TPU_DIAL_SEED", "",
         "deterministic dial-jitter seed; unset = entropy", "p2p"),
    Knob("TM_TPU_GOSSIP_SEED", "",
         "deterministic gossip rng seed; unset = entropy", "consensus"),
    Knob("TM_TPU_MISBEHAVIORS", "",
         "comma list of injected misbehaviors (testing)", "node"),
    Knob("TM_TPU_FAIL_INDEX", "",
         "deterministic fault-injection index (testing)", "node"),
    Knob("TM_TPU_LOG_FMT", "",
         "log format override; json = structured lines", "node"),
    Knob("TM_TPU_PROFILE", "",
         "CLI cProfile dump path; unset = off", "node"),
    # -- observability sinks -------------------------------------------
    Knob("TM_TPU_DEVSTATS", "1",
         "device stats sink (devmon STATS)", "observability"),
    Knob("TM_TPU_COMPILE_COLD_S", "5.0",
         "devmon compile-storm cold-compile threshold (s)", "observability"),
    Knob("TM_TPU_TRACE", "0",
         "flight-recorder span tracing", "observability"),
    Knob("TM_TPU_TRACE_RING", "4096",
         "trace ring-buffer capacity in spans", "observability"),
    Knob("TM_TPU_TRACE_OUT", "bench_trace.json",
         "bench.py Chrome-trace output path", "observability"),
    Knob("TM_TPU_JOURNAL", "",
         "structured consensus event journal; 1 = journal.jsonl", "observability"),
    Knob("TM_TPU_JOURNAL_LIMIT", "67108864",
         "journal total size bound in bytes", "observability"),
    Knob("TM_TPU_TXLIFE", "1",
         "per-tx lifecycle tracer", "observability"),
    Knob("TM_TPU_COSTMODEL", "1",
         "analytic kernel cost model", "observability"),
    Knob("TM_TPU_PEAK_FLOPS", "",
         "advertised accelerator peak FLOPS override", "observability"),
    # -- health watchdog ------------------------------------------------
    Knob("TM_TPU_HEALTH", "1",
         "health monitor (detectors + sampler thread)", "health"),
    Knob("TM_TPU_HEALTH_INTERVAL_S", "2.0",
         "health sampling cadence (s)", "health"),
    Knob("TM_TPU_HEALTH_STALL_S", "expected block interval",
         "height-stall detector expectation (s)", "health"),
    Knob("TM_TPU_HEALTH_QUEUE_HW", "512",
         "verify-queue saturation high-water mark", "health"),
    Knob("TM_TPU_HEALTH_BUNDLE_MIN_S", "60.0",
         "minimum seconds between forensic bundles", "health"),
    Knob("TM_TPU_HEALTH_BUNDLE_KEEP", "5",
         "forensic bundles kept on disk", "health"),
    # -- remediation ----------------------------------------------------
    Knob("TM_TPU_REMEDIATE", "1",
         "remediation controller (acts on health transitions)", "remediate"),
    Knob("TM_TPU_REMEDIATE_RETUNE", "0",
         "allow batch-threshold retuning remediations", "remediate"),
    Knob("TM_TPU_REMEDIATE_REWARM_MIN_S", "300.0",
         "minimum seconds between device rewarms", "remediate"),
    Knob("TM_TPU_REMEDIATE_RETRY_AFTER_MS", "1000",
         "shed-mode RPC retry hint (ms)", "remediate"),
    Knob("TM_TPU_REMEDIATE_SHED_RPC_BYTES", "4096",
         "shed-mode RPC response byte cap", "remediate"),
    Knob("TM_TPU_REMEDIATE_FLAP_THRESHOLD", "3",
         "ladder flaps before peer eviction", "remediate"),
    Knob("TM_TPU_REMEDIATE_QUARANTINE_S", "30.0",
         "base peer quarantine window (s)", "remediate"),
    Knob("TM_TPU_REMEDIATE_QUARANTINE_CAP_S", "120.0",
         "peer quarantine backoff cap (s)", "remediate"),
    # -- profiler -------------------------------------------------------
    Knob("TM_TPU_PROF", "1",
         "continuous statistical profiler", "profiler"),
    Knob("TM_TPU_PROF_HZ", "19.0",
         "profiler sweep frequency (Hz)", "profiler"),
    Knob("TM_TPU_PROF_WINDOW_S", "10.0",
         "profile aggregation window (s)", "profiler"),
    Knob("TM_TPU_PROF_TRIGGER_MIN_S", "30.0",
         "minimum seconds between trigger-driven captures", "profiler"),
    # -- metric history -------------------------------------------------
    Knob("TM_TPU_HISTORY", "1",
         "embedded metric time-series recorder", "history"),
    Knob("TM_TPU_HISTORY_INTERVAL_S", "10.0",
         "history sampling cadence (s)", "history"),
    Knob("TM_TPU_HISTORY_SEGMENT_POINTS", "360",
         "points per on-disk segment before sealing", "history"),
    Knob("TM_TPU_HISTORY_KEEP", "24",
         "sealed segments kept on disk", "history"),
    Knob("TM_TPU_HISTORY_MAX_SERIES", "4096",
         "series cap per sample (drop + count beyond)", "history"),
    # -- sanitizers (dev/test) -----------------------------------------
    Knob("TM_TPU_LOCKCHECK", "0",
         "runtime lock-order checker (utils/lockcheck)", "sanitizers"),
    Knob("TM_TPU_RACECHECK", "0",
         "lockset race sanitizer (utils/racecheck)", "sanitizers"),
)

#: the set the env-knob-registry lint rule checks literal reads against
KNOWN: frozenset[str] = frozenset(k.name for k in KNOBS)

#: table grouping order (render_table and docs/observability.md)
SUBSYSTEM_ORDER = ("crypto", "ops", "gateway", "p2p", "consensus", "node",
                  "observability", "health", "remediate", "profiler",
                  "history", "sanitizers")


def get(name: str) -> Knob | None:
    for k in KNOBS:
        if k.name == name:
            return k
    return None


def read(name: str, default: str | None = None) -> str | None:
    """os.environ.get through the registry — unknown names are a
    programming error, caught here instead of silently returning the
    fallback."""
    knob = get(name)
    if knob is None:
        raise KeyError(f"unregistered TM_TPU knob: {name}")
    return os.environ.get(name, knob.default if default is None else default)


def render_table() -> str:
    """The consolidated markdown env table embedded in
    docs/observability.md between the knobs:begin/knobs:end markers."""
    lines = ["| Knob | Default | Subsystem | Controls |",
             "| --- | --- | --- | --- |"]
    for sub in SUBSYSTEM_ORDER:
        for k in KNOBS:
            if k.subsystem != sub:
                continue
            default = f"`{k.default}`" if k.default else "unset"
            lines.append(f"| `{k.name}` | {default} | {k.subsystem} "
                         f"| {k.doc} |")
    return "\n".join(lines) + "\n"
