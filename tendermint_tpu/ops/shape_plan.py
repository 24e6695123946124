"""Shape plan + ahead-of-time compilation for the verify pipeline.

The verifier's dominant operational cost at start-up is XLA
compilation: every program (one per rung, impl and mesh size) costs
seconds to minutes cold, and the lazy first-call-compiles design means a
cold node pays that tax at the worst moment: when the first commit
arrives.  This module replaces lazy compilation with an explicit,
serializable story in three parts:

  * **ShapePlan** — the bucket ladder as DATA.  `bucket(n)` (the
    module-level function) is what `ops.ed25519_jax._bucket` delegates
    to; the ACTIVE plan resolves, per call, from
      1. `TM_TPU_RUNGS`       comma-separated rung override,
      2. `TM_TPU_SHAPE_PLAN`  "legacy" | "consolidated" | /path/to.json,
      3. the plan saved next to the persistent compile cache by
         `tendermint-tpu warm` (utils/jaxcache.plan_path()),
      4. the built-in legacy formula ladder (bit-identical to the
         historical `_bucket`, so nothing changes until an operator
         opts in).
    The consolidated plan is the ladder devmon's batch-occupancy
    histograms argue for: fewer, larger rungs (20 programs to 20480 vs
    27), dropping the rungs real runs never fill (16, 32, 320, 640,
    1280, 2560, 5120) while keeping the measured padding bound <= 1.5x
    over the device-eligible sweep n in [65, 20000] and keeping 10240
    (the 10k-commit north star runs at 1.024x padded).
  * **AOT compilation** — `warm_entry`/`warm_rungs`/`warm_plan` build
    executables with `jit(...).lower().compile()` for every
    (kind, rung, impl, flags) in the plan, BEFORE traffic needs them,
    and register them so `ops.ed25519_jax._compiled`
    hands them straight out.  Off XLA-CPU the compiled artifact is also
    written to disk through `jax.experimental.serialize_executable`
    (utils/jaxcache.aot_dir()) for later starts to deserialize; on
    XLA-CPU the compile itself warms the persistent cache — either way
    a restart skips the compile.  (The save/load path has not yet run
    on an accelerator: chip_smoke.py reports it as not exercised.)
  * **Warm-on-start** — `start_background_warm()` is wired into the
    async-verify service, `crypto.batch.start_device_warmup`, and node
    start.  It is a strict opt-in: it does nothing unless a saved plan
    exists (an operator ran `tendermint-tpu warm` at least once) and
    `TM_TPU_AOT` != "0", and it runs on a daemon thread so a slow or
    failing device stalls only the warm thread, never the caller — the
    same degradation philosophy as `crypto.batch._DEVICE_READY`.

Compile provenance: every warm records a devmon compile event with
`source` = "aot" (compiled here, ahead of traffic) or "deserialized"
(loaded from a serialized executable); the lazy path's events classify
as "persistent-cache" or "cold" by the duration heuristic.  A post-warm
run therefore proves itself: `jit_compile_total{source="cold"}` == 0.

Sharded-mesh story (round 10): plans carry a `mesh` dimension — the
mesh sizes (device counts) the warm sweep covers.  `parallel.sharding`
pads buckets to a multiple of the mesh size; every plan rung here is a
multiple of 8, covering the 1/2/4/8 meshes the harness runs, and
`plan_for_warm` folds the CURRENT topology into the implicit plan so
`tendermint-tpu warm` compiles the sharded per-row program for every
(rung, mesh) pair the dispatcher (crypto/mesh_dispatch) will route to.
The sharded jits are warmed by executing them (which populates the
persistent HLO cache) but never serialized: serialized executables are
topology-bound, which is also why `_aot_path` keys artifacts on device
count AND a host-machine signature — loading an executable compiled for
another machine's CPU features is the cpu_aot_loader SIGILL hazard, and
a signature mismatch must mean "recompile", never "deserialize".
"""

from __future__ import annotations

import bisect
import functools
import hashlib
import json
import logging
import os
import threading
import time

_log = logging.getLogger("tendermint_tpu.shape_plan")

PLAN_VERSION = 1

# Device-eligible range the padding bound is measured over (the
# `_bucket` docstring's historical exhaustive sweep).
PADDING_SWEEP = (65, 20_000)
MAX_PADDING = 1.5

# Materialize the legacy formula ladder up to here; beyond it (rare,
# compiles lazily) every plan falls back to the formula.
LADDER_TOP = 20_480

# The consolidated ladder: every step ratio <= 1.5 from the 64 floor up,
# so padding for n in (r_k, r_{k+1}] is r_{k+1}/(r_k+1) <= 1.5 —
# worst case 6144/4097 = 1.4996.  10240 stays (10k commit at 1.024x);
# 8/64 stay (warmup, threshold probes, and the coalescing floor).
CONSOLIDATED_RUNGS = (
    8, 64, 96, 128, 192, 256, 384, 512, 768, 1024, 1536, 2048,
    3072, 4096, 6144, 8192, 10240, 12288, 16384, 20480,
)

DEFAULT_IMPLS = ("int64",)
DEFAULT_KINDS = ("verify",)


def _ladder_bucket(n: int) -> int:
    from tendermint_tpu.ops.ed25519_jax import _ladder_bucket as lb

    return lb(n)


class ShapePlan:
    """An explicit bucket ladder: sorted rungs plus the (impls, kinds)
    the warm path compiles for and the mesh sizes (device counts) the
    sharded warm sweep covers.  Pure data — JSON round-trips; plans
    saved before the mesh dimension existed load as mesh=(1,)."""

    __slots__ = ("name", "rungs", "impls", "kinds", "mesh")

    def __init__(self, rungs, *, impls=DEFAULT_IMPLS, kinds=DEFAULT_KINDS,
                 name: str = "custom", mesh=(1,)):
        rs = sorted({int(r) for r in rungs})
        if not rs or rs[0] < 1:
            raise ValueError(f"shape plan needs positive rungs, got {rungs!r}")
        ms = sorted({int(m) for m in (mesh or (1,))})
        if ms[0] < 1:
            raise ValueError(f"shape plan needs positive mesh sizes, "
                             f"got {mesh!r}")
        self.rungs = tuple(rs)
        self.impls = tuple(impls)
        self.kinds = tuple(kinds)
        self.mesh = tuple(ms)
        self.name = name

    @property
    def top(self) -> int:
        return self.rungs[-1]

    def bucket(self, n: int) -> int:
        """Smallest plan rung >= n; above the plan's top rung the legacy
        formula ladder takes over so arbitrarily large batches still
        bucket (they compile lazily — a plan bounds what warms, not what
        runs)."""
        if n <= self.rungs[0]:
            return self.rungs[0]
        i = bisect.bisect_left(self.rungs, n)
        if i < len(self.rungs):
            return self.rungs[i]
        return max(_ladder_bucket(n), self.top)

    def max_padding(self, lo: int | None = None, hi: int | None = None) -> float:
        """Worst-case bucket(n)/n over the device-eligible sweep
        (exhaustive, like the `_bucket` docstring's [65, 20000])."""
        lo = PADDING_SWEEP[0] if lo is None else lo
        hi = PADDING_SWEEP[1] if hi is None else hi
        worst = 1.0
        for i in range(bisect.bisect_left(self.rungs, lo), len(self.rungs)):
            # per covered interval (prev, rung] the worst n is prev+1
            prev = self.rungs[i - 1] if i else 0
            n = max(lo, prev + 1)
            if n > hi:
                break
            worst = max(worst, self.rungs[i] / n)
        if hi > self.top:
            # formula-ladder tail: the legacy ladder's own bound holds
            n = self.top + 1
            worst = max(worst, _ladder_bucket(n) / n)
        return worst

    def entries(self, kinds=None, impls=None):
        """[(kind, rung, impl)] the single-device warm path compiles."""
        out = []
        for kind in (kinds or self.kinds):
            for impl in (impls or self.impls):
                for rung in self.rungs:
                    out.append((kind, rung, impl))
        return out

    def mesh_entries(self, rungs=None):
        """[(rung, mesh_size)] the SHARDED warm path compiles: one
        sharded per-row program per plan rung per mesh size > 1, skipping
        rungs the mesh does not divide (parallel.sharding pads those up
        to the next device multiple, i.e. a different rung)."""
        out = []
        for m in self.mesh:
            if m <= 1:
                continue
            for rung in (rungs or self.rungs):
                if rung % m == 0:
                    out.append((rung, m))
        return out

    def to_dict(self) -> dict:
        return {"version": PLAN_VERSION, "name": self.name,
                "rungs": list(self.rungs), "impls": list(self.impls),
                "kinds": list(self.kinds), "mesh": list(self.mesh)}

    @classmethod
    def from_dict(cls, doc: dict) -> "ShapePlan":
        if int(doc.get("version", 1)) > PLAN_VERSION:
            raise ValueError(f"shape plan version {doc.get('version')} "
                             f"is newer than this build ({PLAN_VERSION})")
        return cls(doc["rungs"],
                   impls=tuple(doc.get("impls") or DEFAULT_IMPLS),
                   kinds=tuple(doc.get("kinds") or DEFAULT_KINDS),
                   name=str(doc.get("name", "custom")),
                   mesh=tuple(doc.get("mesh") or (1,)))

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=1)

    @classmethod
    def from_json(cls, text: str) -> "ShapePlan":
        return cls.from_dict(json.loads(text))


@functools.lru_cache(maxsize=1)
def _legacy_rungs() -> tuple:
    return tuple(sorted({_ladder_bucket(n) for n in range(1, LADDER_TOP + 1)}))


def legacy_plan() -> ShapePlan:
    """The historical formula ladder as a plan — the default, so
    behavior is bit-identical until an operator installs another plan."""
    return ShapePlan(_legacy_rungs(), name="legacy")


def consolidated_plan(device_stats: dict | None = None) -> ShapePlan:
    """The consolidated ladder, optionally tuned by a devmon
    `device_stats()` snapshot: rungs the workload already fills well
    (>= 0.9 mean occupancy over >= 2 flushes) are exact fits whose
    removal would push those flushes a rung up, so they are kept even
    when the base ladder dropped them."""
    rungs = set(CONSOLIDATED_RUNGS)
    for cell in (device_stats or {}).get("rungs", []):
        try:
            if (cell.get("flushes", 0) >= 2
                    and cell.get("mean_occupancy", 0.0) >= 0.9):
                rungs.add(int(cell["rung"]))
        except (TypeError, ValueError):
            continue
    return ShapePlan(sorted(rungs), name="consolidated")


# ---------------------------------------------------------------------------
# Active-plan resolution (per-call env, never at import — tmlint
# import-time-env is exactly the footgun here)
# ---------------------------------------------------------------------------

_ACTIVE: ShapePlan | None = None
_ACTIVE_LOCK = threading.Lock()


def plan_path() -> str:
    from tendermint_tpu.utils import jaxcache

    return jaxcache.plan_path()


def aot_dir() -> str:
    from tendermint_tpu.utils import jaxcache

    return jaxcache.aot_dir()


def load_plan(path: str) -> ShapePlan:
    with open(path) as fh:
        return ShapePlan.from_json(fh.read())


def save_plan(plan: ShapePlan, path: str | None = None) -> str:
    path = path or plan_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        fh.write(plan.to_json())
    os.replace(tmp, path)
    return path


def _resolve_explicit_plan() -> ShapePlan | None:
    raw = os.environ.get("TM_TPU_RUNGS", "")
    if raw:
        try:
            return ShapePlan([int(x) for x in raw.split(",") if x.strip()],
                             name="env-rungs")
        except ValueError:
            _log.warning("ignoring malformed TM_TPU_RUNGS=%r", raw)
    sel = os.environ.get("TM_TPU_SHAPE_PLAN", "")
    if sel == "legacy":
        return legacy_plan()
    if sel == "consolidated":
        return consolidated_plan()
    if sel:
        try:
            return load_plan(sel)
        except (OSError, ValueError, KeyError) as e:
            _log.warning("ignoring unreadable TM_TPU_SHAPE_PLAN=%r: %s",
                         sel, e)
    saved = plan_path()
    if os.path.exists(saved):
        try:
            return load_plan(saved)
        except (OSError, ValueError, KeyError) as e:
            _log.warning("ignoring unreadable saved shape plan %s: %s",
                         saved, e)
    return None


def _resolve_plan() -> ShapePlan:
    return _resolve_explicit_plan() or legacy_plan()


def active_plan() -> ShapePlan:
    global _ACTIVE
    p = _ACTIVE
    if p is None:
        with _ACTIVE_LOCK:
            if _ACTIVE is None:
                _ACTIVE = _resolve_plan()
                if _ACTIVE.name != "legacy":
                    _log.info("shape plan active: %s (%d rungs, top %d)",
                              _ACTIVE.name, len(_ACTIVE.rungs), _ACTIVE.top)
            p = _ACTIVE
    return p


def reload_plan() -> None:
    """Drop the cached active plan so the next bucket() re-resolves the
    environment/saved file (tests, `warm`, config reload)."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = None


def bucket(n: int) -> int:
    """Smallest compiled bucket >= n under the ACTIVE plan — the
    function `ops.ed25519_jax._bucket` delegates to."""
    return active_plan().bucket(n)


def plan_for_warm(device_stats: dict | None = None) -> ShapePlan:
    """The plan `tendermint-tpu warm` compiles when none is named: an
    explicit env/saved plan wins (warm refreshes its artifacts);
    otherwise the consolidated ladder — warming is the opt-in moment
    where the fewer-larger-rungs tradeoff is taken.

    Round 9: warming is also where the auto-resolved field impl
    (TM_TPU_FIELD_IMPL=auto — packed where the golden check validates
    it, else int64) becomes operational, so the resolved default impl is
    folded into the implicit plan and the AOT sweep compiles exactly the
    programs production dispatch will run.  XLA-CPU resolves to int64:
    the warm grid there is unchanged.

    Round 10: the CURRENT device topology is folded in the same way —
    on a multi-device slice the plan's mesh dimension grows the visible
    device count, so the warm sweep also compiles the sharded per-row
    programs the mesh dispatcher routes large flushes to."""
    explicit = _resolve_explicit_plan()
    if explicit is not None:
        return _fold_mesh(explicit)
    plan = consolidated_plan(device_stats)
    from tendermint_tpu.ops import ed25519_jax as dev

    impl = dev.default_impl()
    if impl not in plan.impls:
        plan = ShapePlan(plan.rungs, impls=(impl,) + plan.impls,
                         kinds=plan.kinds, name=plan.name, mesh=plan.mesh)
    return _fold_mesh(plan)


def _fold_mesh(plan: ShapePlan) -> ShapePlan:
    """Grow a plan's mesh dimension with the visible device count, so a
    warm on a slice covers the dispatcher's sharded route.  A plan that
    already names mesh sizes > 1 is kept as-is (the operator chose)."""
    if plan.mesh != (1,):
        return plan
    try:
        import jax

        n_dev = len(jax.devices())
    except Exception:  # noqa: BLE001 — no backend: single-device plan
        return plan
    if n_dev <= 1:
        return plan
    return ShapePlan(plan.rungs, impls=plan.impls, kinds=plan.kinds,
                     name=plan.name, mesh=(1, n_dev))


# ---------------------------------------------------------------------------
# AOT executable registry
# ---------------------------------------------------------------------------

class AotEntry:
    __slots__ = ("executable", "source", "seconds")

    def __init__(self, executable, source: str, seconds: float = 0.0):
        self.executable = executable
        self.source = source  # "aot" | "deserialized"
        self.seconds = seconds


_REGISTRY: dict[tuple, AotEntry] = {}
_REG_LOCK = threading.Lock()


def _flag_key(flags: dict) -> tuple:
    return tuple(sorted(flags.items()))


def _reg_key(kind: str, rung: int, impl: str, flags: dict) -> tuple:
    return (kind, int(rung), impl) + _flag_key(flags)


def aot_lookup(kind: str, rung: int, impl: str, **flags) -> AotEntry | None:
    """The pre-compiled executable for one jit cache key, or None —
    consulted by ops.ed25519_jax._compiled before it builds a lazy
    jit."""
    with _REG_LOCK:
        return _REGISTRY.get(_reg_key(kind, rung, impl, flags))


def registry_snapshot() -> list[dict]:
    with _REG_LOCK:
        return [{"kind": k[0], "rung": k[1], "impl": k[2],
                 "flags": dict(k[3:]), "source": e.source,
                 "seconds": round(e.seconds, 3)}
                for k, e in sorted(_REGISTRY.items(), key=lambda kv: kv[0][:3])]


def clear_registry() -> None:
    """Tests/benchmarks.  Callers holding a functools-cached _compiled
    proxy keep it; only the NEXT cache build re-consults the registry."""
    with _REG_LOCK:
        _REGISTRY.clear()


def _entry_flags(kind: str, impl: str) -> dict:
    """The trace-time flags a production dispatch would resolve for this
    (kind, impl) right now — the AOT executable must be compiled with
    the SAME flags or the registry key will never match the runtime
    lookup."""
    from tendermint_tpu.ops import ed25519_jax as dev

    return {"donate": dev.donate_rows()}


def abstract_rows(kind: str, rung: int) -> tuple:
    """jax.ShapeDtypeStruct argument shapes for one rung — what
    `.lower()` traces against instead of concrete arrays."""
    import numpy as np

    import jax

    u8row = jax.ShapeDtypeStruct((rung, 32), np.uint8)
    valid = jax.ShapeDtypeStruct((rung,), np.bool_)
    return (u8row, u8row, u8row, u8row, valid)


def _aot_compile(kind: str, rung: int, impl: str, flags: dict):
    """jit(...).lower().compile() for one plan entry; returns
    (executable, wall_seconds).  Built through ed25519_jax._jit_for so
    the call convention (donation included) matches the lazy path
    exactly."""
    from tendermint_tpu.ops import ed25519_jax as dev

    kw = dict(flags)
    donate = kw.pop("donate", None)
    jitted = dev._jit_for(kind, impl, donate=donate, **kw)
    t0 = time.perf_counter()
    compiled = jitted.lower(*abstract_rows(kind, rung)).compile()
    return compiled, time.perf_counter() - t0


# -- serialized executables -------------------------------------------------
#
# Trust model: the aot dir lives next to the persistent compile cache
# (utils/jaxcache — JAX_COMPILATION_CACHE_DIR or inside the checkout,
# never a world-writable /tmp), and deserializing either one executes what the
# directory owner planted; the pickle here adds no new exposure beyond
# what jax's own compile cache already carries.

def _dump_executable(compiled) -> bytes | None:
    """Serialized form of a compiled executable.  None on XLA-CPU, by
    measurement: its JIT'd executables reference process-local symbols
    and deserialize to "Symbols not found" in the next process, so on
    the cpu backend the persistent cache IS the warm story."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    import pickle

    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree),
                        protocol=pickle.HIGHEST_PROTOCOL)


def _load_executable(blob: bytes):
    import pickle

    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = pickle.loads(blob)
    return se.deserialize_and_load(payload, in_tree, out_tree)


@functools.lru_cache(maxsize=1)
def host_signature() -> str:
    """Fingerprint of the machine an AOT artifact was compiled ON:
    platform triple + a hash of the CPU feature flags + the first
    device's kind.  MULTICHIP_r05's tail showed cpu_aot_loader warning
    "Compile machine features ... doesn't match the machine type for
    execution ... could lead to SIGILL" — an executable serialized on a
    machine with wider SIMD must never be deserialized on a narrower
    one.  Folding this signature into the artifact KEY makes a
    cross-machine load structurally impossible: on a different host the
    path simply does not exist, so warm_entry recompiles cleanly."""
    import platform

    parts = [platform.system(), platform.machine(),
             platform.processor() or ""]
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.lower().startswith(("flags", "features")):
                    feats = " ".join(sorted(line.split(":", 1)[1].split()))
                    parts.append(
                        hashlib.sha256(feats.encode()).hexdigest()[:12])
                    break
    except OSError:
        pass
    try:
        import jax

        parts.append(str(jax.devices()[0].device_kind))
    except Exception:  # noqa: BLE001 — no backend: platform triple only
        pass
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def _aot_path(kind: str, rung: int, impl: str, flags: dict) -> str:
    """Artifact path keyed on everything that makes an executable
    non-portable: flags, jax version, backend platform, device count
    (executables are topology-bound), and the host-machine signature
    (CPU features — the SIGILL hazard; see host_signature)."""
    import jax

    sig = hashlib.sha256(repr((
        kind, rung, impl, _flag_key(flags), jax.__version__,
        jax.default_backend(), len(jax.devices()), host_signature(),
    )).encode()).hexdigest()[:16]
    return os.path.join(aot_dir(), f"{kind}_{impl}_r{rung}_{sig}.aotx")


def _harvest_costs(kind: str, rung: int, impl: str, flags: dict,
                   executable) -> None:
    """Read cost_analysis()/memory_analysis() off a just-warmed
    executable into the cost model (utils/costmodel) — the cheapest
    possible harvest: the executable is already compiled, so this is a
    pair of C++ accessor calls, and record_compiled never raises."""
    from tendermint_tpu.utils import costmodel as _cost

    if _cost.COSTS.enabled:
        _cost.COSTS.record_compiled(kind, rung, impl, flags, executable)


# ---------------------------------------------------------------------------
# Warming
# ---------------------------------------------------------------------------

def warm_entry(kind: str, rung: int, impl: str, *, flags: dict | None = None,
               serialize: bool = True, force: bool = False) -> dict:
    """Make one (kind, rung, impl) executable hot: registry hit >
    deserialize from disk > jit().lower().compile() (which also warms
    the persistent cache), optionally serializing fresh compiles to
    disk.  Records a devmon compile event with the true source."""
    from tendermint_tpu.utils import devmon as _devmon

    flags = dict(flags) if flags is not None else _entry_flags(kind, impl)
    key = _reg_key(kind, rung, impl, flags)
    with _REG_LOCK:
        existing = _REGISTRY.get(key)
    report = {"kind": kind, "rung": int(rung), "impl": impl,
              "flags": {k: v for k, v in _flag_key(flags)}}
    if existing is not None and not force:
        report.update(source="registered", seconds=0.0, skipped=True)
        return report

    path = None
    try:
        path = _aot_path(kind, rung, impl, flags)
    except Exception as e:  # noqa: BLE001 — no backend yet: compile decides
        _log.info("aot artifact path unavailable: %s", e)

    if path and os.path.exists(path) and not force:
        try:
            with open(path, "rb") as fh:
                blob = fh.read()
            t0 = time.perf_counter()
            exe = _load_executable(blob)
            dt = time.perf_counter() - t0
            with _REG_LOCK:
                _REGISTRY[key] = AotEntry(exe, "deserialized", dt)
            _devmon.TRACKER.record(kind, rung, impl, _flag_key(flags), dt,
                                   source="deserialized")
            _harvest_costs(kind, rung, impl, flags, exe)
            report.update(source="deserialized", seconds=round(dt, 3),
                          path=path)
            return report
        except Exception as e:  # noqa: BLE001 — stale artifact: recompile
            _log.warning("stale aot artifact %s (%s); recompiling",
                         path, str(e)[:200])

    exe, dt = _aot_compile(kind, rung, impl, flags)
    with _REG_LOCK:
        _REGISTRY[key] = AotEntry(exe, "aot", dt)
    _devmon.TRACKER.record(kind, rung, impl, _flag_key(flags), dt,
                           source="aot")
    _harvest_costs(kind, rung, impl, flags, exe)
    report.update(source="aot", seconds=round(dt, 3))
    if serialize and path:
        blob = _dump_executable(exe)
        if blob is not None:
            try:
                os.makedirs(os.path.dirname(path), exist_ok=True)
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "wb") as fh:
                    fh.write(blob)
                os.replace(tmp, path)
                report.update(serialized=True, path=path,
                              serialized_bytes=len(blob))
            except OSError as e:
                _log.warning("could not write aot artifact %s: %s", path, e)
                report["serialized"] = False
        else:
            report["serialized"] = False  # persistent-cache warming only
    return report


def warm_rungs(*, kinds=DEFAULT_KINDS, rungs, impls=DEFAULT_IMPLS,
               serialize: bool = True) -> list[dict]:
    """Warm a specific (kinds x impls x rungs) grid; one report dict per
    entry, failures recorded per entry instead of aborting the sweep
    (one rung OOMing must not cost the others their warmth)."""
    out = []
    for kind in kinds:
        for impl in impls:
            for rung in rungs:
                try:
                    out.append(warm_entry(kind, rung, impl,
                                          serialize=serialize))
                except Exception as e:  # noqa: BLE001
                    _log.warning("warm %s r%d %s failed: %s",
                                 kind, rung, impl, e)
                    out.append({"kind": kind, "rung": int(rung),
                                "impl": impl, "source": "error",
                                "seconds": 0.0, "error": str(e)[-300:]})
    return out


def warm_mesh_entry(rung: int, m: int) -> dict:
    """Warm the SHARDED per-row program for one (rung, mesh-size) by
    executing it on zero rows through the exact dispatcher call path
    (prepartition + sharded_verify_fn).  Sharded executables are never
    serialized — they are topology-bound, and XLA-CPU cannot serialize
    at all — but the execution compiles through jax's persistent HLO
    cache, which is precisely what a mesh-enabled service start reuses.
    The compile event lands in devmon via sharding's track_jit wrapper."""
    import numpy as np

    report: dict = {"kind": "verify_sharded", "rung": int(rung),
                    "mesh": int(m), "serialized": False}
    t0 = time.perf_counter()
    try:
        from tendermint_tpu.ops import ed25519_jax as dev
        from tendermint_tpu.parallel import sharding as _sh

        report["impl"] = dev.default_impl()
        mesh = _sh.make_mesh(n_devices=m)
        rows = tuple(np.zeros((rung, 32), np.uint8) for _ in range(4)) \
            + (np.zeros((rung,), np.bool_),)
        out = _sh.sharded_verify_fn(mesh)(*_sh.prepartition(mesh, rows))
        np.asarray(out)  # block until the compile/execute completes
        dt = time.perf_counter() - t0
        report.update(
            source=("persistent-cache" if dt < _cold_threshold() else "cold"),
            seconds=round(dt, 3))
    except Exception as e:  # noqa: BLE001 — per-entry failure isolation
        _log.warning("mesh warm r%d x%d failed: %s", rung, m, e)
        report.update(source="error", seconds=round(
            time.perf_counter() - t0, 3), error=str(e)[-300:])
    return report


def _cold_threshold() -> float:
    from tendermint_tpu.utils import devmon as _devmon

    return _devmon._cold_compile_threshold_s()


def warm_plan(plan: ShapePlan, *, kinds=None, impls=None,
              serialize: bool = True, save: bool = True) -> dict:
    """Warm every entry of a plan and (by default) save the plan next to
    the compile cache so restarts — and start_background_warm — pick it
    up.  Returns the full report `tendermint-tpu warm --json` prints.
    Plans with a mesh dimension (round 10) additionally warm the sharded
    per-row program for every (rung, mesh-size) pair, clamped to the
    devices actually visible right now."""
    t0 = time.perf_counter()
    entries = warm_rungs(kinds=kinds or plan.kinds, rungs=plan.rungs,
                         impls=impls or plan.impls, serialize=serialize)
    try:
        import jax

        visible = len(jax.devices())
    except Exception:  # noqa: BLE001 — no backend: skip sharded warm
        visible = 1
    for rung, m in plan.mesh_entries():
        if m <= visible:
            entries.append(warm_mesh_entry(rung, m))
    sources: dict[str, int] = {}
    for e in entries:
        sources[e["source"]] = sources.get(e["source"], 0) + 1
    report = {
        "plan": plan.to_dict(),
        "max_padding": round(plan.max_padding(), 4),
        "entries": entries,
        "sources": sources,
        "errors": sum(1 for e in entries if e.get("error")),
        "seconds_total": round(time.perf_counter() - t0, 3),
        "aot_dir": aot_dir(),
    }
    if save:
        report["plan_path"] = save_plan(plan)
        reload_plan()  # the saved plan is now the active one
    return report


# ---------------------------------------------------------------------------
# Warm-on-start (service / node / device-warmup wiring)
# ---------------------------------------------------------------------------

_BG_LOCK = threading.Lock()
_BG_STARTED = False
_BG_INFLIGHT = False


def aot_enabled() -> bool:
    """TM_TPU_AOT kill switch, resolved per call (default on)."""
    return os.environ.get("TM_TPU_AOT", "1") != "0"


def start_background_warm(reason: str = "", force: bool = False) -> bool:
    """Warm the SAVED plan on a daemon thread (idempotent per process).

    Strict opt-in: no saved plan (the operator never ran
    `tendermint-tpu warm`) or TM_TPU_AOT=0 means no thread, no device
    contact, nothing — so test suites and host-only deployments are
    untouched.  With a saved plan, artifacts deserialize in well under a
    second each and missing entries compile against the (warm)
    persistent cache; either way the first real flush finds its program
    ready instead of paying the compile inline.

    `force=True` bypasses the once-per-process latch — the remediation
    controller's compile-storm self-heal re-warms a LIVE node whose
    cache went stale mid-run.  Overlap is still excluded (one warm
    worker at a time); the controller provides the rate limit."""
    global _BG_STARTED, _BG_INFLIGHT
    if not aot_enabled():
        return False
    try:
        path = plan_path()
    except Exception:  # noqa: BLE001 — no cache dir resolvable
        return False
    if not os.path.exists(path):
        return False
    with _BG_LOCK:
        if _BG_INFLIGHT or (_BG_STARTED and not force):
            return False
        _BG_STARTED = True
        _BG_INFLIGHT = True

    def _bg() -> None:
        global _BG_INFLIGHT
        try:
            plan = load_plan(path)
            rep = warm_plan(plan, serialize=False, save=False)
            _log.info(
                "background AOT warm (%s) done: %d entries in %.1fs %s",
                reason or "start", len(rep["entries"]),
                rep["seconds_total"], rep["sources"])
        except Exception as e:  # noqa: BLE001 — warm is best-effort
            _log.warning("background AOT warm (%s) failed: %s",
                         reason or "start", e)
        finally:
            with _BG_LOCK:
                _BG_INFLIGHT = False

    threading.Thread(target=_bg, daemon=True, name="tm-aot-warm").start()
    return True
