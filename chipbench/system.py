"""The system under test, started as `Node.start()` starts it, and the
counters a device claim rests on.  The only module of the harness that
reaches into the program."""

from __future__ import annotations

import hashlib
import os
import time

READY_WAIT_S = 1100.0


class NoChip(Exception):
    pass


class SetupFailed(Exception):
    pass


def find_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it.  A measured run needs `chips` TPU
    chips; a rehearsal needs the CPU and says so in its result."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    want = "cpu" if rehearse else "tpu"
    if device["platform"] != want or (not rehearse and len(devs) < chips):
        raise NoChip(f"need {chips} x platform {want!r}; jax.devices() "
                     f"returned {[str(d) for d in devs]}")
    if not rehearse:
        device["count"] = chips
    return device


def start(trace_on: bool) -> dict:
    """Compile cache, native library, verifier and service, in the order
    a node uses; returns what was found.  Runs the node's defaults: the
    only environment set is what keeps a left-over plan or AOT artifact
    of another session from changing what is compiled."""
    import jax

    os.environ["TM_TPU_SHAPE_PLAN"] = "legacy"
    os.environ["TM_TPU_AOT"] = "0"
    os.environ.pop("TM_TPU_RUNGS", None)

    from tendermint_tpu.crypto import async_verify as av
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.ops import shape_plan
    from tendermint_tpu.utils import host_prep, jaxcache, native_loader, trace

    cache = jaxcache.enable(jax)
    host_prep.load_lib()
    trace.set_ring_size(1 << 18)
    trace.set_enabled(trace_on)   # `tracing(on)` changes it between windows
    bv = cbatch.new_batch_verifier()
    if not isinstance(bv, cbatch.JAXBatchVerifier):
        raise SetupFailed(f"batch verifier is {type(bv).__name__}, not the jax backend")
    svc = av.get_service()
    status = native_loader.build_report().get("libedhost.so")
    if status not in ("loaded", "built", "rebuilt"):
        raise SetupFailed(f"native host-prep library: {status}")
    if shape_plan.active_plan().name != "legacy":
        raise SetupFailed(f"shape plan {shape_plan.active_plan().name!r} active")
    return {"compile_cache": cache, "cache_capacity": svc.cache.maxsize,
            "linger_ms": svc.linger_s * 1e3}


def wait_ready(seed: int) -> dict:
    """The first >= 64-signature flush a node sees starts the threshold
    measurement (and with it the golden check and the readiness program);
    wait for the device, failing WITH what prevented it."""
    from cryptography.hazmat.primitives import serialization as ser
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    from tendermint_tpu.crypto import async_verify as av
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.ops import ed25519_jax as dev

    items = []
    for i in range(64):
        key = Ed25519PrivateKey.from_private_bytes(
            hashlib.sha256(b"%d|ready|%d" % (seed, i)).digest())
        msg = b"ready-%d-%d" % (seed, i)
        items.append((key.public_key().public_bytes(
            ser.Encoding.Raw, ser.PublicFormat.Raw), msg, key.sign(msg)))
    if not all(av.verify_many(items)):
        raise SetupFailed("the first 64-signature flush returned a false verdict")
    t0 = time.monotonic()
    while not cbatch.device_ready() and time.monotonic() - t0 < READY_WAIT_S:
        diag = cbatch.threshold_diagnostics()
        if "error" in diag or "warmup_error" in diag:
            break
        time.sleep(0.05)
    diag = cbatch.threshold_diagnostics()
    if not cbatch.device_ready():
        err = diag.get("error") or diag.get("warmup_error")
        raise SetupFailed(
            f"device not ready: {err['type']}: {err['message']}\n{err['traceback']}"
            if err else f"device not ready after {READY_WAIT_S:.0f}s: {diag}")
    return {"impl": dev.default_impl(), "goldens": dev.optin_report(),
            "threshold": {k: diag.get(k) for k in (
                "measured", "device_rtt_ms", "host_us_per_sig", "threshold",
                "reason", "platform", "device_kind")}}


def tracing(on: bool) -> None:
    """The program's spans on or off (one branch a site when off)."""
    from tendermint_tpu.utils import trace

    trace.set_enabled(on)


def counters() -> dict:
    """Read together: `device_batches` counts enqueues, so alone it
    proves nothing; `resolved_on_device` is the path="device" count of
    verify_e2e_seconds, the signatures whose verdict the chip gave."""
    from tendermint_tpu.crypto import async_verify as av
    from tendermint_tpu.utils import devmon

    st = av.service_stats()
    on_device = av.VERIFY_E2E_SECONDS.label_stats().get(("device",), (0, 0))
    dm = devmon.STATS.snapshot()
    return {
        "submitted": st["submitted"], "flushes": st["flushes"],
        "host_flushes": st["host_flushes"],
        "device_batches": st["device_batches"],
        "device_errors": st["device_errors"],
        "cache_hits": st["cache_hits"],
        "resolved_on_device": on_device[0],
        "rows_requested": dm["rows_requested_total"],
        "rows_padded": dm["rows_padded_total"],
        "hist": {
            "queue_wait": av.VERIFY_QUEUE_WAIT_SECONDS.label_stats().get((), (0, 0.0)),
            "host_prep": av.VERIFY_HOST_PREP_SECONDS.label_stats().get((), (0, 0.0)),
            "linger": av.VERIFY_LINGER_SECONDS.label_stats().get((), (0, 0.0)),
        },
    }


def last_route():
    from tendermint_tpu.crypto import async_verify as av

    return av.get_service().last_route


def last_shard_layout():
    """((device id, padded rows), ...) of the last sharded flush, or None."""
    from tendermint_tpu.crypto import async_verify as av

    return av.get_service().last_shard_layout


def compile_events() -> list[dict]:
    from tendermint_tpu.utils import devmon

    return devmon.TRACKER.snapshot()["events"]


def spans_since(t0_ns: int) -> list[dict]:
    from tendermint_tpu.utils import trace

    return [s for s in trace.spans() if s["t0_ns"] >= t0_ns]


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return peak


class CompileWatch:
    """Every compile request JAX makes (its own monitoring event, cache
    hit or not), stamped on the perf_counter clock: what compiled or was
    loaded inside a window, whichever program it was."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.stamps: list[float] = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.stamps.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.stamps if t0 <= t <= t1)
