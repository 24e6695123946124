"""Pluggable batch signature verification — the framework's north star.

The reference has *no* BatchVerifier: every consensus/light-client/fast-sync
signature is verified one at a time (reference: crypto/ed25519/ed25519.go:149-156
and the call-site census in SURVEY §2.9).  Here every verification surface
(VoteSet.add_vote, ValidatorSet.verify_commit*, fast sync, light client)
funnels into this interface, and the default backend aggregates the whole
batch into a single JAX/XLA device call.

Backends:
  * "cpu"  — sequential host loop: libcrypto fast path with pure-ZIP-215
             re-check on rejection (bit-identical verdicts; see
             ed25519.verify_fast — the PURE reference baseline is
             ed25519.verify_batch_reference)
  * "jax"  — vmapped TPU/XLA verifier (tendermint_tpu.ops.ed25519_jax)
  * "auto" — jax if importable, else cpu
The initial default comes from env TM_TPU_CRYPTO_BACKEND (auto|jax|cpu).
"""

from __future__ import annotations

import logging
import os
import traceback
from typing import Protocol, runtime_checkable

from tendermint_tpu.utils.metrics import Counter

from . import ed25519 as _ed


_log = logging.getLogger("tendermint_tpu.crypto.batch")


def _pub_bytes(pub) -> bytes:
    return pub.bytes_() if hasattr(pub, "bytes_") else bytes(pub)


def _bytes_column(col, convert=bytes) -> list[bytes]:
    # a type test per row, not a call: the surfaces hand in bytes already
    return [x if type(x) is bytes else convert(x) for x in col]


# How a verifier's rows came in: `bulk` by add_many (a column a job —
# the commit surfaces), `row` by add (vote slices, evidence).  Bumped
# once a verify(), never per row; node/metrics.py registers it.
ROWS_ADDED_TOTAL = Counter(
    "rows_added_total",
    "Rows handed to a batch verifier, by how they came in",
    namespace="tendermint", subsystem="verify", label_names=("how",),
)


@runtime_checkable
class BatchVerifier(Protocol):
    def add(self, pub_key, msg: bytes, sig: bytes) -> None: ...

    def add_many(self, pubs, msgs, sigs) -> None:
        """Three columns of equal length, appended in row order."""
        ...

    def count(self) -> int: ...

    def verify(self) -> tuple[bool, list[bool]]:
        """Returns (all_valid, per-item validity).  Resets the batch."""
        ...


class _BaseBatch:
    """Three columns — public keys, messages, signatures — in the order
    the rows came in, by `add` (one row) or `add_many` (a column each)."""

    def __init__(self) -> None:
        self._pubs: list[bytes] = []
        self._msgs: list[bytes] = []
        self._sigs: list[bytes] = []
        self._bulk = 0  # of the rows, those that came by add_many

    def add(self, pub_key, msg: bytes, sig: bytes) -> None:
        self._pubs.append(_pub_bytes(pub_key))
        self._msgs.append(bytes(msg))
        self._sigs.append(bytes(sig))

    def add_many(self, pubs, msgs, sigs) -> None:
        self._extend(_bytes_column(pubs, _pub_bytes), _bytes_column(msgs),
                     _bytes_column(sigs))

    def _extend(self, pubs, msgs, sigs) -> None:
        if not len(pubs) == len(msgs) == len(sigs):
            raise ValueError("add_many: columns of unequal length")
        self._pubs.extend(pubs)
        self._msgs.extend(msgs)
        self._sigs.extend(sigs)
        self._bulk += len(pubs)

    def count(self) -> int:
        return len(self._pubs)

    def _take(self):
        batch = (self._pubs, self._msgs, self._sigs)
        bulk, single = self._bulk, len(self._pubs) - self._bulk
        if bulk:
            ROWS_ADDED_TOTAL.inc(bulk, how="bulk")
        if single:
            ROWS_ADDED_TOTAL.inc(single, how="row")
        self._pubs, self._msgs, self._sigs, self._bulk = [], [], [], 0
        return batch


def _split_verify(pubs, msgs, sigs, ed_batch_fn) -> list[bool]:
    """Key-type routing for a mixed batch (reference: VerifyCommit &c.
    call pubkey.VerifySignature through the crypto.PubKey interface, so
    any registered key type participates).  Key-byte length is the
    discriminator — ed25519 pubs are 32 bytes, secp256k1 compressed
    pubs are 33 — so no type tags ride the batch.  The ed25519 majority
    goes through `ed_batch_fn` (batched: native kernel or device);
    other rows verify individually."""
    from tendermint_tpu.crypto.encoding import (
        ED25519_PUB_SIZE,
        SECP256K1_PUB_SIZE,
    )

    ed_idx = [i for i, p in enumerate(pubs) if len(p) == ED25519_PUB_SIZE]
    if len(ed_idx) == len(pubs):
        return ed_batch_fn(pubs, msgs, sigs)
    oks = [False] * len(pubs)
    if ed_idx:
        ed_oks = ed_batch_fn([pubs[i] for i in ed_idx],
                             [msgs[i] for i in ed_idx],
                             [sigs[i] for i in ed_idx])
        for i, ok in zip(ed_idx, ed_oks):
            oks[i] = bool(ok)
    from tendermint_tpu.crypto.secp256k1 import PubKeySecp256k1

    for i, p in enumerate(pubs):
        if len(p) == SECP256K1_PUB_SIZE:
            try:
                oks[i] = PubKeySecp256k1(p).verify_signature(msgs[i], sigs[i])
            except ValueError:
                oks[i] = False
        # any other length: not a known key encoding, stays False
    return oks


class CPUBatchVerifier(_BaseBatch):
    """Sequential host loop — ZIP-215 verdicts via the libcrypto fast
    path (rejections re-checked by the pure reference; see
    ed25519.verify_fast for the bit-identity argument)."""

    def verify(self) -> tuple[bool, list[bool]]:
        pubs, msgs, sigs = self._take()
        oks = _split_verify(pubs, msgs, sigs, _ed.verify_batch_fast)
        return all(oks) if oks else False, oks


import threading as _threading

_MEASURED_THRESHOLD: int | None = None
_THRESHOLD_DIAG: dict = {}
# Two locks with distinct jobs: _MEASURE_LOCK serializes the actual
# device measurement and is held for its whole duration (backend init
# plus the first compiles: seconds to minutes on a cold cache);
# _FLAG_LOCK guards only the started-flags and is held for nanoseconds.
# start_threshold_measurement/start_device_warmup touch ONLY _FLAG_LOCK
# (after a benign racy fast-path read), so a >=64-sig verify arriving
# while the measurement worker holds _MEASURE_LOCK never blocks behind
# it — a single-lock shape wedged the consensus receive loop for the
# measurement duration.
_MEASURE_LOCK = _threading.Lock()
_FLAG_LOCK = _threading.Lock()
_MEASURE_STARTED = False
_DEVICE_DISPATCHES = 0  # process-wide count of device-path batches

# Device readiness gate: the FIRST device contact in a process pays
# backend init + one compile (or compile-cache load) per program —
# seconds to minutes — and a consensus event loop that blocks that long
# gets its peers evicted.  So production batches route to the host path
# until a background warmup (or a successful threshold measurement)
# proves the device answers; only then do >=threshold batches dispatch.
# A device that never comes up therefore degrades to the host path
# instead of wedging consensus — and the exception that kept it down is
# kept in threshold_diagnostics() and logged when it happens, so the
# degradation is never silent.
_DEVICE_READY = _threading.Event()
_WARMUP_STARTED = False


def _exc_record(e: BaseException) -> dict:
    return {"type": type(e).__name__, "message": str(e)[-500:],
            "traceback": "".join(traceback.format_exception(e))[-4000:]}


def _mark_device_ready(via: str) -> None:
    """Set the readiness gate and say WHAT became ready: XLA-CPU counts
    as a "device" for tests that ask for the jax backend, so the log
    line and threshold_diagnostics() (hence /status) carry the platform
    and device kind — a node whose TPU did not come up must not read as
    healthy."""
    import jax

    devs = jax.devices()
    _THRESHOLD_DIAG.update(platform=devs[0].platform,
                           device_kind=devs[0].device_kind,
                           device_count=len(devs))
    _DEVICE_READY.set()
    _log.info("verify device ready via %s: platform=%s device_kind=%s "
              "devices=%d", via, devs[0].platform, devs[0].device_kind,
              len(devs))


def start_device_warmup() -> None:
    """Warm the device on a daemon thread (idempotent): one n=8
    verify_batch through the real device program; success sets
    _DEVICE_READY.  Failure (or a hang) leaves it unset — callers keep
    using the host path — and the exception lands in
    threshold_diagnostics()["warmup_error"] and the log."""
    global _WARMUP_STARTED
    # fast path WITHOUT any lock (benign racy read — worst case two
    # threads reach the flag lock): callers are the verify hot path and
    # must never queue behind an in-flight measurement (ADVICE r5 high)
    if _WARMUP_STARTED or _MEASURE_STARTED or _DEVICE_READY.is_set():
        return
    with _FLAG_LOCK:
        if (_WARMUP_STARTED or _MEASURE_STARTED
                or _DEVICE_READY.is_set()):
            return  # a measurement worker doubles as warmup
        _WARMUP_STARTED = True

    def _warm() -> None:
        try:
            from tendermint_tpu.crypto.keys import priv_key_from_seed
            from tendermint_tpu.ops import ed25519_jax as dev

            privs = [priv_key_from_seed(bytes([i + 1]) * 32) for i in range(8)]
            pubs = [p.pub_key().bytes_() for p in privs]
            msgs = [b"device-warmup-%d" % i for i in range(8)]
            sigs = [p.sign(m) for p, m in zip(privs, msgs)]
            ok = dev.verify_batch(pubs, msgs, sigs)
            if not all(bool(v) for v in ok):
                raise RuntimeError(
                    "device warm-up batch returned wrong verdicts: "
                    f"{[bool(v) for v in ok]}")
            _mark_device_ready("warmup")
            # device proven answering: warm the rest of the shape
            # plan's rungs in the background (ops/shape_plan) so
            # steady-state buckets are compiled before traffic
            # needs them — no-op unless `tendermint-tpu warm`
            # saved a plan, killed by TM_TPU_AOT=0
            from tendermint_tpu.ops import shape_plan as _sp

            _sp.start_background_warm("device-warmup")
        except Exception as e:  # noqa: BLE001 — not-ready routes to host
            _THRESHOLD_DIAG["warmup_error"] = _exc_record(e)
            _log.warning("device warm-up failed; flushes stay on the host "
                         "path", exc_info=True)

    _threading.Thread(target=_warm, daemon=True,
                      name="tm-device-warmup").start()


def device_ready() -> bool:
    return _DEVICE_READY.is_set()


def start_threshold_measurement() -> None:
    """Kick the one-time dispatch-threshold measurement on a daemon
    worker thread (idempotent).  The measurement's warm-up device round
    trips (backend init plus a cold compile: seconds to minutes) must
    never run on the consensus receive loop — callers route batches to
    the host path until `measured_cpu_threshold_ready()` reports the
    result."""
    global _MEASURE_STARTED
    # fast path WITHOUT any lock (benign racy read): while the worker
    # measures — holding _MEASURE_LOCK for the full device round trip —
    # every >=64-sig verify lands here, and queueing on that lock would
    # wedge the consensus receive loop for the measurement duration
    # (ADVICE r5 high)
    if _MEASURE_STARTED or _MEASURED_THRESHOLD is not None:
        return
    with _FLAG_LOCK:
        if _MEASURE_STARTED or _MEASURED_THRESHOLD is not None:
            return
        _MEASURE_STARTED = True
    # late-bound lookup so tests can monkeypatch measured_cpu_threshold
    _threading.Thread(
        target=lambda: measured_cpu_threshold(), daemon=True,
        name="tm-threshold-measure",
    ).start()


def measured_cpu_threshold_ready() -> int | None:
    """The measured threshold if the background measurement finished,
    else None (callers use the host path meanwhile)."""
    return _MEASURED_THRESHOLD


def measured_cpu_threshold() -> int:
    """Breakeven batch size between the host loop and the device
    program, measured ONCE per process: one warm n=8 device round trip
    (min of 3, after a warmup call that absorbs compile/transfer setup)
    divided by the host path's per-signature cost on real signatures.
    Clamped to [16, 16384].  Falls back to 64 (the old default) if the
    device cannot be timed — with the exception kept in
    `threshold_diagnostics()["error"]` and logged at warning level.
    Diagnostics (measured RTT, host cost) are kept there too.

    Thread-safe: the background worker (start_threshold_measurement) and
    direct callers (bench harnesses) serialize on _MEASURE_LOCK, so the
    device warm-up runs exactly once per process.  _MEASURE_STARTED is
    raised first so concurrent start_* fast paths return without ever
    touching this (long-held) lock.
    """
    global _MEASURE_STARTED
    with _FLAG_LOCK:
        _MEASURE_STARTED = True
    with _MEASURE_LOCK:
        return _measure_cpu_threshold_locked()


def _measure_cpu_threshold_locked() -> int:
    global _MEASURED_THRESHOLD
    if _MEASURED_THRESHOLD is not None:
        return _MEASURED_THRESHOLD
    import time

    try:
        from tendermint_tpu.crypto.keys import priv_key_from_seed
        from tendermint_tpu.ops import ed25519_jax as dev

        import jax

        if jax.default_backend() == "cpu":
            # XLA-CPU is a test/diagnostic configuration: its device
            # program is never the production choice, and paying an
            # n=8 compile at every node start stalls e2e nets.  Real
            # accelerators get measured.
            _THRESHOLD_DIAG.update(
                measured=False, reason="xla-cpu backend; static default",
                threshold=64,
            )
            _MEASURED_THRESHOLD = 64
            _mark_device_ready("xla-cpu static default")
            return 64

        privs = [priv_key_from_seed(bytes([i + 1]) * 32) for i in range(32)]
        pubs = [p.pub_key().bytes_() for p in privs]
        msgs = [b"rtt-probe-%d" % i for i in range(32)]
        sigs = [p.sign(m) for p, m in zip(privs, msgs)]

        # warm: compile + setup, n=8 bucket
        oks = dev.verify_batch(pubs[:8], msgs[:8], sigs[:8])
        if not all(bool(v) for v in oks):
            raise RuntimeError(
                "device rtt-probe batch returned wrong verdicts: "
                f"{[bool(v) for v in oks]}")
        rtt = None
        for _ in range(3):
            t0 = time.perf_counter()
            dev.verify_batch(pubs[:8], msgs[:8], sigs[:8])
            dt = time.perf_counter() - t0
            rtt = dt if rtt is None else min(rtt, dt)

        # host cost at n=32: batches the threshold arbitrates (>=16) run
        # the NATIVE one-call kernel, so probing with n=8 (Python loop,
        # several times slower per sig) would set the breakeven several
        # times too low and misroute mid-size batches to the device
        _ed.verify_batch_fast(pubs, msgs, sigs)  # warm native lib
        t0 = time.perf_counter()
        for _ in range(4):
            _ed.verify_batch_fast(pubs, msgs, sigs)
        host_per_sig = (time.perf_counter() - t0) / 128

        thr = max(16, min(16384, int(rtt / max(host_per_sig, 1e-7))))
        _THRESHOLD_DIAG.update(
            device_rtt_ms=round(rtt * 1e3, 3),
            host_us_per_sig=round(host_per_sig * 1e6, 2),
            threshold=thr,
            measured=True,
        )
        _MEASURED_THRESHOLD = thr
        # the measurement's round trips ARE the warmup
        _mark_device_ready("threshold measurement")
        _log.info("dispatch threshold measured: %s", _THRESHOLD_DIAG)
    except Exception as e:  # noqa: BLE001 — no device, failed compile, ...
        _THRESHOLD_DIAG.update(measured=False, error=_exc_record(e),
                               threshold=64)
        _MEASURED_THRESHOLD = 64
        _log.warning("dispatch-threshold measurement failed; static "
                     "default 64, device not ready", exc_info=True)
    return _MEASURED_THRESHOLD


def threshold_diagnostics() -> dict:
    """The last measured_cpu_threshold() measurement (empty before):
    device round trip, host cost and the resulting threshold; the
    platform/device_kind that became ready; and, where the measurement
    or the warm-up raised, the exception under "error"/"warmup_error"
    (type, message, traceback)."""
    return dict(_THRESHOLD_DIAG)


#: one-entry (raw, parsed) memo so the env parse (and the malformed
#: warning) runs once per distinct raw value, not once per flush.
#: Benign under races: a tuple rebind is atomic and any winner is right.
_ENV_THRESHOLD_MEMO: tuple[str, int | None] | None = None


def _env_cpu_threshold() -> int | None:
    """TM_TPU_CPU_THRESHOLD as an int pin, or None (unset/auto/
    malformed = defer to lazy measurement).  Breakeven background: a
    hardcoded 64 encodes a dispatch-cost assumption no deployment has
    to share, so by default the breakeven is MEASURED lazily — at the
    first batch that clears the static 64-sig floor, i.e. the first
    call that was about to initialize the device anyway; touching the
    device any earlier is forbidden here (backend init can take
    arbitrarily long).  The env var pins it explicitly, and is
    re-read on every call so a value set after a verifier (or the
    process-wide service singleton) was built still takes effect."""
    global _ENV_THRESHOLD_MEMO
    raw = os.environ.get("TM_TPU_CPU_THRESHOLD", "auto")
    memo = _ENV_THRESHOLD_MEMO
    if memo is not None and memo[0] == raw:
        return memo[1]
    val: int | None = None
    if raw != "auto":
        try:
            val = int(raw)
        except ValueError:
            import warnings

            warnings.warn(
                f"ignoring malformed TM_TPU_CPU_THRESHOLD={raw!r}; "
                "deferring to lazy measurement"
            )
    _ENV_THRESHOLD_MEMO = (raw, val)
    return val


class JAXBatchVerifier(_BaseBatch):
    """One XLA device program verifies the entire batch (vmapped, bucketed).

    Batches below `cpu_threshold` run on the CPU reference instead: the
    host→device round trip dwarfs a handful of verifies, and consensus
    liveness depends on small vote batches staying sub-millisecond
    (SURVEY §7 hard part 2 — deadline flush with CPU fallback for
    singletons).

    On a multi-device mesh the SAME production path shards the batch axis
    across all devices (tendermint_tpu.parallel.sharding) — this is what
    `dryrun_multichip` exercises and what a pod deployment runs; a 10k-sig
    commit splits across ICI with zero collectives."""

    def __init__(self, cpu_threshold: int | None = None) -> None:
        super().__init__()
        from tendermint_tpu.ops import ed25519_jax, host_prep  # lazy: jax import

        self._impl = ed25519_jax
        self._n_devices: int | None = None  # resolved on first device call
        # build/load the native host-prep kernel NOW (node startup), not
        # inside the first vote-batch verification — a lazy `make` there
        # would stall the consensus receive loop for seconds
        host_prep.load_lib()
        # Threshold precedence: explicit pin (ctor arg / assignment) >
        # TM_TPU_CPU_THRESHOLD, re-read at every resolution so a value
        # set AFTER construction still takes effect (construction-time
        # capture on the process-wide service singleton was the
        # order-dependent test_multinode device-path flake) > lazily
        # measured breakeven (None here = measure at first >=64 batch).
        self._pinned_threshold = cpu_threshold
        self._measured_local: int | None = None

    @property
    def cpu_threshold(self) -> int | None:
        if self._pinned_threshold is not None:
            return self._pinned_threshold
        env = _env_cpu_threshold()
        if env is not None:
            return env
        return self._measured_local

    @cpu_threshold.setter
    def cpu_threshold(self, value: int | None) -> None:
        self._pinned_threshold = value

    def _device_count(self) -> int:
        if self._n_devices is None:
            import jax

            self._n_devices = len(jax.devices())
        return self._n_devices

    def _resolved_threshold(self, n: int) -> int:
        """The dispatch threshold, measured on first demand WITHOUT
        stalling the caller: batches under the static 64 floor stay on
        the host without ever touching the device; the first batch
        at/over the floor kicks the one-time RTT measurement on a
        worker thread (start_threshold_measurement) and itself runs on
        the host path — the consensus receive loop never blocks on the
        device warm-up (an eager-at-startup variant hung whole nets on
        a backend that would not initialize, and an inline variant
        moved that stall into the hot path instead)."""
        thr = self.cpu_threshold
        if thr is not None:
            return thr
        if n < 64:
            return 64
        measured = measured_cpu_threshold_ready()
        if measured is not None:
            # cached as measured, NOT as a pin: a TM_TPU_CPU_THRESHOLD
            # set later still wins (see cpu_threshold precedence)
            self._measured_local = measured
            return measured
        start_threshold_measurement()
        return n + 1  # host path while the worker measures

    def _ed_batch(self, pubs, msgs, sigs) -> list[bool]:
        """The ed25519-only core: device program (sharded on a mesh) or
        host fallback below the dispatch threshold."""
        if len(pubs) < self._resolved_threshold(len(pubs)):
            return _ed.verify_batch_fast(pubs, msgs, sigs)
        if not _DEVICE_READY.is_set():
            # first device contact costs backend init + compile (or
            # compile-cache load), seconds to minutes, and must never
            # block the consensus loop: warm on a worker, verify on the
            # host meanwhile
            start_device_warmup()
            return _ed.verify_batch_fast(pubs, msgs, sigs)
        global _DEVICE_DISPATCHES
        _DEVICE_DISPATCHES += 1
        if _DEVICE_DISPATCHES == 1:
            # one-time structured evidence line: a TPU-in-the-loop net's
            # artifact must be able to PROVE the chip was dispatched to,
            # and node logs are the only surface another process can read
            import sys

            import jax

            sys.stderr.write(
                "tm-tpu: first device dispatch n=%d backend=%s threshold=%s\n"
                % (len(pubs), jax.default_backend(), self.cpu_threshold))
            sys.stderr.flush()
        if self._device_count() > 1:
            from tendermint_tpu.parallel import sharding

            oks = sharding.verify_batch_sharded(pubs, msgs, sigs)
        else:
            oks = self._impl.verify_batch(pubs, msgs, sigs)
        return [bool(v) for v in oks]

    def verify(self) -> tuple[bool, list[bool]]:
        pubs, msgs, sigs = self._take()
        if not pubs:
            return False, []
        oks = _split_verify(pubs, msgs, sigs, self._ed_batch)
        return bool(all(oks)), oks


# None = not yet resolved: TM_TPU_CRYPTO_BACKEND is read lazily at the
# first new_batch_verifier() call (not at import — tmlint
# import-time-env; the PR 3 multinode flake came from exactly this kind
# of construction-time env capture).  set_default_backend() pins a
# value; reload_env() un-pins back to the environment.
_DEFAULT_BACKEND: str | None = None


def _default_backend() -> str:
    global _DEFAULT_BACKEND
    if _DEFAULT_BACKEND is None:
        backend = os.environ.get("TM_TPU_CRYPTO_BACKEND", "auto")
        _DEFAULT_BACKEND = backend if backend in ("auto", "jax", "cpu") \
            else "auto"
    return _DEFAULT_BACKEND


def set_default_backend(name: str) -> None:
    global _DEFAULT_BACKEND
    if name not in ("auto", "jax", "cpu"):
        raise ValueError(f"unknown batch-verifier backend {name!r}")
    _DEFAULT_BACKEND = name


def reload_env() -> None:
    """Drop the cached/pinned default so the next new_batch_verifier()
    re-reads TM_TPU_CRYPTO_BACKEND."""
    global _DEFAULT_BACKEND
    _DEFAULT_BACKEND = None


def new_batch_verifier(backend: str | None = None) -> BatchVerifier:
    backend = backend or _default_backend()
    if backend not in ("auto", "jax", "cpu"):
        raise ValueError(f"unknown batch-verifier backend {backend!r}")
    if backend == "cpu":
        return CPUBatchVerifier()
    if backend == "jax":
        return JAXBatchVerifier()
    try:
        return JAXBatchVerifier()
    except Exception:  # noqa: BLE001 — "auto" promises a verifier
        _log.warning("jax batch verifier unavailable; 'auto' backend "
                     "falls back to the host verifier", exc_info=True)
        return CPUBatchVerifier()
