"""Asynchronous verification service: cross-caller micro-batching,
host/device pipelining, and a verified-signature cache.

Every device round trip has a fixed dispatch cost, yet the hot callers
(VoteSet.add_votes slices, gossip prechecks, blocksync windows) each
construct their own BatchVerifier and submit batches that are
individually below the CPU/TPU breakeven — so no caller ever amortizes
a dispatch, even when several of them are verifying at the same moment.

This module is the continuous-batching answer (the Orca-style
iteration-level scheduling of inference serving, applied to signature
verification; PAPERS.md):

  * `submit_many(items) -> Future[list[bool]]` (and `submit(pub, msg,
    sig) -> Future[bool]`, a submit of one row) never blocks.
    `submit_columns(pubs, msgs, sigs)` is the same entry for a caller
    that holds its rows as three columns (the commit surfaces, through
    `ServiceBatchVerifier.add_many`): an items submit is unzipped into
    columns and shares everything with it from the key pass on.  The
    unit the service queues, accounts and resolves is the SUBMIT — a
    group of rows with one future — not the row: a 10,000-row commit
    costs one future, one queue entry, one cache pass each way and a
    handful of histogram observes with a count.  Groups from
    independent callers land in ONE submission queue; a daemon worker
    coalesces them into a single batch (cutting a group that is wider than what a flush has
    left) and dispatches when the queued rows reach a size rung from
    the `_bucket` ladder or when a linger deadline (`TM_TPU_LINGER_MS`)
    expires.  Below-threshold flushes route to the host path exactly as
    today.
  * Double-buffered host/device pipelining: the worker ENQUEUES the
    compiled device program for batch i (JAX dispatch is async) and
    immediately starts host prep (sign-bytes SHA-512, s<L) for batch
    i+1; verdicts are drained when a second batch is in flight or the
    queue runs dry.  A flush is one program at its rung.
  * A bounded verified-signature LRU cache is consulted before
    enqueue — one bulk probe per submit, under one lock acquisition —
    and populated ONLY on success, one bulk put per flush — gossip
    duplicates and replay re-verification never reach the device (and a
    corrupted signature can never be cached as valid, by construction).
    A row's key is ONE 32-byte SHA-256 over an injective encoding of
    the row, `u32le len(pub) || pub || u32le len(sig) || sig || msg`
    (fixed-width lengths: no two distinct rows share an encoding).
    Where `libedhost` and libcrypto's SHA-256 load
    (`crypto/sigcache_native`), a submit's key pass is one C call over
    its three columns into a `uint8[n, 32]` array, and the LRU of keys
    lives in native code: the key pass, the probe and the put make no
    Python object per row.  Elsewhere the same keys come from `hashlib`
    and the store is an `OrderedDict` keyed by their bytes — chosen by
    whether the library loads, as host prep's k pass is.

Degradation contract (the `_DEVICE_READY` guarantee, one level up): the
worker only dispatches to the device after crypto.batch's warmup has
proven it answers; until then — and forever, on a device that never
comes up — every flush runs the host path, so a submitter is never
blocked by backend init or compile-cache loads.  A device failure
AFTER readiness (enqueue or verdict readback raising) also resolves the
flush with host verdicts, but never silently: each such event bumps the
`device_errors` counter (service_stats(), /metrics, /status) and the
first one per site is logged with its traceback.  `device_batches`
counts ENQUEUES, so read alone it proves nothing — read it beside
`device_errors` and the `path="device"` count of verify_e2e_seconds.

Env knobs:
  TM_TPU_ASYNC_VERIFY   1 (default) routes the framework's verify
                        surfaces through the service; 0 restores
                        per-caller BatchVerifier instances.
  TM_TPU_LINGER_MS      coalescing window in milliseconds (default 1.0).
  TM_TPU_VERIFY_CACHE   verified-signature cache capacity in entries
                        (default 65536; 0 disables the cache), resolved
                        at every probe and put: a shrink evicts.
  TM_TPU_MESH           multi-device dispatch (crypto/mesh_dispatch):
                        unset/auto shards large flushes across the full
                        device mesh and pins small ones to one chip;
                        1 forces single-device (bit-identical to the
                        pre-mesh service); 0 restores the legacy
                        synchronous multi-device routing.
  TM_TPU_MESH_MIN_SHARD flush size at/above which a flush shards
                        (default 64 rows per device).
  TM_TPU_TRACE          1 additionally records submit (with one child
                        per bulk pass: keys — `native` says which key
                        pass ran —, probe) and wait (caller) and
                        coalesce/account/flush/host-prep/device-execute/
                        resolve (its child cache_put) (worker) spans
                        into the utils.trace ring,
                        the worker's tied by a `flush` number
                        (docs/observability.md); the latency histograms
                        below are always on, observed once per flush
                        segment with the segment's row count.
"""

from __future__ import annotations

import hashlib
import logging
import os
import struct
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from itertools import compress

import numpy as np

from tendermint_tpu.utils import devmon as _devmon
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.utils.metrics import Counter, Histogram

from . import ed25519 as _ed
from . import batch as _batch
from . import mesh_dispatch as _mesh
from . import sigcache_native as _sn
from .batch import _BaseBatch, _bytes_column, _pub_bytes, _split_verify

_log = logging.getLogger("tendermint_tpu.crypto.async_verify")

DEFAULT_LINGER_MS = 1.0
DEFAULT_CACHE_SIZE = 65536
MAX_COALESCE = 16384  # per-flush cap == the bucket ladder's top rung

# -- pipeline latency histograms (process-wide, like the service itself;
# node/metrics.py registers them so every node's /metrics scrape exposes
# them).  Buckets reach down to 50us: host flushes of small rungs finish
# well under the default prometheus grid.
_FAST_BUCKETS = (
    0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
    0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)

VERIFY_QUEUE_WAIT_SECONDS = Histogram(
    "verify_queue_wait_seconds",
    "Time a request sits in the submission queue before its flush",
    namespace="tendermint", subsystem="crypto", buckets=_FAST_BUCKETS)
VERIFY_LINGER_SECONDS = Histogram(
    "verify_linger_seconds",
    "How long a flush lingered coalescing before dispatch",
    namespace="tendermint", subsystem="crypto", buckets=_FAST_BUCKETS)
VERIFY_HOST_PREP_SECONDS = Histogram(
    "verify_host_prep_seconds",
    "Host-side device-batch preparation (sign-bytes SHA-512, s<L, padding)",
    namespace="tendermint", subsystem="crypto", buckets=_FAST_BUCKETS)
VERIFY_DEVICE_EXECUTE_SECONDS = Histogram(
    "verify_device_execute_seconds",
    "Device enqueue to verdict readback per flush, by bucket rung",
    namespace="tendermint", subsystem="crypto", label_names=("rung",),
    buckets=_FAST_BUCKETS)
VERIFY_E2E_SECONDS = Histogram(
    "verify_e2e_seconds",
    "Submit to resolve end to end, by resolution path",
    namespace="tendermint", subsystem="crypto", label_names=("path",),
    buckets=_FAST_BUCKETS)

PIPELINE_HISTOGRAMS = (
    VERIFY_QUEUE_WAIT_SECONDS,
    VERIFY_LINGER_SECONDS,
    VERIFY_HOST_PREP_SECONDS,
    VERIFY_DEVICE_EXECUTE_SECONDS,
    VERIFY_E2E_SECONDS,
)

# Which key pass the cache's keys took: `native` (one C call a column,
# crypto/sigcache_native) or `python` (hashlib, the library did not
# load).  Bumped once a key pass with its row count; node/metrics.py
# registers it.
VERIFY_CACHE_ROWS_TOTAL = Counter(
    "verify_cache_rows_total",
    "Rows whose signature-cache key was computed, by key pass",
    namespace="tendermint", subsystem="crypto", label_names=("pass",))
_NATIVE_PASS, _PYTHON_PASS = {"pass": "native"}, {"pass": "python"}


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return max(0, int(os.environ.get(name, default)))
    except ValueError:
        return default


class _Group:
    """One submit: the unit the service queues, accounts and resolves.
    `pubs` / `msgs` / `sigs` are its FRESH rows (the cache misses; the
    submit's own lists when nothing hit), `keys` their cache keys
    (`uint8[n, 32]`), `n` how many, `pos` their places in `results`
    (None: every row is fresh, in place).
    `results` starts as the cache probe's answer — True at the hits,
    False at every row no verify path has answered yet — and `future`
    resolves to it once the last fresh row has landed (`single`: to its
    only element).  `taken` counts the rows handed to a flush (moved
    under the service lock), `left` those not yet resolved (the
    worker's alone)."""

    __slots__ = ("pubs", "msgs", "sigs", "keys", "n", "pos", "results",
                 "single", "t_submit", "future", "taken", "left")

    def __init__(self, pubs, msgs, sigs, keys, pos, results, single,
                 t_submit):
        self.pubs, self.msgs, self.sigs, self.keys = pubs, msgs, sigs, keys
        self.n = len(pubs)
        self.pos = pos
        self.results = results
        self.single = single
        self.t_submit = t_submit
        self.future: Future = Future()
        self.taken = 0
        self.left = self.n

    def land(self, start: int, oks: list) -> None:
        """Verdicts of the fresh rows from `start` on; the last to land
        resolves the future.  A group whose future already failed
        ignores what lands later."""
        if self.pos is None:
            self.results[start:start + len(oks)] = oks
        else:
            for i, ok in zip(self.pos[start:start + len(oks)], oks):
                self.results[i] = ok
        self.left -= len(oks)
        if self.left <= 0 and not self.future.done():
            self.future.set_result(self.results[0] if self.single
                                   else self.results)

    def fail(self, err: BaseException) -> None:
        if not self.future.done():
            self.future.set_exception(err)


class _Batch:
    """The rows of one flush: the segments `(group, start, end)` in
    row order, and the three lists a verify path takes — the group's
    own when the batch is one whole group (a commit's case: no copy),
    else the segments' slices concatenated."""

    __slots__ = ("segs", "pubs", "msgs", "sigs")

    def __init__(self, segs: list):
        self.segs = segs
        g, a, b = segs[0]
        if len(segs) == 1 and a == 0 and b == g.n:
            self.pubs, self.msgs, self.sigs = g.pubs, g.msgs, g.sigs
        else:
            self.pubs = [p for g, a, b in segs for p in g.pubs[a:b]]
            self.msgs = [m for g, a, b in segs for m in g.msgs[a:b]]
            self.sigs = [s for g, a, b in segs for s in g.sigs[a:b]]

    def __len__(self) -> int:
        return len(self.pubs)


_u32le = struct.Struct("<I").pack


def _row_key(pub: bytes, msg: bytes, sig: bytes) -> bytes:
    return hashlib.sha256(b"".join(
        (_u32le(len(pub)), pub, _u32le(len(sig)), sig, msg))).digest()


def _row_keys(pubs, msgs, sigs) -> tuple[np.ndarray, bool]:
    """The key of every row as `uint8[n, 32]`, and whether the native
    pass made it (one C call) or `hashlib` did (the library did not
    load).  The columns hold `bytes`."""
    keys = _sn.row_keys(pubs, msgs, sigs)
    native = keys is not None
    if not native:
        keys = np.frombuffer(b"".join(map(_row_key, pubs, msgs, sigs)),
                             dtype=np.uint8).reshape(-1, 32)
    VERIFY_CACHE_ROWS_TOTAL.inc(len(keys), **(_NATIVE_PASS if native
                                              else _PYTHON_PASS))
    return keys, native


def _one_key(key: bytes) -> np.ndarray:
    return np.frombuffer(key, dtype=np.uint8).reshape(1, 32)


class _PyStore:
    """The LRU store where the native one does not load: an OrderedDict
    keyed by the 32 key bytes, least recently used first."""

    def __init__(self):
        self._d: OrderedDict = OrderedDict()

    @staticmethod
    def _rows(keys: np.ndarray) -> list[bytes]:
        b = keys.tobytes()
        return [b[i:i + 32] for i in range(0, len(b), 32)]

    def probe(self, keys: np.ndarray, cap: int) -> tuple[np.ndarray, int]:
        d = self._d
        while len(d) > cap:
            d.popitem(last=False)
        rows = self._rows(keys)
        found = [k in d for k in rows]
        for k in compress(rows, found):
            d.move_to_end(k)
        return np.array(found, dtype=np.uint8), sum(found)

    def put(self, keys: np.ndarray, mask: bytes | None, cap: int) -> int:
        d = self._d
        rows = self._rows(keys)
        if mask is not None:
            rows = list(compress(rows, mask))
        for k in rows:
            d[k] = True
            d.move_to_end(k)
        while len(d) > cap:
            d.popitem(last=False)
        return len(rows)

    def __len__(self) -> int:
        return len(self._d)


class VerifiedSigCache:
    """Bounded thread-safe LRU of the keys of rows proven VALID.  Only
    True verdicts are ever stored: a rejected signature is re-verified
    on every appearance, so a corrupted signature cannot be cached as
    valid no matter what races occur.  Probes and puts are bulk: one
    lock acquisition and one read of the capacity for a whole submit or
    flush.

    A row's key is SHA-256(u32le len(pub) || pub || u32le len(sig) ||
    sig || msg): 32 bytes, the lengths fixed-width so that no two
    distinct rows share an encoding — as collision-resistant as the
    (pub, sha256(msg), sig) triple it replaced.  `keys()` computes a
    column's in one native call (`uint8[n, 32]`), `key()` one row's with
    `hashlib` over the same bytes.  The store is native
    (`sigcache_native.NativeStore`) where the library loads, else an
    OrderedDict of the same keys; the same exact LRU either way: a hit
    becomes the most recently used, a put of a present key refreshes it,
    the least recently used goes beyond the capacity."""

    def __init__(self, maxsize: int | None = None):
        # None = resolve TM_TPU_VERIFY_CACHE at every probe, so a value
        # set AFTER the process-wide service was built still takes
        # effect (the construction-time capture was half of the
        # order-dependent test_multinode flake — the pinned-threshold
        # half lives in crypto/batch.py)
        self._pinned_maxsize = maxsize
        self._store = _sn.NativeStore() if _sn.available() else _PyStore()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    @property
    def maxsize(self) -> int:
        if self._pinned_maxsize is not None:
            return self._pinned_maxsize
        return _env_int("TM_TPU_VERIFY_CACHE", DEFAULT_CACHE_SIZE)

    @staticmethod
    def keys(pubs, msgs, sigs) -> np.ndarray:
        return _row_keys(pubs, msgs, sigs)[0]

    @staticmethod
    def key(pub: bytes, msg: bytes, sig: bytes) -> bytes:
        return _row_key(pub, msg, sig)

    def probe(self, keys: np.ndarray) -> tuple[np.ndarray, int]:
        """(`found`, hits): `found[i]` 1 where row i of `keys` is cached
        (each hit becomes the most recently used).  A capacity below the
        store's size evicts first."""
        n = len(keys)
        cap = self.maxsize
        if cap <= 0:
            self.misses += n  # tmsan: shared=diagnostic counter on the disabled-cache path; tolerates lost updates
            return np.zeros(n, dtype=np.uint8), 0
        with self._lock:
            found, hits = self._store.probe(keys, cap)
            self.hits += hits
            self.misses += n - hits
        return found, hits

    def get_many(self, keys: np.ndarray) -> list[bool]:
        """Which rows of `keys` are cached, as bools."""
        return self.probe(keys)[0].astype(bool).tolist()

    def get(self, key: bytes) -> bool:
        return self.get_many(_one_key(key))[0]

    def put_many(self, keys: np.ndarray, mask: bytes | None = None) -> int:
        """Remember the rows of `keys` whose `mask` byte is non-zero
        (every row without a mask) — rows PROVEN valid, the caller's
        duty — and evict the least recently used beyond the capacity.
        Returns the rows stored."""
        cap = self.maxsize
        if cap <= 0 or not len(keys):
            return 0
        with self._lock:
            return self._store.put(keys, mask, cap)

    def put(self, key: bytes) -> None:
        self.put_many(_one_key(key))

    def __len__(self) -> int:
        return len(self._store)


class VerifyService:
    """The process-wide verification daemon.  See the module docstring
    for the batching/pipelining/caching design; `get_service()` returns
    the shared instance."""

    def __init__(self, *, linger_ms: float | None = None,
                 cache_size: int | None = None,
                 cpu_threshold: int | None = None):
        # linger/cache sizing resolve their env knobs lazily when not
        # pinned by a ctor arg — see VerifiedSigCache.maxsize
        self._pinned_linger_ms = linger_ms
        self.cache = VerifiedSigCache(cache_size)
        self._cv = threading.Condition()
        self._queue: deque[_Group] = deque()
        self._worker: threading.Thread | None = None
        self._closed = False
        self.stats = {
            "submits": 0,    # groups queued
            "submitted": 0,  # their fresh rows
            "flushes": 0,
            "host_flushes": 0,
            "device_batches": 0,
            "coalesced_max": 0,
            "pipelined_drains": 0,
            "mesh_pinned_batches": 0,
            "mesh_sharded_batches": 0,
            "device_errors": 0,
            "queue_depth": 0,  # rows of the queued groups not yet taken
        }
        # sites ("enqueue", "enqueue_sharded", "readback", "sync") whose
        # first device error was already logged with its traceback
        self._logged_error_sites: set[str] = set()
        # sequence number of the flush the worker is working on: assigned
        # in _collect, kept with the batch in `inflight`, and rides every
        # worker span as `flush` so that one flush's spans can be paired
        # by name and number instead of by order.  Worker-thread-owned.
        self._flush_no = 0  # tmsan: shared=written by the worker thread only (ctor aside); read by its own span sites
        # last (path, reason) the router chose — tests assert the
        # routing DECISION (pinned vs sharded), not just the verdicts
        self.last_route: tuple[str, str] | None = None  # tmsan: shared=atomic tuple rebind, last-write-wins diagnostic
        # ((device id, rows), ...) of the last sharded flush's RESULT,
        # read from the array's addressable shards — where the verdicts
        # actually lived, as opposed to where the router meant them to
        self.last_shard_layout: tuple | None = None  # tmsan: shared=atomic tuple rebind, last-write-wins diagnostic
        # the threshold/readiness arbitration reuses JAXBatchVerifier's
        # lazy measurement machinery; on a jax-less box every flush
        # routes to the host path
        try:
            self._jax_bv = _batch.JAXBatchVerifier(cpu_threshold=cpu_threshold)
        except Exception:  # noqa: BLE001 — no jax: host-only service
            _log.warning("jax verifier unavailable; the verify service "
                         "runs host-only", exc_info=True)
            self._jax_bv = None
        # AOT warm-on-start (ops/shape_plan): if an operator ran
        # `tendermint-tpu warm` (a saved plan exists next to the compile
        # cache), deserialize/compile its executables on a daemon thread
        # NOW so the first real flush finds warm programs instead of
        # paying the compiles inline.  Strict no-op otherwise, and
        # TM_TPU_AOT=0 kills it; a slow or failing device stalls only
        # the warm thread (same contract as start_device_warmup).
        if self._jax_bv is not None:
            from tendermint_tpu.ops import shape_plan as _sp

            _sp.start_background_warm("verify-service-start")

    @property
    def linger_s(self) -> float:
        ms = (self._pinned_linger_ms if self._pinned_linger_ms is not None
              else _env_float("TM_TPU_LINGER_MS", DEFAULT_LINGER_MS))
        return ms / 1e3

    # -- submission (caller side; never blocks) -----------------------

    def submit(self, pub, msg: bytes, sig: bytes) -> Future:
        """Queue one verification; resolves to bool — a submit of one
        row, its result unwrapped.  A cache hit resolves immediately
        without queueing."""
        return self._submit((pub,), (msg,), (sig,), single=True).future

    def submit_many(self, items) -> Future:
        """Bulk submit: ONE future for the whole submit, resolving to
        the list of verdicts in input order (immediately if every row
        hit the cache)."""
        return self._submit_items(items).future

    def submit_columns(self, pubs, msgs, sigs) -> Future:
        """`submit_many` for a caller that holds its rows as three
        columns of equal length (the commit surfaces): nothing is zipped
        into tuples to be taken apart again."""
        return self._submit(pubs, msgs, sigs).future

    def _submit_items(self, items) -> _Group:
        t_sub = time.perf_counter()  # the unzip is part of the submit
        pubs, msgs, sigs = tuple(zip(*items)) or ((), (), ())
        return self._submit(pubs, msgs, sigs, t_sub=t_sub)

    def _submit(self, pubs, msgs, sigs, single: bool = False,
                t_sub: float | None = None) -> _Group:
        """Build and queue the group of one submit: every pass is bulk —
        the keys in one native call, the cache probe and the queue
        append under one lock acquisition each — so a 10k commit makes
        no Python object per row on its way to the queue."""
        if t_sub is None:
            t_sub = time.perf_counter()  # one stamp per submit
        if not len(pubs) == len(msgs) == len(sigs):
            raise ValueError("submit: columns of unequal length")
        pubs = _bytes_column(pubs, _pub_bytes)
        msgs = _bytes_column(msgs)
        sigs = _bytes_column(sigs)
        t_keys = time.perf_counter()
        keys, native = _row_keys(pubs, msgs, sigs)
        t_probe = time.perf_counter()
        found, hits = self.cache.probe(keys)
        t_probed = time.perf_counter()
        n, pos = len(pubs), None
        if hits:
            VERIFY_E2E_SECONDS.observe_n(t_probed - t_sub, hits, path="cache")
            results = found.astype(bool).tolist()
            fresh = np.flatnonzero(found == 0)
            keys, pos = keys[fresh], fresh.tolist()
            pubs, msgs, sigs = ([col[i] for i in pos]
                                for col in (pubs, msgs, sigs))
        else:
            results = [False] * n
        group = _Group(pubs, msgs, sigs, keys, pos, results, single, t_sub)
        if group.n:
            with self._cv:
                if self._closed:
                    raise RuntimeError("verify service is closed")
                self.stats["submits"] += 1
                self.stats["submitted"] += group.n
                self._queue.append(group)
                self.stats["queue_depth"] += group.n
                self._ensure_worker_locked()
                self._cv.notify()
        else:
            group.land(0, [])  # every row hit (or none was given): resolved
        if _trace.enabled():
            sid = _trace.record("verify.submit", t_sub,
                                time.perf_counter() - t_sub,
                                n=n, fresh=n - hits, hits=hits)
            # one child per bulk pass, never per row
            _trace.record("verify.submit.keys", t_keys, t_probe - t_keys,
                          parent=sid, n=n, native=int(native))
            _trace.record("verify.submit.probe", t_probe, t_probed - t_probe,
                          parent=sid, n=n, hits=hits)
        return group

    def verify_many(self, items) -> list[bool]:
        """Sync convenience wrapper: submit, wait for the submit's one
        future.  Blocks only on verification work the host path could
        also perform — never on device warmup (the worker routes around
        a cold or wedged device)."""
        return self._wait(self._submit_items(items))

    def verify_columns(self, pubs, msgs, sigs) -> list[bool]:
        """`verify_many` over three columns (see `submit_columns`)."""
        return self._wait(self._submit(pubs, msgs, sigs))

    @staticmethod
    def _wait(group: _Group) -> list[bool]:
        # the caller sleeps until the flush that lands the group's last
        # row has resolved it: what of this span lies past that flush's
        # `verify.resolve` is the caller's wake-up alone
        with _trace.span("verify.wait", n=len(group.results)):
            return group.future.result()

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    # -- worker -------------------------------------------------------

    def _ensure_worker_locked(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._run, daemon=True, name="tm-verify-service")
            self._worker.start()

    def _flush_rung(self) -> int:
        """Stop lingering once the queue can fill a device-worthy bucket:
        the smallest `_bucket` rung at/above the dispatch threshold (64
        while the threshold is unmeasured or on a host-only service)."""
        target = 64
        bv = self._jax_bv
        if bv is not None:
            thr = bv.cpu_threshold
            if thr is None:
                thr = _batch.measured_cpu_threshold_ready()
            if thr is not None:
                target = max(64, min(MAX_COALESCE, thr))
        try:
            from tendermint_tpu.ops.ed25519_jax import _bucket

            return min(MAX_COALESCE, _bucket(target))
        except Exception:  # noqa: BLE001
            return target

    def _collect(self, block: bool) -> "_Batch | None":
        """Take the next coalesced batch off the queue: wait (if `block`)
        for the first group, then linger until the queued rows fill the
        rung or the deadline passes.  Groups are taken whole while they
        fit; one wider than what the flush has left is cut and stays at
        the head of the queue for the next flush."""
        with self._cv:
            if block:
                while not self._queue and not self._closed:
                    self._cv.wait()
            if not self._queue:
                return None
            t_linger0 = time.perf_counter()
            if self.linger_s > 0:
                rung = self._flush_rung()
                deadline = time.monotonic() + self.linger_s
                while (self.stats["queue_depth"] < rung
                       and not self._closed):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cv.wait(remaining)
            segs, room = [], MAX_COALESCE
            while self._queue and room:
                group = self._queue[0]
                start = group.taken
                end = group.taken = min(group.n, start + room)
                segs.append((group, start, end))
                room -= end - start
                if end == group.n:
                    self._queue.popleft()
            n = MAX_COALESCE - room
            self.stats["queue_depth"] -= n
            # counter updates stay inside the lock so service_stats()
            # snapshots are never torn across a flush boundary
            self.stats["flushes"] += 1
            self.stats["coalesced_max"] = max(self.stats["coalesced_max"], n)
            flush = self._flush_no = self.stats["flushes"]  # tmsan: shared=written by the worker thread only
        now = time.perf_counter()
        if _trace.enabled():
            # oldest_submit_ns == the t0_ns of the `verify.submit` span
            # that queued the batch's first group (same float, same
            # rounding): the tie between a caller's spans and a flush
            _trace.record("verify.coalesce", t_linger0, now - t_linger0,
                          n=n, groups=len(segs), flush=flush,
                          oldest_submit_ns=int(segs[0][0].t_submit * 1e9))
        # accounting on the worker, the batch already taken and not yet
        # routed (the device idles under this span): one observe per
        # segment, counted once per row — every row of a submit waited
        # the same time
        with _trace.span("verify.account", n=n, groups=len(segs), flush=flush):
            VERIFY_LINGER_SECONDS.observe(now - t_linger0)
            for group, start, end in segs:
                VERIFY_QUEUE_WAIT_SECONDS.observe_n(now - group.t_submit,
                                                    end - start)
        return _Batch(segs)

    def _run(self) -> None:
        # in-flight device batches awaiting verdict readback:
        # (pending_device_value, batch, ...).  Depth 2 = double buffering
        # — batch i executes on device while batch i+1 is host-prepped
        # and enqueued behind it.
        inflight: deque = deque()
        while True:
            with self._cv:
                if self._closed and not self._queue and not inflight:
                    return
                queue_empty = not self._queue
            if inflight and queue_empty:
                self._drain_one(inflight)
                continue
            batch = self._collect(block=not inflight)
            if batch is not None:
                try:
                    self._flush(batch, inflight)
                except BaseException as e:  # noqa: BLE001
                    self._resolve_failed(batch, e)
            while len(inflight) >= 2:
                self._drain_one(inflight)

    def _flush(self, batch: _Batch, inflight: deque) -> None:
        """Route one coalesced batch: host below threshold / before
        device readiness; async device enqueue otherwise.  The flush
        span records which path won and WHY (the question the raw
        counters could never answer)."""
        t0 = time.perf_counter()
        path, reason = self._route(batch, inflight)
        self.last_route = (path, reason)  # tmsan: shared=atomic tuple rebind, last-write-wins diagnostic
        if _trace.enabled():
            _trace.record("verify.flush", t0, time.perf_counter() - t0,
                          path=path, reason=reason, n=len(batch),
                          flush=self._flush_no)

    def _route(self, batch: _Batch, inflight: deque) -> tuple[str, str]:
        n = len(batch)
        bv = self._jax_bv
        if bv is None:
            self._host_verify(batch)
            return "host", "no_jax"
        thr = bv._resolved_threshold(n)
        if n < thr:
            self._host_verify(batch)
            return "host", "below_threshold"
        if not _batch._DEVICE_READY.is_set():
            # identical degradation to JAXBatchVerifier._ed_batch: kick
            # the warmup worker, verify on host meanwhile — device init
            # must never block a submitter
            _batch.start_device_warmup()
            self._host_verify(batch)
            return "host", "device_not_ready"
        mixed = any(len(p) != 32 for p in batch.pubs)
        if mixed:
            # the rarer shape (a secp-mixed batch) runs the existing
            # synchronous routing — bit-identical verdicts, no pipelining
            self._sync_device_verify(batch, bv)
            return "device", "sync_routing"
        ndev = bv._device_count()
        if ndev > 1:
            if not _mesh.dispatcher_enabled():
                # TM_TPU_MESH=0: legacy synchronous mesh routing
                self._sync_device_verify(batch, bv)
                return "device", "sync_routing"
            route, m = _mesh.decide(n, ndev)
            if route == "sharded":
                try:
                    self._enqueue_sharded(batch, inflight, m)
                    return "device", "mesh_sharded"
                except Exception:  # noqa: BLE001 — mesh failure: host
                    self._device_error("enqueue_sharded", n)
                    self._host_verify(batch)
                    return "host", "device_error"
            # pinned: fall through to the single-chip pipelined enqueue
            # below — identical programs/cache keys to a 1-device node
        try:
            self._enqueue_device(batch, inflight)
            if ndev > 1:
                with self._cv:
                    self.stats["mesh_pinned_batches"] += 1
                return "device", "mesh_pinned"
            return "device", "pipelined"
        except Exception:  # noqa: BLE001 — device failure: host fallback
            self._device_error("enqueue", n)
            self._host_verify(batch)
            return "host", "device_error"

    def _device_error(self, site: str, n: int) -> None:
        """A device program failed on a ready device and the flush is
        about to resolve with host verdicts (the liveness contract).
        Call from the `except` block: counts the event and logs the
        active exception with its traceback once per site — consensus
        keeps running, but the failure is never silent."""
        with self._cv:
            self.stats["device_errors"] += 1
            first = site not in self._logged_error_sites
            self._logged_error_sites.add(site)
        if first:
            _log.warning("device verify failed at %s (n=%d); flush "
                         "resolved on the host path — further failures "
                         "at this site are counted in device_errors "
                         "only", site, n, exc_info=True)

    def _enqueue_device(self, batch: _Batch, inflight: deque) -> None:
        """Host prep + async enqueue of the per-row device program at
        the flush's rung.  Verdict readback happens in _drain_one — by
        then the worker has already host-prepped the NEXT batch behind
        the executing one."""
        from tendermint_tpu.ops import ed25519_jax as dev

        n = len(batch)
        flush = self._flush_no
        impl = dev.default_impl()
        b = dev._bucket(n)
        padded = self._host_prep(batch, b, flush)
        if _devmon.STATS.enabled:
            _mesh.record_pinned_flush(
                n, b, nbytes=sum(a.nbytes for a in padded))
        while len(inflight) >= 2:
            self._drain_one(inflight)
        pending, t_enq = self._launch(dev._compiled(b, impl), padded,
                                      flush, b, chips=1)
        inflight.append((pending, batch, t_enq, b, flush))
        with self._cv:
            self.stats["device_batches"] += 1

    @staticmethod
    def _host_prep(batch: _Batch, b: int, flush: int) -> tuple:
        """The flush's device inputs padded to rung `b`: `prepare_batch`
        and `_pad_rows`, timed as one `verify_host_prep_seconds` observe
        and one `verify.host_prep` span; with spans on, its steps hang
        under it as children (`verify.host_prep.rows`, `.canon`, `.msgs`,
        `.k`, `.pad`), each with the flush's number."""
        from tendermint_tpu.ops import ed25519_jax as dev

        n = len(batch)
        steps = [] if _trace.enabled() else None
        t_prep = time.perf_counter()
        rows = dev.prepare_batch(batch.pubs, batch.msgs, batch.sigs, sink=steps)
        t_pad = time.perf_counter() if steps is not None else 0.0
        padded = dev._pad_rows(n, b, *rows)
        t_end = time.perf_counter()
        prep_dt = t_end - t_prep
        VERIFY_HOST_PREP_SECONDS.observe(prep_dt)
        if steps is not None:
            sid = _trace.record("verify.host_prep", t_prep, prep_dt,
                                n=n, rung=b, flush=flush)
            steps.append(("verify.host_prep.pad", t_pad, t_end - t_pad,
                          {"rung": b, "pad_rows": b - n}))
            for name, t0, dur, attrs in steps:
                _trace.record(name, t0, dur, parent=sid, flush=flush, **attrs)
        return padded

    @staticmethod
    def _launch(program, args: tuple, flush: int, rung: int,
                chips: int) -> tuple:
        """`program(*args)` and the time it was called: the enqueue of a
        flush, returning before the device is done.  With spans on, a
        `verify.launch` span (`flush`, `rung`, `chips`) covers the call,
        and the profiler's host plane holds the same call as a
        `verify.launch` annotation with the flush's number, on its own
        clock."""
        t_enq = time.perf_counter()
        if not _trace.enabled():
            return program(*args), t_enq
        import jax

        with jax.profiler.TraceAnnotation("verify.launch", flush=flush):
            pending = program(*args)
        _trace.record("verify.launch", t_enq, time.perf_counter() - t_enq,
                      flush=flush, rung=rung, chips=chips)
        return pending, t_enq

    def _enqueue_sharded(self, batch: _Batch, inflight: deque,
                         m: int) -> None:
        """Host prep + async enqueue of the SHARDED per-row program over
        an m-device mesh: rows are padded to a device-multiple rung and
        pre-partitioned (jax.device_put against the mesh NamedSharding)
        so XLA never reshards.  Readback stays in _drain_one — the
        double-buffered pipeline is preserved across the mesh hop."""
        from tendermint_tpu.ops import ed25519_jax as dev
        from tendermint_tpu.parallel import sharding as _sh

        mesh = _mesh.mesh_for(m)
        n = len(batch)
        flush = self._flush_no
        b = _sh.sharded_bucket(n, m)
        padded = self._host_prep(batch, b, flush)
        if _devmon.STATS.enabled:
            _mesh.record_sharded_flush(
                n, b, mesh, nbytes=sum(a.nbytes for a in padded))
        while len(inflight) >= 2:
            self._drain_one(inflight)
        pending, t_enq = self._launch(_mesh.enqueue_sharded, (mesh, padded),
                                      flush, b, chips=m)
        self.last_shard_layout = tuple(  # tmsan: shared=atomic tuple rebind, last-write-wins diagnostic
            (int(s.device.id), int(s.data.shape[0]))
            for s in pending.addressable_shards)
        inflight.append((pending, batch, t_enq, b, flush))
        with self._cv:
            self.stats["device_batches"] += 1
            self.stats["mesh_sharded_batches"] += 1

    def _drain_one(self, inflight: deque) -> None:
        import numpy as np

        pending, batch, t_enq, rung, flush = inflight.popleft()
        # the worker turns to an older flush, possibly in the middle of
        # enqueueing a newer one: spans below carry the drained number
        routing, self._flush_no = self._flush_no, flush  # tmsan: shared=written by the worker thread only
        with self._cv:
            self.stats["pipelined_drains"] += 1
        try:
            oks = np.asarray(pending)[:len(batch)].tolist()
        except Exception:  # noqa: BLE001 — readback failed: host verdicts
            self._device_error("readback", len(batch))
            self._host_verify(batch, count_flush=False)
        else:
            dt = time.perf_counter() - t_enq
            VERIFY_DEVICE_EXECUTE_SECONDS.observe(dt, rung=rung)
            if _trace.enabled():
                # enqueue-to-readback: includes time queued behind the
                # other in-flight batch, i.e. what a submitter actually
                # experiences
                _trace.record("verify.device_execute", t_enq, dt,
                              n=len(batch), rung=rung, flush=flush)
            self._resolve(batch, oks, path="device")
        self._flush_no = routing  # tmsan: shared=written by the worker thread only

    def _sync_device_verify(self, batch: _Batch, bv) -> None:
        t0 = time.perf_counter()
        try:
            oks = _verify_rows(batch, bv._ed_batch)
            with self._cv:
                self.stats["device_batches"] += 1
        except Exception:  # noqa: BLE001 — device failure: host verdicts
            self._device_error("sync", len(batch))
            self._host_verify(batch)
            return
        dt = time.perf_counter() - t0
        VERIFY_DEVICE_EXECUTE_SECONDS.observe(dt, rung="sync")
        if _trace.enabled():
            _trace.record("verify.device_execute", t0, dt,
                          n=len(batch), rung="sync", flush=self._flush_no)
        self._resolve(batch, oks, path="device")

    def _host_verify(self, batch: _Batch, count_flush: bool = True) -> None:
        if count_flush:
            with self._cv:
                self.stats["host_flushes"] += 1
        t0 = time.perf_counter()
        try:
            oks = _verify_rows(batch, _ed.verify_batch_fast)
        except BaseException as e:  # noqa: BLE001
            self._resolve_failed(batch, e)
            return
        if _trace.enabled():
            _trace.record("verify.host_verify", t0,
                          time.perf_counter() - t0, n=len(batch),
                          flush=self._flush_no)
        self._resolve(batch, oks, path="host")

    def _resolve(self, batch: _Batch, oks: list, path: str = "host") -> None:
        """Account and land one batch's verdicts (`oks`: one bool per
        row): the end-to-end observe once per segment with its row
        count, then the bulk part in `_land`."""
        now = time.perf_counter()
        with _trace.span("verify.resolve", n=len(batch),
                         groups=len(batch.segs), path=path,
                         flush=self._flush_no):
            for group, start, end in batch.segs:
                VERIFY_E2E_SECONDS.observe_n(now - group.t_submit,
                                             end - start, path=path)
            self._land(batch.segs, oks)

    def _land(self, segs: list, oks: list) -> None:
        """Put the valid rows' keys with ONE bulk cache put — the
        segments' key slices and the verdicts as its mask: only True
        rows, as the cache promises; before any future resolves, so a
        caller that wakes finds its rows cached — then hand each segment
        its slice of the verdicts; a group whose last row lands
        resolves."""
        with _trace.span("verify.cache_put", n=len(oks),
                         flush=self._flush_no) as sp:
            if len(segs) == 1:
                g, a, b = segs[0]
                keys = g.keys[a:b]
            else:
                keys = np.concatenate([g.keys[a:b] for g, a, b in segs])
            sp.set(stored=self.cache.put_many(keys, bytes(oks)))
        at = 0
        for group, start, end in segs:
            group.land(start, oks[at:at + end - start])
            at += end - start

    def _resolve_failed(self, batch: _Batch, err: BaseException) -> None:
        """Catastrophic path: even the batched host verify raised.  Fall
        back to per-item verification, a segment at a time, so one
        poisoned row cannot take the whole flush down; a segment with a
        row that still fails propagates the error to its group's
        submitter (same contract as the sync path, which would have
        raised to the caller)."""
        for seg in batch.segs:
            group, start, end = seg
            try:
                oks = [bool(_ed.verify_fast(p, m, s)) for p, m, s in zip(
                    group.pubs[start:end], group.msgs[start:end],
                    group.sigs[start:end])]
            except BaseException:  # noqa: BLE001
                group.fail(err)
            else:
                self._land([seg], oks)


def _verify_rows(batch: _Batch, ed_batch_fn) -> list[bool]:
    return list(map(bool, _split_verify(batch.pubs, batch.msgs, batch.sigs,
                                        ed_batch_fn)))


class ServiceBatchVerifier(_BaseBatch):
    """BatchVerifier-protocol adapter over the shared service: existing
    call sites keep their add/count/verify shape, but the actual crypto
    is submitted to the cross-caller queue — concurrent verifiers'
    batches coalesce into one device dispatch, and duplicates resolve
    from the verified-signature cache.  The rows are kept as three
    columns and handed to the service's column entry as they are."""

    def __init__(self, service: "VerifyService | None" = None):
        super().__init__()
        self._svc = service or get_service()

    def add_many(self, pubs, msgs, sigs) -> None:
        # no type test here: the service's column entry makes it
        self._extend(pubs, msgs, sigs)

    def verify(self) -> tuple[bool, list[bool]]:
        pubs, msgs, sigs = self._take()
        if not pubs:
            return False, []
        oks = self._svc.verify_columns(pubs, msgs, sigs)
        return all(oks), oks


# ---------------------------------------------------------------------------
# Process-wide singleton
# ---------------------------------------------------------------------------

_SERVICE: VerifyService | None = None
_SERVICE_LOCK = threading.Lock()


def service_enabled() -> bool:
    """TM_TPU_ASYNC_VERIFY gates the routing of the framework's verify
    surfaces through the service (default on); resolved per call so
    tests/benches can flip it."""
    return os.environ.get("TM_TPU_ASYNC_VERIFY", "1") != "0"


def get_service() -> VerifyService:
    global _SERVICE
    svc = _SERVICE
    if svc is not None:
        return svc
    with _SERVICE_LOCK:
        if _SERVICE is None:
            _SERVICE = VerifyService()
        return _SERVICE


def reset_service(**kwargs) -> VerifyService:
    """Replace the singleton (tests/benchmarks): closes the old worker
    and builds a fresh service with the given constructor overrides."""
    global _SERVICE
    with _SERVICE_LOCK:
        if _SERVICE is not None:
            _SERVICE.close()
        _SERVICE = VerifyService(**kwargs)
        return _SERVICE


def clear_service() -> None:
    """Drop the singleton entirely so the NEXT get_service() rebuilds it
    from the then-current environment.  Test isolation: the service
    captures TM_TPU_CPU_THRESHOLD / linger / cache sizing at
    construction, so a singleton built by an earlier test would silently
    override a later test's env (the order-dependent multinode
    device-path flake)."""
    global _SERVICE
    with _SERVICE_LOCK:
        if _SERVICE is not None:
            _SERVICE.close()
        _SERVICE = None


def verify_many(items) -> list[bool]:
    """Module-level sync wrapper over the shared service."""
    return get_service().verify_many(items)


def submit(pub, msg: bytes, sig: bytes) -> Future:
    return get_service().submit(pub, msg, sig)


def verify_one(pub, msg: bytes, sig: bytes, fill: bool = True) -> bool:
    """ONE signature against the shared verified-sig LRU: probe, else
    verify on the caller's thread and (only on success, and only when
    `fill`) populate the cache.  The single-signature admission paths
    (VoteSet.add_vote for own/broadcast-delivered votes, proposal
    signature checks) were the last verify surfaces still paying a full
    scalar-mult per CALLER per signature — an in-process multi-node net
    (simnet, test localnets) re-verified every broadcast vote once per
    node.  Deliberately NOT submitted to the service queue: a single
    must not perturb the worker's flush/coalescing behavior (threshold
    routing, linger) nor block on the linger window — the cache is the
    only shared state touched.

    `fill=False` (the vote path) probes without populating: votes are
    ALSO verified through the batched service path (precheck slices),
    and a cache pre-filled by trickling singles would starve those
    flushes of fresh work — the device batch path would never engage on
    a quiet net.  Slice-verified votes fill the cache through the
    service as before; the probe here then serves every later caller.
    TM_TPU_ASYNC_VERIFY=0 keeps even the cache out of the path."""
    if not service_enabled():
        return bool(pub.verify_signature(msg, sig))
    cache = get_service().cache
    key = VerifiedSigCache.key(_pub_bytes(pub), bytes(msg), bytes(sig))
    if cache.get(key):
        return True
    ok = bool(pub.verify_signature(msg, sig))
    if ok and fill:
        cache.put(key)
    return ok


def service_stats() -> dict:
    """Counters for metrics/bench scraping; zeros before first use (the
    metrics server must not instantiate the service).  The service
    counters are snapshotted under the service lock and the cache
    counters under the cache lock, so a scrape never observes a torn
    counter set (e.g. a flush counted but its coalesced_max not yet)."""
    svc = _SERVICE
    if svc is None:
        return {"submits": 0, "submitted": 0, "flushes": 0, "host_flushes": 0,
                "device_batches": 0, "coalesced_max": 0,
                "pipelined_drains": 0, "mesh_pinned_batches": 0,
                "mesh_sharded_batches": 0, "device_errors": 0,
                "cache_hits": 0,
                "cache_misses": 0, "cache_size": 0, "queue_depth": 0}
    with svc._cv:
        out = dict(svc.stats)
    cache = svc.cache
    with cache._lock:
        out["cache_hits"] = cache.hits
        out["cache_misses"] = cache.misses
        out["cache_size"] = len(cache)
    return out


def device_stats() -> dict:
    """Device-layer snapshot next to service_stats(): utils/devmon's
    compile/occupancy/padding/memory accounting folded together with the
    service's live queue depth and verified-signature cache hit ratio —
    one call answers "how efficiently is the device being used right
    now".  Like service_stats(), never instantiates the service."""
    out = _devmon.device_stats()
    st = service_stats()
    lookups = st["cache_hits"] + st["cache_misses"]
    out["queue_depth"] = st["queue_depth"]
    out["cache_hit_ratio"] = (round(st["cache_hits"] / lookups, 6)
                              if lookups else 0.0)
    return out


def new_service_batch_verifier():
    """A BatchVerifier routed through the shared service when enabled,
    else a plain per-caller verifier — THE constructor every verify
    surface (vote slices, commit windows, evidence) should use."""
    if service_enabled():
        return ServiceBatchVerifier()
    return _batch.new_batch_verifier()
