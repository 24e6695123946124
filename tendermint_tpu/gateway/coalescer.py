"""Cross-client verify coalescer: single-flight dedup + linger-window
batching of light-client commit-verify jobs.

The async verification service (crypto/async_verify.py) already
coalesces raw SIGNATURES across callers — but only after each caller
has paid sign-bytes assembly, and only once per distinct (pub, msg,
sig) per cache generation: 100 clients syncing the same chain
concurrently all submit the same signatures BEFORE the first flush
resolves, so the verified-sig LRU never gets a chance to dedup them and
the device sees clients×blocks work.  This module is the missing level:
dedup at the JOB level (one commit at one height), before any
per-signature work happens.

  * `verify_jobs(jobs)` has the exact contract of
    `types.validator.batch_verify_commits` (raises ValueError naming
    the first failing height) so it drops into the light verifier's
    `verify_fn` seam unchanged.
  * A flush is ONE verify call (`types.validator.commit_job_outcomes`)
    and every job's future resolves from its OWN outcome: a refused
    header costs the clients that shared its flush nothing — no second
    verification, no row submitted twice.
  * Jobs are keyed by (chain_id, height, mode, block hash, commit
    digest).  The FIRST submitter of a key owns it; every concurrent
    duplicate — a different client verifying the same height — waits on
    the owner's future instead of submitting again.  Keys stay
    registered until their flush resolves, so the dedup window covers
    the whole in-flight period, not just the queue.
  * A linger window (`TM_TPU_GATEWAY_LINGER_MS`, default 2 ms) lets
    distinct heights from many clients merge into ONE
    batch_verify_commits flush — the PR 1 cross-caller micro-batching
    trick one level up, so device flushes scale with DISTINCT heights,
    not clients×blocks.  A flush is cut by ROWS as blocksync's window is
    (`blocksync.reactor.window_jobs`): the leading jobs that, each
    counted at its commit's signature count, fit one flush of the verify
    service (`MAX_COALESCE`), never fewer than one; the rest head the
    next flush.
  * Graceful degradation: when `shed_fn()` reports a non-zero level
    (wired to the remediation controller's verify-queue-saturation
    shed level), submissions raise `GatewayBackpressureError` with a
    retry hint instead of queueing — consensus keeps the device, read
    clients get a structured signal, and the remediation journal
    records the shed.

Thread model: client threads call `verify_jobs`/`submit_jobs`; one
daemon worker drains the queue and runs the flush (which itself blocks
on the async-verify service).  All shared state lives under one
condition variable.

Spans (utils/trace, off = one branch a site; docs/observability.md):
`gateway.submit` and `gateway.wait` on the client's thread,
`gateway.linger`, `gateway.flush` (the parent of that flush's
`commit.*` / `verify.submit` / `verify.wait`) and `gateway.resolve` on
the worker; `flush` is this coalescer's sequence number and ties a
client's wait to the flush that answered it.  Series beside the
`tendermint_gateway_*` counters: `GATEWAY_SERIES` below.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import deque
from concurrent.futures import Future

from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.utils.metrics import Counter, Histogram

from .errors import GatewayBackpressureError

DEFAULT_LINGER_MS = 2.0

# Observed by the worker, once a flush (process-wide; registered by
# node/metrics.py beside the tendermint_gateway_* callback series).
FLUSH_JOBS = Histogram(
    "flush_jobs", "Commit-verify jobs one coalesced gateway flush carried",
    namespace="tendermint", subsystem="gateway",
    buckets=(1, 2, 3, 4, 5, 6, 8, 12, 16, 24, 32, 64, 128))
JOB_WAIT_SECONDS = Histogram(
    "job_wait_seconds",
    "Time a job waited in the gateway for its flush: submit to the start "
    "of the verify call that carries it (queue and linger)",
    namespace="tendermint", subsystem="gateway",
    buckets=(0.00025, 0.0005, 0.001, 0.002, 0.003, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 1.0))
REFUSED_JOBS_TOTAL = Counter(
    "refused_jobs_total",
    "Jobs whose own verification failed (every other job of the flush "
    "was answered by the same verify call)",
    namespace="tendermint", subsystem="gateway")
GATEWAY_SERIES = (FLUSH_JOBS, JOB_WAIT_SECONDS, REFUSED_JOBS_TOTAL)


def _commit_digest(commit) -> bytes:
    """Digest of the exact signature set, memoized on the commit object
    (commits are immutable once decoded, and the gateway's response
    cache hands ONE object to N clients — the digest is computed once
    per commit per process, not once per client per height).  Raw
    signature bytes are hashed directly instead of proto-encoding the
    whole commit: same discriminating power over the verdict-relevant
    content at a fraction of the per-job cost."""
    d = getattr(commit, "_gw_digest", None)
    if d is None:
        h = hashlib.sha256()
        h.update(commit.round.to_bytes(4, "big", signed=True))
        for cs in commit.signatures:
            h.update(bytes([int(cs.block_id_flag)]))
            h.update(cs.signature or b"")
        d = h.digest()
        try:
            commit._gw_digest = d
        except AttributeError:   # slotted commit type: recompute per call
            pass
    return d


def job_key(job) -> tuple:
    """Identity of one commit-verify job.  The block hash commits to
    the header (and through it the validator-set hash); the commit
    digest covers the exact signature set, so two providers serving
    different commits for the same block never share a verdict."""
    return (job.chain_id, job.height, job.mode,
            bytes(job.block_id.hash), _commit_digest(job.commit))


class _Entry:
    __slots__ = ("key", "job", "future", "t_submit", "rows", "flush")

    def __init__(self, key, job, t_submit: float):
        self.key = key
        self.job = job
        self.future: Future = Future()
        self.t_submit = t_submit
        # what the job counts for when a flush is cut: its commit's
        # signature count, as blocksync's window counts a block
        self.rows = len(job.commit.signatures)
        self.flush = None   # the sequence number of the flush that took it


def _raise_only_outcomes(verify, jobs) -> list:
    """`commit_job_outcomes` from a verifier of the raise-only contract
    (`batch_verify_commits`' own; what an injected `verify_fn` has): it
    names the FIRST failure and says nothing of the rest, so a refused
    batch is verified again job by job."""
    try:
        verify(jobs)
        return [None] * len(jobs)
    except BaseException:  # noqa: BLE001 — isolate per job below
        pass
    outcomes = []
    for job in jobs:
        try:
            verify([job])
            outcomes.append(None)
        except BaseException as err:  # noqa: BLE001
            outcomes.append(err)
    return outcomes


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class VerifyCoalescer:
    """The gateway's cross-client verify funnel; see the module
    docstring.  A flush goes through types.validator's
    commit_job_outcomes: one verify call, an outcome a job.  `verify_fn`
    (injectable for tests) is a verifier of batch_verify_commits'
    raise-only contract; a flush it refuses is verified again job by job
    (`_raise_only_outcomes`)."""

    def __init__(self, *, linger_ms: float | None = None,
                 verify_fn=None, shed_fn=None, remediate=None,
                 retry_after_ms: int = 1000):
        self.linger_s = (linger_ms if linger_ms is not None
                         else _env_float("TM_TPU_GATEWAY_LINGER_MS",
                                         DEFAULT_LINGER_MS)) / 1e3
        self._verify_fn = verify_fn
        self._shed_fn = shed_fn
        self._remediate = remediate
        self.retry_after_ms = int(retry_after_ms)
        self._cv = threading.Condition()
        self._pending: dict[tuple, _Entry] = {}   # queued OR in-flight
        self._queue: deque[_Entry] = deque()
        self._queued_rows = 0    # sum of the queued entries' `rows`
        self._flush_seq = 0
        self._worker: threading.Thread | None = None
        self._closed = False
        self.stats = {
            "verify_jobs": 0,        # jobs submitted (incl. coalesced)
            "verify_coalesced": 0,   # jobs that joined an in-flight twin
            "verify_flushed_jobs": 0,  # distinct jobs actually verified
            "verify_flushes": 0,     # batch_verify_commits calls
            "shed": 0,               # jobs rejected by backpressure
        }

    # -- submission (client threads) ------------------------------------

    def shed_level(self) -> int:
        if self._shed_fn is None:
            return 0
        try:
            return int(self._shed_fn())
        except Exception:  # noqa: BLE001 — a broken probe must not shed
            return 0

    def submit_jobs(self, jobs) -> list[Future]:
        """Queue jobs for coalesced verification; never blocks.  Each
        future resolves to True or raises the job's verification error.
        Raises GatewayBackpressureError immediately under shed."""
        return [e.future for e in self._submit(jobs)]

    def _submit(self, jobs) -> list[_Entry]:
        level = self.shed_level()
        if level > 0:
            rm = self._remediate
            with self._cv:
                self.stats["shed"] += len(jobs)
            if rm is not None and rm.enabled:
                rm.record("gateway_shed",
                          f"{len(jobs)} read-path verify jobs shed at "
                          f"level {level}")
            raise GatewayBackpressureError(level, self.retry_after_ms)
        t_sub = time.perf_counter()
        entries: list[_Entry] = []
        with _trace.span("gateway.submit", jobs=len(jobs)) as sp:
            keys = [job_key(job) for job in jobs]
            joined = 0
            with self._cv:
                if self._closed:
                    raise RuntimeError("gateway coalescer is closed")
                self.stats["verify_jobs"] += len(jobs)
                for key, job in zip(keys, jobs):
                    entry = self._pending.get(key)
                    if entry is not None:
                        # single-flight: another client already owns this
                        # exact job (queued or mid-flush) — share its verdict
                        joined += 1
                    else:
                        entry = _Entry(key, job, t_sub)
                        self._pending[key] = entry
                        self._queue.append(entry)
                        self._queued_rows += entry.rows
                    entries.append(entry)
                self.stats["verify_coalesced"] += joined
                self._ensure_worker_locked()
                self._cv.notify()
            sp.set(joined=joined)
        return entries

    def verify_jobs(self, jobs) -> None:
        """batch_verify_commits-compatible surface: submit, wait, raise
        the first failure.  This is what a light client's `verify_fn` /
        `commit_verifier` seam points at."""
        if not jobs:
            return
        entries = self._submit(list(jobs))
        with _trace.span("gateway.wait", jobs=len(entries)) as sp:
            e = entries[0]
            try:
                for e in entries:
                    e.future.result()   # re-raises the job's own error
            finally:
                sp.set(flush=e.flush)   # of the job last waited for

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    # -- worker ----------------------------------------------------------

    def _ensure_worker_locked(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._run, daemon=True, name="tm-gateway-coalescer")
            self._worker.start()

    def _run(self) -> None:
        # the most rows one flush of the verify service holds (its top
        # rung); read when the worker starts, as the service reads it
        from tendermint_tpu.crypto.async_verify import MAX_COALESCE as max_rows

        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if not self._queue:
                    return   # closed and drained
                t_first = time.perf_counter()
                if self.linger_s > 0:
                    # linger so concurrent clients' distinct heights
                    # merge into one flush
                    deadline = time.monotonic() + self.linger_s
                    while self._queued_rows < max_rows and not self._closed:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cv.wait(remaining)
                # the cut, as blocksync.reactor.window_jobs makes it: the
                # leading jobs whose counted rows fit one flush, never
                # fewer than one; the rest head the next flush
                batch = [self._queue.popleft()]
                rows = batch[0].rows
                while self._queue and rows + self._queue[0].rows <= max_rows:
                    rows += self._queue[0].rows
                    batch.append(self._queue.popleft())
                self._queued_rows -= rows
                self._flush_seq += 1
                seq = self._flush_seq
                for e in batch:
                    e.flush = seq
                self.stats["verify_flushes"] += 1
                self.stats["verify_flushed_jobs"] += len(batch)
            _trace.record("gateway.linger", t_first,
                          time.perf_counter() - t_first, flush=seq,
                          jobs=len(batch))
            self._flush(batch, seq, rows)

    def _outcomes(self, jobs) -> list:
        if self._verify_fn is not None:
            return _raise_only_outcomes(self._verify_fn, jobs)
        from tendermint_tpu.types.validator import commit_job_outcomes

        return commit_job_outcomes(jobs)

    def _flush(self, batch: list[_Entry], seq: int, rows: int) -> None:
        """One coalesced verify call; each entry's future is resolved
        from its own job's outcome, so one bad height fails only its own
        waiters.  A verifier that itself breaks fails them all."""
        t_flush = time.perf_counter()
        waits = [t_flush - e.t_submit for e in batch]
        FLUSH_JOBS.observe(len(batch))
        for w in waits:
            JOB_WAIT_SECONDS.observe(w)
        try:
            with _trace.span("gateway.flush", flush=seq, jobs=len(batch),
                             rows=rows, wait_sum_ns=int(sum(waits) * 1e9)):
                outcomes = self._outcomes([e.job for e in batch])
        except BaseException as err:  # noqa: BLE001 — the verifier broke
            outcomes = [err] * len(batch)
        # entries leave the dedup window only once their verdict is
        # decided; late duplicates fall through to the sig LRU
        with self._cv:
            for e in batch:
                self._pending.pop(e.key, None)
        refused = sum(err is not None for err in outcomes)
        with _trace.span("gateway.resolve", flush=seq, jobs=len(batch),
                         refused=refused):
            for e, err in zip(batch, outcomes):
                if err is None:
                    e.future.set_result(True)
                else:
                    e.future.set_exception(err)
        if refused:
            REFUSED_JOBS_TOTAL.inc(refused)

    # -- views -----------------------------------------------------------

    def stats_snapshot(self) -> dict:
        with self._cv:
            out = dict(self.stats)
            out["queue_depth"] = len(self._queue)
        return out

    def dedup_ratio(self) -> float:
        """Submitted jobs per job actually verified — the cross-client
        sharing factor (1.0 = no sharing)."""
        st = self.stats_snapshot()
        done = st["verify_flushed_jobs"]
        return round(st["verify_jobs"] / done, 4) if done else 0.0
