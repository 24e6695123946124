"""The cpu_threshold derivation (benchmarks/dispatch_rtt.py): the fit and
breakeven math that turns measured dispatch/per-sig costs into the
JAXBatchVerifier threshold (VERDICT r2 weak #5 — the 64 default was an
unvalidated guess; docs/performance.md now carries the measured table)."""

import sys

sys.path.insert(0, "benchmarks")

from dispatch_rtt import breakeven, fit_dispatch_model  # noqa: E402


def test_fit_recovers_linear_model():
    ns = [8, 16, 32, 64, 128, 256]
    dispatch, per_sig = 0.003, 21e-6  # 3ms dispatch, 21us/sig
    lat = [dispatch + n * per_sig for n in ns]
    d, p = fit_dispatch_model(ns, lat)
    assert abs(d - dispatch) < 1e-6
    assert abs(p - per_sig) < 1e-9


def test_breakeven_round1_tpu_scenarios():
    host = 45e-6  # libcrypto ~45us/sig
    dev = 21e-6   # round-1 measured device math
    # a remote device: ~100ms RTT -> threshold in the thousands
    be_remote = breakeven(0.100, dev, host)
    assert be_remote is not None and 3500 <= be_remote <= 5200, be_remote
    # direct-attached: ~3ms dispatch -> low hundreds
    be_direct = breakeven(0.003, dev, host)
    assert be_direct is not None and 100 <= be_direct <= 160, be_direct
    # device per-sig must UNDERCUT host or no batch size ever wins
    assert breakeven(0.001, 50e-6, host) is None


def test_breakeven_monotone_in_dispatch():
    host, dev = 45e-6, 10e-6
    bes = [breakeven(d, dev, host) for d in (0.001, 0.01, 0.1)]
    assert all(b is not None for b in bes)
    assert bes[0] < bes[1] < bes[2]


def test_default_threshold_consistent_with_direct_attach_model():
    """Since r4 the threshold is auto-MEASURED at the first >=64-sig
    batch (crypto/batch.measured_cpu_threshold); 64 survives only as the
    static floor below which the device is never touched.  This pins
    that the floor is consistent with the direct-attach model (dispatch
    ~1.5ms at round-1 device speed): batches under it could not beat the
    host even on the best-case hardware, so skipping measurement for
    them is sound."""
    host, dev = 45e-6, 21e-6
    assert breakeven(0.0015, dev, host) <= 64


def test_measured_cpu_threshold_auto(monkeypatch):
    """VERDICT r3 item 6: with no TM_TPU_CPU_THRESHOLD the breakeven is
    MEASURED from a real n=8 device round trip, clamped to [16, 16384],
    and the diagnostics record the inputs."""
    from tendermint_tpu.crypto import batch

    monkeypatch.setattr(batch, "_MEASURED_THRESHOLD", None)
    monkeypatch.setattr(batch, "_THRESHOLD_DIAG", {})
    thr = batch.measured_cpu_threshold()
    assert 16 <= thr <= 16384
    diag = batch.threshold_diagnostics()
    assert diag["threshold"] == thr
    if diag["measured"]:
        assert diag["device_rtt_ms"] > 0
        assert diag["host_us_per_sig"] > 0
    # once measured, the process-wide cache serves later verifiers
    assert batch.measured_cpu_threshold() == thr


def test_warmup_and_measurement_exceptions_are_kept_and_logged(
        monkeypatch, caplog):
    """A device that raises during warm-up or during the threshold
    measurement still degrades to the host path (not ready, static 64)
    — but the exception that prevented readiness is kept where
    threshold_diagnostics() returns it (type, message, traceback) and
    logged at warning level when it happens."""
    import logging
    import threading
    import time

    import jax

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.ops import ed25519_jax as dev

    monkeypatch.setattr(batch, "_THRESHOLD_DIAG", {})
    monkeypatch.setattr(batch, "_DEVICE_READY", threading.Event())
    monkeypatch.setattr(batch, "_WARMUP_STARTED", False)
    monkeypatch.setattr(batch, "_MEASURE_STARTED", False)
    monkeypatch.setattr(batch, "_MEASURED_THRESHOLD", None)

    def refuse(*_a, **_kw):
        raise RuntimeError("simulated compile refusal")

    monkeypatch.setattr(dev, "verify_batch", refuse)
    with caplog.at_level(logging.WARNING, logger="tendermint_tpu.crypto.batch"):
        batch.start_device_warmup()
        deadline = time.monotonic() + 10.0
        while ("warmup_error" not in batch.threshold_diagnostics()
               and time.monotonic() < deadline):
            time.sleep(0.01)
        err = batch.threshold_diagnostics()["warmup_error"]
        assert err["type"] == "RuntimeError"
        assert "simulated compile refusal" in err["message"]
        assert "Traceback" in err["traceback"]
        assert not batch.device_ready()

        # the measurement path, as a real accelerator takes it
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert batch.measured_cpu_threshold() == 64
        diag = batch.threshold_diagnostics()
        assert diag["measured"] is False and diag["threshold"] == 64
        assert diag["error"]["type"] == "RuntimeError"
        assert "simulated compile refusal" in diag["error"]["traceback"]
        assert not batch.device_ready()
    msgs = [r.getMessage() for r in caplog.records]
    assert any("warm-up failed" in m for m in msgs), msgs
    assert any("threshold measurement failed" in m for m in msgs), msgs


def test_cpu_threshold_env_override_wins(monkeypatch):
    from tendermint_tpu.crypto import batch

    monkeypatch.setenv("TM_TPU_CPU_THRESHOLD", "777")
    v = batch.JAXBatchVerifier()
    assert v.cpu_threshold == 777


def test_cpu_threshold_malformed_env_defers(monkeypatch):
    """Malformed env defers to lazy measurement with a warning.  The
    env is parsed at RESOLUTION (first cpu_threshold read), not at
    construction, and the warning fires once per distinct raw value."""
    from tendermint_tpu.crypto import batch

    monkeypatch.setenv("TM_TPU_CPU_THRESHOLD", "not-a-number")
    monkeypatch.setattr(batch, "_ENV_THRESHOLD_MEMO", None)
    import warnings

    v = batch.JAXBatchVerifier()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert v.cpu_threshold is None  # deferred to lazy measurement
        assert v.cpu_threshold is None  # memoized: no second warning
    assert sum("TM_TPU_CPU_THRESHOLD" in str(x.message) for x in w) == 1


def test_cpu_threshold_env_set_after_construction_wins(monkeypatch):
    """The root cause of the order-dependent test_multinode flake: a
    verifier (or the process-wide service singleton) built BEFORE a
    test monkeypatched TM_TPU_CPU_THRESHOLD kept the construction-time
    value.  The env pin is now re-read at every resolution, so a stale
    instance honors the current environment; an explicit ctor pin
    still wins over the env."""
    from tendermint_tpu.crypto import batch

    monkeypatch.delenv("TM_TPU_CPU_THRESHOLD", raising=False)
    monkeypatch.setattr(batch, "_ENV_THRESHOLD_MEMO", None)
    v = batch.JAXBatchVerifier()          # built under the default env
    monkeypatch.setenv("TM_TPU_CPU_THRESHOLD", "2")
    assert v.cpu_threshold == 2           # late env takes effect
    assert v._resolved_threshold(3) == 2  # ...and routes dispatch
    monkeypatch.setenv("TM_TPU_CPU_THRESHOLD", "auto")
    assert v.cpu_threshold is None        # back to lazy measurement

    pinned = batch.JAXBatchVerifier(cpu_threshold=8)
    monkeypatch.setenv("TM_TPU_CPU_THRESHOLD", "2")
    assert pinned.cpu_threshold == 8      # explicit pin beats env


def test_cpu_threshold_lazy_resolution(monkeypatch):
    """Deferred threshold (r5 shape, VERDICT r4 item 5): sub-floor
    batches resolve to the static 64 without touching the device; the
    first >=64 batch kicks the measurement on a WORKER thread and itself
    routes to the host path (n+1); once the worker resolves, the
    instance pins the measured value."""
    import threading

    from tendermint_tpu.crypto import batch

    monkeypatch.delenv("TM_TPU_CPU_THRESHOLD", raising=False)
    monkeypatch.setattr(batch, "_MEASURED_THRESHOLD", None)
    monkeypatch.setattr(batch, "_MEASURE_STARTED", False)
    v = batch.JAXBatchVerifier()
    assert v.cpu_threshold is None
    done = threading.Event()
    called = []

    def fake_measure():
        called.append(1)
        batch._MEASURED_THRESHOLD = 999
        done.set()
        return 999

    monkeypatch.setattr(batch, "measured_cpu_threshold", fake_measure)
    assert v._resolved_threshold(8) == 64      # floor, no measurement
    assert not called
    assert v._resolved_threshold(64) == 65     # host path, worker kicked
    assert done.wait(5.0)
    assert v._resolved_threshold(64) == 999    # measured result pinned
    assert v.cpu_threshold == 999
    assert v._resolved_threshold(8) == 999     # pinned thereafter
    assert len(called) == 1


def test_device_readiness_gates_dispatch(monkeypatch):
    """r5 TPU-in-the-loop finding: the FIRST device contact (backend
    init + compile-cache load) wedged a live node ~3 min and got it
    evicted.  Production dispatch is therefore gated on _DEVICE_READY:
    >=threshold batches route to the host and kick a warmup worker
    until the device has answered once; then they dispatch."""
    import threading

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.crypto.keys import priv_key_from_seed

    monkeypatch.setenv("TM_TPU_CPU_THRESHOLD", "8")
    monkeypatch.setattr(batch, "_DEVICE_READY", threading.Event())
    monkeypatch.setattr(batch, "_WARMUP_STARTED", False)
    warmups = []
    monkeypatch.setattr(batch, "start_device_warmup",
                        lambda: warmups.append(1))

    v = batch.JAXBatchVerifier()
    assert v.cpu_threshold == 8

    class FakeImpl:
        calls = 0

        @staticmethod
        def verify_batch(pubs, msgs, sigs):
            FakeImpl.calls += 1
            return [True] * len(pubs)

    monkeypatch.setattr(v, "_impl", FakeImpl)
    monkeypatch.setattr(v, "_n_devices", 1)

    privs = [priv_key_from_seed(bytes([i + 1]) * 32) for i in range(16)]
    batch16 = [(p.pub_key(), b"m%d" % i, p.sign(b"m%d" % i))
               for i, p in enumerate(privs)]

    for pub, m, s in batch16:
        v.add(pub, m, s)
    ok, _ = v.verify()
    assert ok
    assert FakeImpl.calls == 0, "dispatched before the device was ready"
    assert warmups, "warmup never kicked"

    batch._DEVICE_READY.set()
    for pub, m, s in batch16:
        v.add(pub, m, s)
    ok, _ = v.verify()
    assert ok
    assert FakeImpl.calls == 1, "ready device was not dispatched to"


def test_threshold_measurement_never_blocks_verify(monkeypatch):
    """The first >=64-sig batch completes on the host path while a SLOW
    measurement (2 s, standing in for backend init + compile) runs behind
    it — and, crucially, the measurement worker HOLDS _MEASURE_LOCK for
    its whole duration exactly like the real measured_cpu_threshold, so
    a SECOND concurrent verify (whose start_threshold_measurement must
    fast-path on the started flag without touching that lock) cannot
    queue behind the in-flight measurement either."""
    import time

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.crypto.keys import priv_key_from_seed

    monkeypatch.delenv("TM_TPU_CPU_THRESHOLD", raising=False)
    monkeypatch.setattr(batch, "_MEASURED_THRESHOLD", None)
    monkeypatch.setattr(batch, "_MEASURE_STARTED", False)

    started = []
    lock_held = __import__("threading").Event()

    def slow_measure():
        # mimic the real shape: the WHOLE measurement runs under
        # _MEASURE_LOCK (the ADVICE r5 regression was precisely that
        # callers queued on this lock)
        with batch._MEASURE_LOCK:
            started.append(time.monotonic())
            lock_held.set()
            time.sleep(2.0)
            batch._MEASURED_THRESHOLD = 4096
        return 4096

    monkeypatch.setattr(batch, "measured_cpu_threshold", slow_measure)

    v = batch.JAXBatchVerifier()
    privs = [priv_key_from_seed(bytes([i + 1]) * 32) for i in range(64)]
    for i, p in enumerate(privs):
        m = b"block-%d" % i
        v.add(p.pub_key(), m, p.sign(m))
    t0 = time.monotonic()
    all_ok, oks = v.verify()
    elapsed = time.monotonic() - t0
    assert all_ok and len(oks) == 64
    # host path: 64 native verifies ~3 ms; generous bound far below the
    # 2 s the measurement needs
    assert elapsed < 0.5, f"verify blocked {elapsed:.3f}s on measurement"
    assert started, "measurement worker was never kicked"

    # second verify while the lock-holding measurement is in flight:
    # must also complete on the host path without queueing on the lock
    assert lock_held.wait(5.0)
    for i, p in enumerate(privs):
        m = b"block2-%d" % i
        v.add(p.pub_key(), m, p.sign(m))
    t0 = time.monotonic()
    all_ok, oks = v.verify()
    elapsed = time.monotonic() - t0
    assert all_ok and len(oks) == 64
    assert elapsed < 0.5, (
        f"second verify blocked {elapsed:.3f}s behind the in-flight "
        "measurement (start_threshold_measurement queued on _MEASURE_LOCK)"
    )


def test_wedged_device_never_blocks_submitters(monkeypatch):
    """Async-service acceptance (round 6): a deliberately WEDGED device
    — warmup hangs forever, standing in for a device that never comes
    up — must never
    block `submit()` callers: flushes at/above the dispatch threshold
    route to the host path while the wedged warmup dangles, and the
    futures resolve promptly."""
    import threading
    import time

    from tendermint_tpu.crypto import async_verify as av
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.crypto.keys import priv_key_from_seed

    monkeypatch.setattr(batch, "_DEVICE_READY", threading.Event())  # unset
    monkeypatch.setattr(batch, "_WARMUP_STARTED", False)
    warmups = []

    def wedged_warmup():
        warmups.append(1)
        # the REAL warmup would now hang on backend init forever; the
        # service must not be waiting on it

    monkeypatch.setattr(batch, "start_device_warmup", wedged_warmup)

    svc = av.reset_service(linger_ms=1.0, cpu_threshold=8)
    try:
        privs = [priv_key_from_seed(bytes([i + 1]) * 32) for i in range(16)]
        items = []
        for i, p in enumerate(privs):
            m = b"wedged-%d" % i
            items.append((p.pub_key().bytes_(), m, p.sign(m)))
        t0 = time.monotonic()
        futs = [svc.submit(*it) for it in items]
        submit_dt = time.monotonic() - t0
        assert submit_dt < 0.25, f"submit blocked {submit_dt:.3f}s"
        oks = [f.result(timeout=10.0) for f in futs]
        assert oks == [True] * 16
        assert warmups, "warmup was never kicked for the >=threshold flush"
        st = av.service_stats()
        assert st["device_batches"] == 0, "dispatched to an unproven device"
        assert st["host_flushes"] >= 1
    finally:
        av.reset_service()
