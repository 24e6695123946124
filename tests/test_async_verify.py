"""The async verification service (crypto/async_verify): cross-caller
micro-batching, host/device pipelining, and the verified-signature
cache.  Verdicts must stay bit-identical to the synchronous
BatchVerifier paths; duplicates must resolve from the cache without any
host or device verify; a corrupted signature must never be cached as
valid."""

import threading
import time

import pytest

from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto.keys import priv_key_from_seed


def _triples(n, bad=(), tag=b"async"):
    items, want = [], []
    for i in range(n):
        k = priv_key_from_seed(bytes([(i % 250) + 1]) * 32)
        m = b"%s-%d" % (tag, i)
        s = k.sign(m)
        ok = True
        if i in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
            ok = False
        items.append((k.pub_key().bytes_(), m, s))
        want.append(ok)
    return items, want


@pytest.fixture(autouse=True)
def lock_order_checked():
    """Every test in this module runs under the runtime lock-order
    checker (utils/lockcheck): the service's queue/cache/service-lock
    interleavings are exactly where an inversion would hide, and the
    PR 1 `_MEASURE_LOCK`/`_FLAG_LOCK` contention was found by hand.
    The singleton is recreated per test (reset_service/clear_service),
    which is what brings its locks into the checker's scope."""
    from tendermint_tpu.utils import lockcheck

    lockcheck.install()
    try:
        yield
        lockcheck.check()
    finally:
        lockcheck.uninstall()


@pytest.fixture(autouse=True)
def race_sanitized():
    """And under the lockset race sanitizer (utils/racecheck): the
    service's worker-thread/caller handoffs are exactly where an
    unguarded shared field would hide (last_route was the live
    example — now allowlisted as a deliberate last-write-wins)."""
    from tendermint_tpu.utils import racecheck

    racecheck.install()
    racecheck.reset()
    racecheck.instrument_defaults()
    try:
        yield
        racecheck.check()
    finally:
        racecheck.uninstall()


@pytest.fixture
def svc():
    s = av.reset_service(linger_ms=1.0)
    yield s
    av.reset_service()


def test_verify_many_verdicts(svc):
    items, want = _triples(20, bad=(3, 11), tag=b"verdicts")
    assert svc.verify_many(items) == want


def test_verify_many_empty(svc):
    assert svc.verify_many([]) == []


def test_submit_returns_future_immediately(svc):
    items, _ = _triples(1, tag=b"future")
    t0 = time.monotonic()
    fut = svc.submit(*items[0])
    assert time.monotonic() - t0 < 0.25, "submit blocked"
    assert fut.result(timeout=10.0) is True


def test_cache_hit_skips_all_verify_work(svc, monkeypatch):
    """A duplicate (pub, msg, sig) resolves from the cache: the hit
    counter moves and NO flush (host or device) runs for it."""
    items, _ = _triples(8, tag=b"cachehit")
    assert svc.verify_many(items) == [True] * 8

    calls = []
    real = av._split_verify

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(av, "_split_verify", counting)
    st0 = av.service_stats()
    assert svc.verify_many(items) == [True] * 8
    st1 = av.service_stats()
    assert st1["cache_hits"] - st0["cache_hits"] == 8
    assert st1["flushes"] == st0["flushes"]
    assert st1["device_batches"] == st0["device_batches"]
    assert not calls, "duplicate submission reached a verify path"


def test_corrupted_sig_never_cached(svc):
    items, _ = _triples(4, bad=(2,), tag=b"corrupt")
    assert svc.verify_many(items) == [True, True, False, True]
    # the rejected row must be re-verified (a fresh flush), not served
    st0 = av.service_stats()
    assert svc.verify_many([items[2]]) == [False]
    st1 = av.service_stats()
    assert st1["cache_hits"] == st0["cache_hits"]
    assert st1["flushes"] == st0["flushes"] + 1
    # and the VALID signature for the same (pub, msg) is its own cache
    # key (the sig is part of the key), verified on its own merits
    fixed, _ = _triples(4, tag=b"corrupt")
    assert svc.verify_many([fixed[2]]) == [True]


def test_cache_disabled(monkeypatch):
    s = av.reset_service(linger_ms=0.5, cache_size=0)
    try:
        items, _ = _triples(3, tag=b"nocache")
        assert s.verify_many(items) == [True] * 3
        st0 = av.service_stats()
        assert s.verify_many(items) == [True] * 3
        st1 = av.service_stats()
        assert st1["cache_hits"] == st0["cache_hits"] == 0
        assert st1["flushes"] > st0["flushes"]
    finally:
        av.reset_service()


def test_cache_lru_bound():
    c = av.VerifiedSigCache(maxsize=4)
    keys = [av.VerifiedSigCache.key(b"p%d" % i, b"m", b"s") for i in range(6)]
    for k in keys:
        c.put(k)
    assert len(c) == 4
    assert not c.get(keys[0]) and not c.get(keys[1])  # evicted
    assert c.get(keys[5])


def test_coalesces_concurrent_submitters():
    """8 threads each submit a 6-sig slice into a lingering service: the
    flushes must coalesce across callers (fewer flushes than callers,
    max coalesced batch larger than any single caller's)."""
    s = av.reset_service(linger_ms=60.0)
    try:
        per = 6
        datasets = [_triples(per, tag=b"stream%d" % i)[0] for i in range(8)]
        results = [None] * 8
        barrier = threading.Barrier(8)

        def worker(i):
            barrier.wait()
            results[i] = s.verify_many(datasets[i])

        ths = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert all(r == [True] * per for r in results)
        st = av.service_stats()
        assert st["coalesced_max"] > per, st
        assert st["flushes"] < 8, st
    finally:
        av.reset_service()


def test_submit_many_is_one_future_and_submit_unwraps_its_row(svc):
    """The unit is the submit: `submit_many` hands back ONE future for
    the list of verdicts, `submit` one for a bool, and the service
    counts groups (`submits`) beside rows (`submitted`)."""
    items, want = _triples(6, bad=(4,), tag=b"onefuture")
    fut = svc.submit_many(items)
    assert fut.result(timeout=10.0) == want
    one = svc.submit(*items[4])                # invalid: fresh again
    assert one.result(timeout=10.0) is False
    assert svc.submit(*items[0]).result(timeout=1.0) is True   # a hit
    assert svc.submit_many([]).result(timeout=1.0) == []
    assert svc.submit_many(iter(items[:2])).result(timeout=1.0) == [True, True]
    st = av.service_stats()
    assert (st["submits"], st["submitted"]) == (2, 7), st
    assert st["cache_hits"] == 3, st


@pytest.mark.parametrize("n,cap,flushes", [(20, 8, 3), (16, 8, 2), (9, 8, 2)])
def test_group_wider_than_a_flush_resolves_across_flushes(
        monkeypatch, n, cap, flushes):
    """A submit wider than MAX_COALESCE (ROADMAP Queue 2 (g): a commit
    over 16,384 rows) is cut into segments: it resolves when its last
    segment lands, with every verdict in its place."""
    monkeypatch.setattr(av, "MAX_COALESCE", cap)
    s = av.reset_service(linger_ms=1.0)
    try:
        bad = (0, cap - 1, cap, n - 1)
        items, want = _triples(n, bad=bad, tag=b"wide%d" % n)
        assert s.verify_many(items) == want
        st = av.service_stats()
        assert (st["submits"], st["submitted"]) == (1, n), st
        assert st["flushes"] == flushes and st["coalesced_max"] == cap, st
        assert st["queue_depth"] == 0, st
        # only the valid rows were cached, whichever segment they rode
        assert s.cache.get_many(av.VerifiedSigCache.keys(*zip(*items))) == want
    finally:
        av.reset_service()


def test_two_submits_share_one_flush_and_get_their_own_rows(monkeypatch):
    """Groups of independent submitters coalesce into one flush (here a
    third is cut by the flush's end) and each future gets its own rows'
    verdicts back, in its own order."""
    monkeypatch.setattr(av, "MAX_COALESCE", 12)
    s = av.reset_service(linger_ms=150.0)
    try:
        a, want_a = _triples(5, bad=(1,), tag=b"share-a")
        b, want_b = _triples(4, bad=(0, 3), tag=b"share-b")
        c, want_c = _triples(6, bad=(2, 5), tag=b"share-c")
        futs = [s.submit_many(x) for x in (a, b, c)]   # inside one linger
        assert [f.result(timeout=10.0) for f in futs] == [want_a, want_b, want_c]
        st = av.service_stats()
        assert (st["submits"], st["submitted"]) == (3, 15), st
        assert (st["flushes"], st["coalesced_max"]) == (2, 12), st
    finally:
        av.reset_service()


def test_many_submitters_under_a_short_switch_interval(monkeypatch):
    """Stress: more submitting threads than cores, the interpreter
    switching every 10 us, flushes small enough that groups are cut and
    shared.  Every submit gets its own verdicts, and the row counters —
    which a lost update under the service lock would break — add up."""
    import sys

    monkeypatch.setattr(av, "MAX_COALESCE", 10)
    s = av.reset_service(linger_ms=0.2)
    threads, rounds, per = 16, 5, 7
    data = [[_triples(per, bad=((t + r) % per,), tag=b"stress-%d-%d" % (t, r))
             for r in range(rounds)] for t in range(threads)]
    wrong: list = []

    def submitter(t):
        for items, want in data[t]:
            got = s.verify_many(items)
            if got != want:
                wrong.append((t, got, want))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ths = [threading.Thread(target=submitter, args=(t,)) for t in range(threads)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=60.0)
        assert not any(th.is_alive() for th in ths), "a submitter never returned"
    finally:
        sys.setswitchinterval(was)
        st = av.service_stats()
        av.reset_service()
    assert not wrong, wrong[:3]
    assert st["submits"] == threads * rounds, st
    assert st["submitted"] == threads * rounds * per, st
    assert st["queue_depth"] == 0 and st["coalesced_max"] <= 10, st
    assert st["cache_size"] == threads * rounds * (per - 1), st


def test_part_hits_part_fresh_part_invalid_keeps_input_order(svc):
    """One submit mixing cached rows, fresh valid rows and invalid rows:
    verdicts come back in input order, only the fresh rows are queued,
    and only the valid fresh rows enter the cache."""
    seen, _ = _triples(4, tag=b"mix-seen")
    assert svc.verify_many(seen) == [True] * 4
    fresh, want_fresh = _triples(5, bad=(1, 3), tag=b"mix-fresh")
    items = [fresh[0], seen[0], fresh[1], seen[1], seen[2], fresh[2],
             fresh[3], seen[3], fresh[4]]
    want = [True, True, False, True, True, True, False, True, True]
    st0 = av.service_stats()
    e2e0 = dict(av.VERIFY_E2E_SECONDS.label_stats())
    assert svc.verify_many(items) == want
    st1 = av.service_stats()
    e2e1 = av.VERIFY_E2E_SECONDS.label_stats()
    assert st1["cache_hits"] - st0["cache_hits"] == 4
    assert st1["submitted"] - st0["submitted"] == 5
    assert st1["submits"] - st0["submits"] == 1
    assert st1["cache_size"] - st0["cache_size"] == 3
    assert e2e1[("cache",)][0] - e2e0.get(("cache",), (0, 0))[0] == 4
    assert e2e1[("host",)][0] - e2e0[("host",)][0] == 5
    assert svc.cache.get_many(av.VerifiedSigCache.keys(*zip(*fresh))) == want_fresh


def test_a_row_that_still_raises_fails_its_own_submit_only(svc, monkeypatch):
    """The catastrophic path: the batched host verify raised, and in the
    per-row fallback one row raises again.  Its group's future carries
    the batch's error (what `verify_many` raised to the caller anyway);
    the other group of the same flush resolves."""
    good, want = _triples(3, bad=(1,), tag=b"poison-good")
    poisoned, _ = _triples(3, tag=b"poison-bad")
    real = av._ed.verify_fast

    def batch_raises(*_a):
        raise RuntimeError("simulated batch failure")

    def row_raises(pub, msg, sig):
        if msg == poisoned[1][1]:
            raise ValueError("simulated poisoned row")
        return real(pub, msg, sig)

    monkeypatch.setattr(av._ed, "verify_batch_fast", batch_raises)
    monkeypatch.setattr(av._ed, "verify_fast", row_raises)
    s = av.reset_service(linger_ms=100.0)
    try:
        f_good, f_bad = s.submit_many(good), s.submit_many(poisoned)
        assert f_good.result(timeout=10.0) == want
        with pytest.raises(RuntimeError, match="simulated batch failure"):
            f_bad.result(timeout=10.0)
        assert av.service_stats()["flushes"] == 1
        # the valid rows of the group that resolved are cached; of the
        # failed group, none
        assert s.cache.get_many(av.VerifiedSigCache.keys(*zip(*good))) == want
        assert not any(s.cache.get_many(
            av.VerifiedSigCache.keys(*zip(*poisoned))))
    finally:
        av.reset_service()


def test_batch_keeps_segments_and_rows_aligned():
    def group(tag, n):
        rows = [b"%s%d" % (tag, i) for i in range(n)]
        return av._Group(rows, rows, rows, rows, None, [False] * n, False, 0.0)

    groups = {"a": group(b"a", 4), "b": group(b"b", 2), "c": group(b"c", 6)}
    batch = av._Batch([(groups["a"], 1, 4), (groups["b"], 0, 2),
                       (groups["c"], 2, 6)])
    assert len(batch) == 9
    assert batch.pubs == batch.msgs == batch.sigs == [
        b"a1", b"a2", b"a3", b"b0", b"b1", b"c2", b"c3", b"c4", b"c5"]
    # one whole group is handed on as its own lists: no copy
    assert av._Batch([(groups["c"], 0, 6)]).pubs is groups["c"].pubs


@pytest.mark.parametrize("labels", [{}, {"path": "device"}])
def test_observe_n_leaves_what_n_observes_leave(labels):
    """Histogram.observe_n(v, n) == n x observe(v): count, sum and the
    one bucket, for a plain and a labelled series, beside other values
    and in the overflow bucket."""
    from tendermint_tpu.utils.metrics import Histogram

    def pair():
        kw = {"label_names": tuple(labels)} if labels else {}
        return [Histogram("h", "help", buckets=(0.001, 0.01, 0.1), **kw)
                for _ in range(2)]

    one, many = pair()
    for value, n in ((0.004, 7), (0.01, 1), (0.25, 10_000), (0.0005, 3)):
        for _ in range(n):
            one.observe(value, **labels)
        many.observe_n(value, n, **labels)
    a, b = one.samples(), many.samples()
    assert [x[:2] for x in a] == [x[:2] for x in b]
    for (suffix, _lbl, want), (_, _, got) in zip(a, b):
        # n additions of v and one of n*v round differently in the sum
        assert got == (pytest.approx(want, rel=1e-9) if suffix == "_sum"
                       else want), suffix
    key = tuple(labels.values())
    assert many.label_stats()[key][0] == 7 + 1 + 10_000 + 3


def test_mixed_key_types(svc):
    pytest.importorskip("cryptography")
    from tendermint_tpu.crypto.secp256k1 import PrivKeySecp256k1

    ed_items, _ = _triples(3, tag=b"mixed")
    sk = PrivKeySecp256k1(bytes([7]) * 32)
    m = b"mixed-secp"
    items = ed_items + [(sk.pub_key().bytes_(), m, sk.sign(m))]
    bad_sig = bytearray(items[-1][2])
    bad_sig[-1] ^= 1
    items.append((items[-1][0], m, bytes(bad_sig)))
    oks = svc.verify_many(items)
    assert oks[:4] == [True] * 4
    assert oks[4] is False


def test_device_pipelining_keeps_two_flushes_in_flight(monkeypatch):
    """With a ready 'device' (XLA-CPU program) and a tiny threshold, a
    submit wider than a flush is cut into flushes of one program each,
    enqueued behind one another: never more than two in flight, drained
    in the order they were enqueued, every row in its place."""
    ev = threading.Event()
    ev.set()
    monkeypatch.setattr(cbatch, "_DEVICE_READY", ev)
    monkeypatch.setattr(av, "MAX_COALESCE", 8)
    s = av.reset_service(linger_ms=5.0, cpu_threshold=8)
    # the conftest forces 8 virtual devices; pin the single-device view
    # so the flush takes the async-enqueue path rather than sharding
    s._jax_bv._n_devices = 1
    drained, real_drain = [], s._drain_one

    def drain_one(inflight):
        drained.append((len(inflight), inflight[0][4]))
        real_drain(inflight)

    monkeypatch.setattr(s, "_drain_one", drain_one)
    try:
        items, want = _triples(24, bad=(5, 13), tag=b"pipeline")
        assert s.verify_many(items) == want
        st = av.service_stats()
        assert (st["flushes"], st["device_batches"],
                st["pipelined_drains"]) == (3, 3, 3), st  # 3 x 8 rows
        # flush 2 was enqueued behind flush 1, flush 3 behind flush 2
        assert drained == [(2, 1), (2, 2), (1, 3)]
    finally:
        av.reset_service()


def test_device_flush_counts_rows_not_groups(monkeypatch):
    """Two submits coalesced into one device flush: every pipeline
    series still counts ROWS — the `path="device"` count of
    verify_e2e_seconds (what chipbench's `resolved_on_device` reads) and
    the queue-wait count grow by the rows, `submits` by the groups."""
    ev = threading.Event()
    ev.set()
    monkeypatch.setattr(cbatch, "_DEVICE_READY", ev)
    s = av.reset_service(linger_ms=100.0, cpu_threshold=8)
    s._jax_bv._n_devices = 1
    try:
        a, want_a = _triples(5, bad=(2,), tag=b"rows-a")
        b, want_b = _triples(3, bad=(0,), tag=b"rows-b")
        e2e0 = av.VERIFY_E2E_SECONDS.label_stats().get(("device",), (0, 0.0))
        qw0 = av.VERIFY_QUEUE_WAIT_SECONDS.label_stats()[()]
        fa, fb = s.submit_many(a), s.submit_many(b)
        assert (fa.result(timeout=120.0), fb.result(timeout=10.0)) == (want_a, want_b)
        e2e1 = av.VERIFY_E2E_SECONDS.label_stats()[("device",)]
        qw1 = av.VERIFY_QUEUE_WAIT_SECONDS.label_stats()[()]
        assert e2e1[0] - e2e0[0] == 8
        assert qw1[0] - qw0[0] == 8
        # a count-weighted mean: each row waited at most the linger + slack
        assert 0 < (qw1[1] - qw0[1]) / 8 < 5.0
        st = av.service_stats()
        assert (st["submits"], st["submitted"], st["flushes"]) == (2, 8, 1), st
        assert (st["device_batches"], st["host_flushes"]) == (1, 0), st
        assert s.last_route == ("device", "pipelined")
    finally:
        av.reset_service()


def test_enqueue_that_raises_sends_the_whole_flush_to_the_host(monkeypatch):
    """The first enqueue raises: the whole flush (two submits coalesced)
    resolves on the host, every row lands exactly once and in its place,
    and the next flush goes to the device again."""
    from tendermint_tpu.ops import ed25519_jax as dev

    ev = threading.Event()
    ev.set()
    monkeypatch.setattr(cbatch, "_DEVICE_READY", ev)
    real, calls = dev._compiled, []

    def first_call_fails(*key):
        calls.append(key)
        if len(calls) == 1:
            raise RuntimeError("simulated enqueue failure")
        return real(*key)

    monkeypatch.setattr(dev, "_compiled", first_call_fails)
    s = av.reset_service(linger_ms=100.0, cpu_threshold=8)
    s._jax_bv._n_devices = 1
    try:
        a, want_a = _triples(10, bad=(3, 8), tag=b"whole-a")
        b, want_b = _triples(6, bad=(5,), tag=b"whole-b")
        e2e0 = dict(av.VERIFY_E2E_SECONDS.label_stats())
        fa, fb = s.submit_many(a), s.submit_many(b)
        assert (fa.result(timeout=120.0), fb.result(timeout=10.0)) == \
            (want_a, want_b)
        e2e1 = dict(av.VERIFY_E2E_SECONDS.label_stats())
        assert e2e1.get(("device",), (0, 0))[0] == \
            e2e0.get(("device",), (0, 0))[0]
        assert e2e1[("host",)][0] - e2e0.get(("host",), (0, 0))[0] == 16
        st = av.service_stats()
        assert (st["flushes"], st["device_errors"], st["device_batches"],
                st["host_flushes"]) == (1, 1, 0, 1), st
        assert s.last_route == ("host", "device_error")

        c, want_c = _triples(9, bad=(0,), tag=b"whole-c")
        assert s.verify_many(c) == want_c
        st = av.service_stats()
        assert (st["device_errors"], st["device_batches"],
                st["host_flushes"]) == (1, 1, 1), st
        assert s.last_route == ("device", "pipelined")
    finally:
        av.reset_service()


class _Unreadable:
    """A pending device value whose readback raises (the device died
    between enqueue and drain)."""

    def __array__(self, *a, **kw):
        raise RuntimeError("simulated readback failure")


@pytest.mark.parametrize("site", ["enqueue", "readback"])
def test_device_failure_is_counted_logged_once_and_resolved_on_host(
        monkeypatch, caplog, site):
    """A device program that raises at enqueue, and one whose verdict
    readback raises, each bump `device_errors`, log their traceback once
    per site, and still resolve every future with host verdicts — the
    liveness contract kept, the degradation no longer silent.  A
    readback failure leaves `device_batches` incremented (it counts
    enqueues), which is why it is never read alone."""
    import logging

    from tendermint_tpu.ops import ed25519_jax as dev

    ev = threading.Event()
    ev.set()
    monkeypatch.setattr(cbatch, "_DEVICE_READY", ev)

    def broken_program(*_key):
        def run(*_rows):
            if site == "enqueue":
                raise RuntimeError("simulated enqueue failure")
            return _Unreadable()
        return run

    monkeypatch.setattr(dev, "_compiled", broken_program)
    s = av.reset_service(linger_ms=1.0, cpu_threshold=8)
    s._jax_bv._n_devices = 1  # the pipelined route, not the mesh
    try:
        with caplog.at_level(logging.WARNING,
                             logger="tendermint_tpu.crypto.async_verify"):
            for rnd in range(2):
                items, want = _triples(12, bad=(2, 7),
                                       tag=b"deverr-%s-%d" % (site.encode(), rnd))
                assert s.verify_many(items) == want  # host verdicts
        st = av.service_stats()
        assert st["device_errors"] == 2, st
        assert st["device_batches"] == (0 if site == "enqueue" else 2), st
        logged = [r for r in caplog.records if "device verify failed" in r.message]
        assert len(logged) == 1, [r.message for r in logged]  # once per site
        assert f"at {site}" in logged[0].getMessage()
        assert "simulated" in str(logged[0].exc_info[1])
        assert s.last_route == (("host", "device_error") if site == "enqueue"
                                else ("device", "pipelined"))
    finally:
        av.reset_service()


def test_service_batch_verifier_adapter(svc):
    bv = av.ServiceBatchVerifier(svc)
    assert bv.verify() == (False, [])  # empty matches CPUBatchVerifier
    items, want = _triples(5, bad=(1,), tag=b"adapter")
    for p, m, g in items:
        bv.add(p, m, g)
    assert bv.count() == 5
    ok, per = bv.verify()
    assert ok is False and per == want
    assert bv.count() == 0  # verify resets


def test_new_service_batch_verifier_env_gate(monkeypatch):
    monkeypatch.delenv("TM_TPU_ASYNC_VERIFY", raising=False)
    assert isinstance(av.new_service_batch_verifier(), av.ServiceBatchVerifier)
    monkeypatch.setenv("TM_TPU_ASYNC_VERIFY", "0")
    assert not isinstance(av.new_service_batch_verifier(),
                          av.ServiceBatchVerifier)


def test_env_knob_parsing(monkeypatch):
    monkeypatch.setenv("TM_TPU_LINGER_MS", "2.5")
    monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "128")
    s = av.VerifyService()
    assert s.linger_s == pytest.approx(2.5e-3)
    assert s.cache.maxsize == 128
    s.close()
    monkeypatch.setenv("TM_TPU_LINGER_MS", "garbage")
    monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "-5")
    s = av.VerifyService()
    assert s.linger_s == pytest.approx(av.DEFAULT_LINGER_MS / 1e3)
    assert s.cache.maxsize == 0  # negative clamps to disabled
    s.close()


def test_env_knobs_set_after_construction_take_effect(monkeypatch):
    """The service half of the order-dependent test_multinode flake: a
    singleton built by an earlier test captured TM_TPU_VERIFY_CACHE /
    TM_TPU_LINGER_MS at construction and silently overrode a later
    test's monkeypatched env.  Unpinned knobs now resolve lazily, so a
    stale instance honors the current environment; ctor args still
    pin."""
    monkeypatch.delenv("TM_TPU_VERIFY_CACHE", raising=False)
    monkeypatch.delenv("TM_TPU_LINGER_MS", raising=False)
    s = av.VerifyService()                  # built under the default env
    try:
        assert s.cache.maxsize == av.DEFAULT_CACHE_SIZE
        monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "0")
        monkeypatch.setenv("TM_TPU_LINGER_MS", "4.0")
        assert s.cache.maxsize == 0         # late env takes effect...
        key = av.VerifiedSigCache.key(b"p", b"m", b"s")
        s.cache.put(key)
        assert not s.cache.get(key)         # ...and disables the cache
        assert s.linger_s == pytest.approx(4e-3)
    finally:
        s.close()
    pinned = av.VerifyService(linger_ms=1.0, cache_size=4)
    try:
        monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "99")
        assert pinned.cache.maxsize == 4    # explicit pin beats env
        assert pinned.linger_s == pytest.approx(1e-3)
    finally:
        pinned.close()


def test_routed_surfaces_share_the_service(svc):
    """vote-slice verification (VoteSet.add_votes' crypto funnel) and
    commit verification both submit through the shared service — the
    same signature re-appearing on another surface is a cache hit."""
    from tendermint_tpu.types.vote import batch_verify_votes  # noqa: F401
    from tendermint_tpu.crypto.async_verify import new_service_batch_verifier

    items, _ = _triples(4, tag=b"surfaces")
    bv = new_service_batch_verifier()
    for p, m, g in items:
        bv.add(p, m, g)
    ok, _per = bv.verify()
    assert ok
    st0 = av.service_stats()
    # a different "caller" re-verifying the same signatures: pure hits
    bv2 = new_service_batch_verifier()
    for p, m, g in items:
        bv2.add(p, m, g)
    ok2, per2 = bv2.verify()
    assert ok2 and per2 == [True] * 4
    st1 = av.service_stats()
    assert st1["cache_hits"] - st0["cache_hits"] == 4
