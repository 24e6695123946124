"""Host prep and the launch as spans, through the real service on XLA-CPU
(crypto/async_verify.py): each device flush's `verify.host_prep` with its
five steps hung under it (`ops/ed25519_jax.prepare_batch`,
`utils/host_prep.batch_k_native`, `_pad_rows`), the threads the native
SHA-512 started, `verify.launch` inside the flush's
`verify.device_execute`, the same spans on four sharded devices — and
none of it with tracing off, where `prepare_batch` returns the same bytes.
The programs are the floor rung 8 on one device and rung 64 sharded four
ways."""

import threading

import numpy as np
import pytest

from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.utils import host_prep
from tendermint_tpu.utils import trace

STEPS = ["verify.host_prep.rows", "verify.host_prep.canon", "verify.host_prep.msgs",
         "verify.host_prep.k", "verify.host_prep.pad"]


@pytest.fixture(autouse=True)
def device_service(monkeypatch):
    """A service whose every flush goes to the 'device' (the XLA-CPU
    program): the device marked ready, no host threshold."""
    ready = threading.Event()
    ready.set()
    monkeypatch.setattr(cbatch, "_DEVICE_READY", ready)
    monkeypatch.delenv("TM_TPU_MESH", raising=False)
    monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "0")
    was = trace.enabled()
    trace.set_ring_size(trace.DEFAULT_RING_SIZE)
    trace.clear()
    yield
    trace.set_enabled(was)
    trace.clear()
    av.reset_service()


def _items(n, tag):
    items = []
    for i in range(n):
        k = priv_key_from_seed(bytes([(i % 250) + 1]) * 32)
        m = b"%s-%d" % (tag, i)
        items.append((k.pub_key().bytes_(), m, k.sign(m)))
    return items


def _traced_flushes(calls, n, tag):
    """`calls` verify calls of `n` rows each with spans on; the spans."""
    svc = av.reset_service(linger_ms=1.0, cpu_threshold=0)
    trace.set_enabled(True)
    try:
        for c in range(calls):
            assert all(svc.verify_many(_items(n, b"%s-%d" % (tag, c))))
    finally:
        trace.set_enabled(False)
    return svc, trace.spans()


def _end(s):
    return s["t0_ns"] + s["dur_ns"]


def _check_host_prep(spans, rung, chips):
    """Every device flush: `verify.host_prep` with exactly the five steps
    under it, inside it, summing to no more than it; its launch inside
    its `verify.device_execute`.  Returns the `.k` steps."""
    preps = [s for s in spans if s["name"] == "verify.host_prep"]
    execs = {s["attrs"]["flush"]: s for s in spans if s["name"] == "verify.device_execute"}
    launches = {s["attrs"]["flush"]: s for s in spans if s["name"] == "verify.launch"}
    assert preps and len(preps) == len(execs) == len(launches)
    ks = []
    for prep in preps:
        flush = prep["attrs"]["flush"]
        assert prep["attrs"]["rung"] == rung
        kids = [s for s in spans if s["parent"] == prep["id"]]
        assert [s["name"] for s in kids] == STEPS
        for kid in kids:
            assert kid["attrs"]["flush"] == flush, kid
            assert prep["t0_ns"] <= kid["t0_ns"] and _end(kid) <= _end(prep), kid
        assert sum(k["dur_ns"] for k in kids) <= prep["dur_ns"]
        by = {s["name"]: s for s in kids}
        n = prep["attrs"]["n"]
        assert by["verify.host_prep.rows"]["attrs"] == {"well_formed": 1, "flush": flush}
        assert by["verify.host_prep.pad"]["attrs"] == {"rung": rung, "pad_rows": rung - n,
                                                       "flush": flush}
        ks.append(by["verify.host_prep.k"])
        launch, ex = launches[flush], execs[flush]
        assert launch["attrs"] == {"flush": flush, "rung": rung, "chips": chips}
        assert ex["t0_ns"] <= launch["t0_ns"] and _end(launch) <= _end(ex)
        assert _end(prep) <= launch["t0_ns"]
    return ks


@pytest.mark.parametrize("native", [1, 0], ids=["native", "hashlib"])
def test_each_device_flush_has_five_steps_and_its_launch(monkeypatch, native):
    if not native:
        monkeypatch.setattr(host_prep, "load_lib", lambda: None)
    elif host_prep.load_lib() is None:
        pytest.skip("native edhost kernel unavailable")
    svc, spans = _traced_flushes(2, 5, b"steps-%d" % native)
    assert svc.last_route[0] == "device"
    ks = _check_host_prep(spans, rung=8, chips=1)
    assert len(ks) == 2
    for k in ks:
        assert k["attrs"]["native"] == native and k["attrs"]["threads"] >= 1
    msgs = [s for s in spans if s["name"] == "verify.host_prep.msgs"]
    want = sum(len(m) for _p, m, _s in _items(5, b"steps-%d-0" % native))
    assert msgs[0]["attrs"]["bytes"] == (want if native else 0)


def test_the_sharded_flush_records_the_same_spans_on_four_devices(monkeypatch):
    import jax

    assert len(jax.devices()) >= 4, "conftest must provide the virtual mesh"
    monkeypatch.setenv("TM_TPU_MESH", "4")
    monkeypatch.setenv("TM_TPU_MESH_MIN_SHARD", "64")
    svc, spans = _traced_flushes(1, 64, b"sharded")
    assert svc.last_route == ("device", "mesh_sharded")
    ks = _check_host_prep(spans, rung=64, chips=4)
    assert ks[0]["attrs"]["native"] == int(host_prep.load_lib() is not None)


def test_tracing_off_no_span_and_the_same_bytes():
    svc = av.reset_service(linger_ms=1.0, cpu_threshold=0)
    items = _items(5, b"untraced")
    assert all(svc.verify_many(items))
    assert svc.last_route[0] == "device"
    assert trace.spans() == []

    from tendermint_tpu.ops import ed25519_jax as dev

    pubs, msgs, sigs = (list(col) for col in zip(*items))
    sigs[2] = sigs[2][:63]                          # a malformed row
    sigs[3] = sigs[3][:32] + b"\xff" * 32           # s >= L
    bare = dev.prepare_batch(pubs, msgs, sigs)
    sink = []
    traced = dev.prepare_batch(pubs, msgs, sigs, sink=sink)
    for a, b in zip(bare, traced):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert [name for name, *_ in sink] == STEPS[:-1]
    assert sink[0][3] == {"well_formed": 0}
    assert not bare[4][2] and not bare[4][3] and bare[4][[0, 1, 4]].all()
    assert np.all(np.diff([t0 for _, t0, _, _ in sink]) >= 0)
