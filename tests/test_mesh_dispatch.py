"""The pod-slice mesh dispatcher (round 10, crypto/mesh_dispatch): one
logical verifier across the conftest's 8 virtual devices.

Routing policy is asserted directly (the pure `decide` function) AND
end-to-end (`VerifyService.last_route` after a real flush) — the ISSUE
gate is "routing decision asserted, not just outcome".  Verdict parity
runs the sharded path against the single-device reference on mixed
valid/invalid batches, including the adversarial vectors (torsion,
non-canonical encodings, malformed rows) from test_fe25519_packed,
padded to exactly 64 rows so every program here is a warm shape
(single-device rung 8/64 and the 2/4/8-device sharded rung 64 are all
in the persistent compile cache).
"""

import threading

import numpy as np
import pytest

from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import mesh_dispatch as md
from tendermint_tpu.crypto.keys import priv_key_from_seed


@pytest.fixture(autouse=True)
def _mesh_env(monkeypatch):
    """Default dispatcher env per test: auto mesh, sharding cutoff at
    the 64-row floor rung (so a 64-row flush shards without needing a
    512-row batch), restored singleton afterwards."""
    monkeypatch.delenv("TM_TPU_MESH", raising=False)
    monkeypatch.setenv("TM_TPU_MESH_MIN_SHARD", "64")
    yield
    av.reset_service()


def _svc(monkeypatch, **kw):
    """Service with a ready 'device' (the XLA-CPU program) and every
    flush routed to it (cpu_threshold=0)."""
    ev = threading.Event()
    ev.set()
    monkeypatch.setattr(cbatch, "_DEVICE_READY", ev)
    kw.setdefault("linger_ms", 1.0)
    kw.setdefault("cpu_threshold", 0)
    return av.reset_service(**kw)


def _triples(n, bad=(), tag=b"mesh"):
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


def test_decide_policy(monkeypatch):
    """The pure routing policy, no devices touched."""
    monkeypatch.delenv("TM_TPU_MESH_MIN_SHARD", raising=False)
    # auto mesh, default cutoff = 64 rows/device: small flushes pin
    assert md.decide(8, 8) == ("pinned", 1)
    assert md.decide(511, 8) == ("pinned", 1)
    assert md.decide(512, 8) == ("sharded", 8)
    # single device: always pinned
    assert md.decide(10_000, 1) == ("pinned", 1)
    # explicit mesh size caps the slice and scales the cutoff
    monkeypatch.setenv("TM_TPU_MESH", "4")
    assert md.decide(255, 8) == ("pinned", 1)
    assert md.decide(256, 8) == ("sharded", 4)
    # clamped to the visible device count; garbage falls back to auto
    monkeypatch.setenv("TM_TPU_MESH", "16")
    assert md.decide(1024, 8) == ("sharded", 8)
    monkeypatch.setenv("TM_TPU_MESH", "garbage")
    assert md.decide(512, 8) == ("sharded", 8)
    # TM_TPU_MESH=1 never shards; TM_TPU_MESH=0 disables the dispatcher
    monkeypatch.setenv("TM_TPU_MESH", "1")
    assert md.decide(10_000, 8) == ("pinned", 1)
    assert md.dispatcher_enabled()
    monkeypatch.setenv("TM_TPU_MESH", "0")
    assert not md.dispatcher_enabled()
    # explicit cutoff overrides the per-device default
    monkeypatch.delenv("TM_TPU_MESH", raising=False)
    monkeypatch.setenv("TM_TPU_MESH_MIN_SHARD", "64")
    assert md.decide(64, 8) == ("sharded", 8)
    assert md.decide(63, 8) == ("pinned", 1)


@pytest.mark.parametrize("n_dev", [1, 2, 3, 4, 6, 8])
def test_sharded_bucket_is_whole_groups_of_eight(n_dev):
    """The packed program folds its rows [N] -> [N/8, 8]: a sharded
    bucket is a multiple of the device count AND of 8 on any mesh (a 3-
    or 6-device one used to get 10,242 rows for a 10k commit), and on
    the meshes a host has (1, 2, 4, 8) it is the size it always was."""
    from tendermint_tpu.ops import ed25519_jax as dev
    from tendermint_tpu.parallel.sharding import pad_to_multiple, sharded_bucket

    for n in (1, 63, 65, 257, 667, 1000, 10_000, 16_134, 20_000):
        b = sharded_bucket(n, n_dev)
        assert b >= n and b % n_dev == 0 and b % 8 == 0, (n, b)
        if n_dev in (1, 2, 4, 8):
            assert b == pad_to_multiple(
                max(dev._bucket(n), pad_to_multiple(n, n_dev)), n_dev)
    # where a mesh shards by default, a shard is whole groups of 8 rows
    assert sharded_bucket(10_000, 4) // 4 % 8 == 0


def test_dispatcher_shards_large_flush(monkeypatch):
    """A 64-row mixed-validity flush on the 8-device mesh takes the
    sharded route with verdicts identical to the single-device program."""
    import jax

    from tendermint_tpu.ops import ed25519_jax as dev

    assert len(jax.devices()) > 1, "conftest must provide the virtual mesh"
    s = _svc(monkeypatch)
    items, want = _triples(64, bad=(0, 31, 63), tag=b"mesh-shard")
    assert md.decide(64, len(jax.devices())) == ("sharded", 8)
    oks = s.verify_many(items)
    assert oks == want
    assert s.last_route == ("device", "mesh_sharded")
    st = av.service_stats()
    assert st["mesh_sharded_batches"] == 1, st
    assert st["mesh_pinned_batches"] == 0, st
    single = dev.verify_batch([p for p, _m, _s in items],
                              [m for _p, m, _s in items],
                              [g for _p, _m, g in items])
    assert oks == [bool(v) for v in single]


def test_dispatcher_pins_small_flush(monkeypatch):
    """A flush under the sharding cutoff goes to ONE pinned chip — the
    routing decision itself is asserted, not just the verdicts."""
    import jax

    s = _svc(monkeypatch)
    items, want = _triples(8, bad=(3,), tag=b"mesh-pin")
    assert md.decide(8, len(jax.devices())) == ("pinned", 1)
    assert s.verify_many(items) == want
    assert s.last_route == ("device", "mesh_pinned")
    st = av.service_stats()
    assert st["mesh_pinned_batches"] == 1, st
    assert st["mesh_sharded_batches"] == 0, st


def test_mesh_1_is_single_device_path(monkeypatch):
    """TM_TPU_MESH=1: the dispatcher never builds a Mesh — flushes run
    the pre-mesh single-device enqueue with identical verdicts, so a
    pinned deployment's HLO cache keys are untouched by this round."""
    from tendermint_tpu.ops import ed25519_jax as dev

    monkeypatch.setenv("TM_TPU_MESH", "1")

    def _boom(m):  # a Mesh build here is a routing bug
        raise AssertionError("TM_TPU_MESH=1 built a mesh")

    monkeypatch.setattr(md, "mesh_for", _boom)
    s = _svc(monkeypatch)
    items, want = _triples(64, bad=(7, 40), tag=b"mesh-one")
    oks = s.verify_many(items)
    assert oks == want
    assert s.last_route == ("device", "mesh_pinned")
    single = dev.verify_batch([p for p, _m, _s in items],
                              [m for _p, m, _s in items],
                              [g for _p, _m, g in items])
    assert oks == [bool(v) for v in single]
    assert av.service_stats()["mesh_sharded_batches"] == 0


def test_mesh_0_disables_dispatcher(monkeypatch):
    """TM_TPU_MESH=0 restores the legacy synchronous multi-device
    routing (the pre-round-10 escape hatch)."""
    monkeypatch.setenv("TM_TPU_MESH", "0")
    s = _svc(monkeypatch)
    items, want = _triples(64, bad=(5,), tag=b"mesh-off")
    assert s.verify_many(items) == want
    assert s.last_route == ("device", "sync_routing")
    st = av.service_stats()
    assert st["mesh_pinned_batches"] == 0, st
    assert st["mesh_sharded_batches"] == 0, st


def test_dispatcher_2_device_smoke(monkeypatch):
    """Tier-1 multichip smoke (ISSUE 16 satellite): a 2-device mesh on
    the simulated slice, floor sharding rung only — the 2-device rung-64
    program is persistent-cache warm, so no cold compile in budget."""
    s = _svc(monkeypatch)
    monkeypatch.setenv("TM_TPU_MESH", "2")
    items, want = _triples(64, bad=(1, 62), tag=b"mesh-two")
    assert md.decide(64, 8) == ("sharded", 2)
    assert s.verify_many(items) == want
    assert s.last_route == ("device", "mesh_sharded")
    assert av.service_stats()["mesh_sharded_batches"] == 1
    mesh = md.mesh_for(2)
    assert int(mesh.devices.size) == 2


def test_mixed_key_batches_keep_sync_routing(monkeypatch):
    """A flush containing non-ed25519 (non-32-byte) pubs never reaches
    the mesh paths — the legacy sync routing splits it."""
    s = _svc(monkeypatch)
    items, want = _triples(63, tag=b"mesh-mixed")
    items.append((b"\x02" * 16, b"not-a-key-encoding", b"\x00" * 64))
    want.append(False)
    assert s.verify_many(items) == want
    assert s.last_route == ("device", "sync_routing")
    assert av.service_stats()["mesh_sharded_batches"] == 0


def test_sharded_adversarial_parity_64(monkeypatch):
    """verify_batch_sharded on the full-slice mesh is element-identical
    to the single-device program AND the ZIP-215 reference over the
    adversarial gauntlet (torsion points, non-canonical encodings,
    identity, malformed rows), padded to exactly the warm 64-row rung."""
    import jax

    from tendermint_tpu.crypto import ed25519 as ref
    from tendermint_tpu.ops import ed25519_jax as dev
    from tendermint_tpu.parallel.sharding import make_mesh, verify_batch_sharded

    assert len(jax.devices()) > 1, "conftest must provide the virtual mesh"

    cases = []
    keys = [priv_key_from_seed(bytes([i + 31]) * 32) for i in range(6)]
    for i, k in enumerate(keys):
        msg = b"mesh-gauntlet-%d" % i
        cases.append((k.pub_key().bytes_(), msg, k.sign(msg)))
    pub, msg, sig = cases[0]
    cases.append((pub, msg, sig[:-1] + bytes([sig[-1] ^ 1])))
    cases.append((pub, b"other", sig))
    s_nc = int.from_bytes(sig[32:], "little") + ref.L
    cases.append((pub, msg, sig[:32] + s_nc.to_bytes(32, "little")))
    cases.append((pub, msg, sig[:32] + (ref.L + 12345).to_bytes(32, "little")))
    cases.append(((2).to_bytes(32, "little"), msg, sig))
    cases.append((pub, msg, (2).to_bytes(32, "little") + sig[32:]))
    s0 = bytes(32)
    for pt in ref.eight_torsion_points()[:4]:
        for enc in ref.noncanonical_encodings(pt):
            cases.append((enc, b"any", enc + s0))
    cases.append((ref.encode_point(ref.IDENTITY), msg, sig))
    cases.append((pub[:31], msg, sig))      # malformed pub
    cases.append((pub, msg, sig[:63]))      # malformed sig
    cases = cases[:64]
    i = 0
    while len(cases) < 64:  # pad with fresh valid rows to the warm rung
        k = priv_key_from_seed(bytes([(i % 150) + 101]) * 32)
        m = b"mesh-gauntlet-pad-%d" % i
        cases.append((k.pub_key().bytes_(), m, k.sign(m)))
        i += 1
    assert len(cases) == 64

    pubs = [c[0] for c in cases]
    msgs = [c[1] for c in cases]
    sigs = [c[2] for c in cases]
    sharded = verify_batch_sharded(pubs, msgs, sigs, mesh=make_mesh())
    single = dev.verify_batch(pubs, msgs, sigs)
    assert (np.asarray(sharded) == np.asarray(single)).all(), [
        (i, bool(a), bool(b))
        for i, (a, b) in enumerate(zip(sharded, single)) if bool(a) != bool(b)]
    want = [ref.verify(p, m, g) if len(p) == 32 and len(g) == 64 else False
            for p, m, g in cases]
    assert [bool(v) for v in sharded] == want
    assert any(want) and not all(want)


def test_per_device_flush_attribution():
    """devmon splits a sharded flush's rows/bytes across the devices it
    landed on; the pinned path attributes to device 0 only."""
    from tendermint_tpu.utils import devmon as dm
    from tendermint_tpu.utils.metrics import Histogram

    hist = Histogram("mesh_test_occupancy", "", label_names=("rung",),
                     buckets=dm.OCCUPANCY_BUCKETS)
    st = dm.DeviceStats(enabled=True, hist=hist)
    st.record_flush("verify_sharded", 60, 64, nbytes=8192,
                    devices=(0, 1, 2, 3))
    st.record_flush("verify", 8, 8, nbytes=1024, devices=(0,))
    snap = st.snapshot()
    per = {d["device"]: d for d in snap["devices"]}
    assert per[0] == {"device": 0, "flushes": 2, "rows": 24, "bytes": 3072}
    assert per[3] == {"device": 3, "flushes": 1, "rows": 16, "bytes": 2048}
    assert st.device_flush_samples() == [
        ({"device": "0"}, 2.0), ({"device": "1"}, 1.0),
        ({"device": "2"}, 1.0), ({"device": "3"}, 1.0)]
    rows = dict((lbl["device"], v) for lbl, v in st.device_rows_samples())
    assert rows == {"0": 24.0, "1": 16.0, "2": 16.0, "3": 16.0}
