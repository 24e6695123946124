"""The verified-signature cache's key and store (crypto/async_verify
VerifiedSigCache, crypto/sigcache_native): the native key pass gives the
bytes of `key()`, the store is an exact LRU, only rows proven valid are
stored, and a 10,000-row submit and its land make no Python object per
row.  Every case runs once for each store: the native one (libedhost)
and the Python one (hashlib and an OrderedDict, where the library does
not load)."""

import gc
import hashlib
import random
import struct
from collections import OrderedDict

import numpy as np
import pytest

from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto import sigcache_native
from tendermint_tpu.crypto.keys import priv_key_from_seed


@pytest.fixture(params=["native", "python"])
def store(request, monkeypatch):
    if request.param == "native":
        if not sigcache_native.available():
            pytest.skip("libedhost or its SHA-256 does not load here")
    else:
        monkeypatch.setattr(sigcache_native, "_load", lambda: None)
    return request.param


def _key(i: int) -> bytes:
    return av.VerifiedSigCache.key(b"p%d" % i, b"m", b"s")


def _rows(keys) -> np.ndarray:
    return np.frombuffer(b"".join(keys), dtype=np.uint8).reshape(-1, 32)


def test_the_store_is_the_one_the_library_allows(store):
    store_type = type(av.VerifiedSigCache(maxsize=4)._store)
    assert (store_type is sigcache_native.NativeStore) == (store == "native")


def test_column_keys_are_the_row_key_byte_for_byte(store):
    """Random rows, with the odd widths a column can hold: 33-byte secp
    keys, 63- and 65-byte signatures, an empty and a 16 KB message."""
    rng = random.Random(35)
    rb = lambda k: bytes(rng.getrandbits(8) for _ in range(k))  # noqa: E731
    pubs, msgs, sigs = [], [], []
    for i in range(300):
        pubs.append(rb(rng.choice([32, 32, 33, 0, 1])))
        sigs.append(rb(rng.choice([64, 64, 63, 65, 0])))
        msgs.append(rb(rng.choice([0, 1, 55, 56, 64, 120, 16 * 1024])))
    pubs.append(rb(200))   # a row wider than the native code's one update
    sigs.append(rb(200))
    msgs.append(b"")
    keys = av.VerifiedSigCache.keys(pubs, msgs, sigs)
    assert keys.shape == (len(pubs), 32) and keys.dtype == np.uint8
    for i, (p, m, s) in enumerate(zip(pubs, msgs, sigs)):
        want = hashlib.sha256(struct.pack("<I", len(p)) + p
                              + struct.pack("<I", len(s)) + s + m).digest()
        assert av.VerifiedSigCache.key(p, m, s) == want
        assert keys[i].tobytes() == want, i
    assert av.VerifiedSigCache.keys([], [], []).shape == (0, 32)


def test_the_encoding_keeps_distinct_rows_apart(store):
    """Moving a byte between pub, sig and msg changes the key."""
    rows = [(b"ab", b"cd", b"ef"), (b"a", b"bcd", b"ef"), (b"abc", b"d", b"ef"),
            (b"ab", b"c", b"def"), (b"ab", b"cde", b"f"), (b"", b"abcd", b"ef")]
    keys = av.VerifiedSigCache.keys(*zip(*rows))
    assert len({k.tobytes() for k in keys}) == len(rows)


def test_the_key_pass_counts_its_rows(store):
    def native_python():
        got = {lb["pass"]: v for _, lb, v in av.VERIFY_CACHE_ROWS_TOTAL.samples()}
        return got.get("native", 0), got.get("python", 0)

    before = native_python()
    av.VerifiedSigCache.keys([b"p"] * 7, [b"m"] * 7, [b"s"] * 7)
    after = native_python()
    grew = (7, 0) if store == "native" else (0, 7)
    assert (after[0] - before[0], after[1] - before[1]) == grew


def test_lru_bound_hit_and_reput_refresh(store):
    c = av.VerifiedSigCache(maxsize=4)
    k = [_key(i) for i in range(6)]
    for x in k[:4]:
        c.put(x)                      # LRU order 0 1 2 3
    assert c.get(k[0])                # a hit refreshes: 1 2 3 0
    c.put(k[1])                       # a re-put refreshes: 2 3 0 1
    c.put(k[4])                       # evicts 2: 3 0 1 4
    c.put(k[5])                       # evicts 3: 0 1 4 5
    assert len(c) == 4
    assert c.get_many(_rows(k)) == [True, True, False, False, True, True]
    assert (c.hits, c.misses) == (5, 2)


def test_lru_matches_a_reference_under_random_operations(store):
    """Bulk probes and masked puts with repeated keys, against a plain
    OrderedDict model of the same LRU."""
    rng = random.Random(7)
    cap = 16
    c = av.VerifiedSigCache(maxsize=cap)
    model: OrderedDict = OrderedDict()
    universe = [_key(i) for i in range(40)]
    for _ in range(300):
        batch = [rng.choice(universe) for _ in range(rng.randrange(1, 25))]
        if rng.random() < 0.5:
            want = []
            for k in batch:
                want.append(k in model)
                if k in model:
                    model.move_to_end(k)
            assert c.get_many(_rows(batch)) == want
        else:
            mask = bytes(rng.random() < 0.7 for _ in batch)
            for k, keep in zip(batch, mask):
                if keep:
                    model[k] = True
                    model.move_to_end(k)
                    while len(model) > cap:
                        model.popitem(last=False)
            assert c.put_many(_rows(batch), mask) == sum(mask)
        assert len(c) == len(model)
    assert c.get_many(_rows(list(model))) == [True] * len(model)


def test_keys_ground_onto_one_prefix_stay_an_exact_lru(store):
    """1,500 keys that share their first 8 bytes (what a slot was once
    taken from, raw): the table grows, evicts and erases through one
    long probe run, and answers as the OrderedDict model does."""
    rng = random.Random(11)
    cap = 1000
    c = av.VerifiedSigCache(maxsize=cap)
    model: OrderedDict = OrderedDict()
    keys = [b"\xab" * 8 + rng.randbytes(24) for _ in range(1500)]
    for lo in range(0, len(keys), 100):
        c.put_many(_rows(keys[lo:lo + 100]))
        for k in keys[lo:lo + 100]:
            model[k] = True
        while len(model) > cap:
            model.popitem(last=False)
    assert len(c) == cap
    assert c.get_many(_rows(keys)) == [k in model for k in keys]


def test_a_shrink_at_run_time_evicts_and_zero_disables(store, monkeypatch):
    monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "8")
    c = av.VerifiedSigCache()                 # unpinned: the env at every call
    k = [_key(i) for i in range(8)]
    c.put_many(_rows(k))
    assert len(c) == 8
    monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "3")
    assert c.get_many(_rows(k[5:])) == [True] * 3   # the three most recent
    assert len(c) == 3
    assert c.get_many(_rows(k[:5])) == [False] * 5
    monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "0")
    assert not c.get(k[7])
    assert c.put_many(_rows([_key(99)])) == 0
    monkeypatch.setenv("TM_TPU_VERIFY_CACHE", "3")
    assert not c.get(_key(99))


def test_only_the_rows_of_the_mask_are_stored(store):
    c = av.VerifiedSigCache(maxsize=16)
    k = [_key(i) for i in range(5)]
    assert c.put_many(_rows(k), bytes([1, 0, 1, 0, 0])) == 2
    assert c.get_many(_rows(k)) == [True, False, True, False, False]


def _triples(n, bad=(), tag=b"sigcache"):
    items, want = [], []
    for i in range(n):
        k = priv_key_from_seed(bytes([(i % 250) + 1]) * 32)
        m = b"%s-%d" % (tag, i)
        s = k.sign(m)
        if i in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
        items.append((k.pub_key().bytes_(), m, s))
        want.append(i not in bad)
    return items, want


def test_a_rejected_row_is_never_stored_and_a_hit_skips_the_queue(store):
    s = av.reset_service(linger_ms=1.0)
    try:
        assert (type(s.cache._store) is sigcache_native.NativeStore) == (
            store == "native")
        items, want = _triples(12, bad=(0, 5, 11), tag=b"poison-%s" % store.encode())
        assert s.verify_many(items) == want
        keys = av.VerifiedSigCache.keys(*zip(*items))
        assert s.cache.get_many(keys) == want
        st0 = av.service_stats()
        assert s.verify_many(items) == want           # the 9 valid rows hit
        st1 = av.service_stats()
        assert st1["cache_hits"] - st0["cache_hits"] == 9
        assert st1["submitted"] - st0["submitted"] == 3
        assert st1["cache_size"] == 9
        # a single row's key (hashlib) finds what the column's put stored
        pub = priv_key_from_seed(bytes([2]) * 32).pub_key()   # row 1's
        assert av.verify_one(pub, items[1][1], items[1][2]) is True
        assert av.service_stats()["cache_hits"] == st1["cache_hits"] + 1
    finally:
        av.reset_service()


def test_a_10k_submit_and_its_land_make_no_object_per_row(store):
    """The cache's passes of a 10,000-row submit (keys, probe) and of its
    flush (the put, with the cache full so that it evicts) leave fewer
    than 100 new objects tracked by the collector; a key tuple a row
    left 10,000."""
    n = 10_000

    def cols(tag):
        h = [hashlib.sha256(b"%s%d" % (tag, i)).digest() for i in range(n)]
        return h, [x * 4 for x in h], [x * 2 for x in h]

    s = av.VerifyService(linger_ms=0.0, cache_size=n)
    s._ensure_worker_locked = lambda: None     # this thread plays the worker

    def submit_and_land(rows):
        group = s._submit(*rows)
        batch = s._collect(block=False)
        s._land(batch.segs, [True] * len(batch))
        return group

    try:
        submit_and_land(cols(b"warm"))         # fills the cache
        rows = cols(b"measured")
        gc.collect()
        gc.disable()
        try:
            before = len(gc.get_objects())
            group = submit_and_land(rows)
            grew = len(gc.get_objects()) - before
        finally:
            gc.enable()
        assert group.future.result(timeout=1.0) == [True] * n
        assert len(s.cache) == n                # 10,000 evicted, 10,000 in
        assert grew < 100, grew
    finally:
        s.close()
