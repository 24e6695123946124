"""Sanitizer + concurrency suite for the C++ KV engine.

The reference runs its whole test matrix under the Go race detector
(SURVEY §5.2, coverage.yml -race).  The equivalent for this framework's
native boundary: build src/native/tmdb.cpp with ASan+UBSan
(`make asan`), run a multi-threaded stress through the real ctypes
binding in a subprocess (LD_PRELOAD'd libasan), and fail on any
sanitizer report.  ctypes releases the GIL during C calls, so the
threads genuinely race inside the engine — its internal mutex is what
is under test.
"""

import os
import shutil
import subprocess
import sys
import threading

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src", "native")
NATIVE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "tendermint_tpu", "native")

STRESS = r"""
import os, sys, threading
import tendermint_tpu.store.native_db as ndb
ndb._LIB_NAME = "libtmdb_asan.so"
from tendermint_tpu.store.native_db import NativeDB

path = sys.argv[1]
db = NativeDB(path)
errors = []

def worker(wid):
    try:
        for i in range(300):
            k = b"w%d-k%d" % (wid, i % 40)
            db.set(k, b"v" * (i % 97 + 1))
            db.get(k)
            if i % 7 == 0:
                db.delete(k)
            if i % 23 == 0:
                db.write_batch([(b"b%d" % wid, b"x" * 64)], [b"w%d-k0" % wid])
            if i % 31 == 0:
                list(db.iterate(b"w"))
            if i % 53 == 0:
                db.compact()
    except Exception as e:  # noqa: BLE001
        errors.append(repr(e))

threads = [threading.Thread(target=worker, args=(w,)) for w in range(8)]
[t.start() for t in threads]
[t.join() for t in threads]
db.sync(); db.close()

# crash-recovery under sanitizer: reopen and read back
db2 = NativeDB(path)
n = sum(1 for _ in db2.iterate(b""))
db2.close()
assert not errors, errors
print("STRESS-OK", n)
"""


def _libasan() -> str | None:
    gxx = shutil.which("g++")
    if gxx is None:
        return None
    out = subprocess.run([gxx, "-print-file-name=libasan.so"],
                         capture_output=True, text=True)
    p = out.stdout.strip()
    return p if p and os.path.sep in p and os.path.exists(p) else None


@pytest.mark.slow
def test_native_engine_under_asan_concurrent_stress(tmp_path):
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    asan = _libasan()
    if asan is None:
        pytest.skip("libasan not found")
    build = subprocess.run(["make", "-C", SRC, "asan"],
                           capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr

    env = dict(os.environ)
    env["LD_PRELOAD"] = asan
    # leak detection off: the host python interpreter is not ASan-clean
    env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
    env["JAX_PLATFORMS"] = "cpu"  # the chip belongs to one process: not this child
    proc = subprocess.run(
        [sys.executable, "-c", STRESS, str(tmp_path / "kv.db")],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=os.path.dirname(SRC.rstrip(os.sep).rsplit(os.sep, 1)[0]),
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert "STRESS-OK" in proc.stdout
    for marker in ("ERROR: AddressSanitizer", "runtime error:"):
        assert marker not in proc.stderr, proc.stderr[-3000:]


def test_native_engine_concurrent_stress_plain(tmp_path):
    """The same concurrency stress on the regular build — always runs
    (no sanitizer dependency), catching crashes/data races that
    manifest as corruption."""
    from tendermint_tpu.store.native_db import NativeDB

    db = NativeDB(str(tmp_path / "kv.db"))
    errors: list[str] = []

    def worker(wid: int):
        try:
            for i in range(200):
                k = b"w%d-k%d" % (wid, i % 40)
                db.set(k, b"v" * (i % 97 + 1))
                db.get(k)
                if i % 7 == 0:
                    db.delete(k)
                if i % 31 == 0:
                    list(db.iterate(b"w"))
                if i % 53 == 0:
                    db.compact()
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    db.sync()
    db.close()
    assert not errors, errors

    db2 = NativeDB(str(tmp_path / "kv.db"))
    assert db2.size() >= 0
    for k, v in db2.iterate(b""):
        assert k and v
    db2.close()


SIGNBYTES_STRESS = r"""
import random, sys
import tendermint_tpu.crypto.signbytes_native as sbn
sbn._LIB_NAME = "libedhost_asan.so"
from tendermint_tpu.types.basic import BlockID, BlockIDFlag, GO_ZERO_TIME_NS, PartSetHeader
from tendermint_tpu.types.commit import Commit, CommitSig

assert sbn._load() is not None, "sanitized kernel must load — a silent "\
    "fallback to the Python path would pass this test without ever "\
    "executing C under ASan"

rng = random.Random(5)
for case in range(8):
    n = rng.choice([64, 101, 500])
    sigs = []
    for i in range(n):
        ts = rng.choice([GO_ZERO_TIME_NS, 0, 1, -1, 10**9 - 1,
                         rng.randrange(-10**18, 10**18)])
        sigs.append(CommitSig(
            block_id_flag=rng.choice([BlockIDFlag.COMMIT, BlockIDFlag.NIL]),
            validator_address=bytes([i % 256]) * 20,
            timestamp_ns=ts, signature=b"s" * 64))
    commit = Commit(height=rng.randrange(1, 2**62), round=rng.randrange(0, 2**31 - 1),
                    block_id=BlockID(hash=bytes([case]) * 32,
                                     part_set_header=PartSetHeader(total=1, hash=bytes([case + 1]) * 32)),
                    signatures=sigs)
    chain = "x" * rng.choice([1, 49, 200])
    got = commit.vote_sign_bytes_batch(chain, range(n))
    want = [commit.vote_sign_bytes(chain, i) for i in range(n)]
    assert got == want, case
print("SIGNBYTES-OK")
"""


@pytest.mark.slow
def test_signbytes_kernel_under_asan(tmp_path):
    """tmed_batch_sign_bytes under ASan+UBSan: adversarial timestamps
    (Go zero time, negatives, nanos boundaries), both BlockID flavors,
    odd batch sizes, long chain IDs — byte-identity asserted against the
    Python path inside the sanitized process."""
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    asan = _libasan()
    if asan is None:
        pytest.skip("libasan not found")
    build = subprocess.run(["make", "-C", SRC, "asan"],
                           capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr

    env = dict(os.environ)
    env["LD_PRELOAD"] = asan
    env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", SIGNBYTES_STRESS],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(SRC.rstrip(os.sep).rsplit(os.sep, 1)[0]),
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert "SIGNBYTES-OK" in proc.stdout
    for marker in ("ERROR: AddressSanitizer", "runtime error:"):
        assert marker not in proc.stderr, proc.stderr[-3000:]


BATCH_VERIFY_STRESS = r"""
import random, sys
import tendermint_tpu.utils.host_prep as hp
hp._LIB_NAME = "libedhost_asan.so"
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

lib = hp.load_lib()
assert lib is not None, "sanitized kernel must load"
if not lib.tmed_have_libcrypto():
    print("NO-LIBCRYPTO")  # environment without libcrypto: nothing to stress
    sys.exit(0)

rng = random.Random(7)
privs = [Ed25519PrivateKey.from_private_bytes(bytes([i + 1]) * 32)
         for i in range(80)]
pubs = [p.public_key().public_bytes_raw() for p in privs]
for case in range(6):
    n = rng.choice([16, 33, 80])
    msgs = [bytes([case]) * rng.choice([0, 1, 7, 300]) or b"" for _ in range(n)]
    msgs = [m + b"m%d" % i for i, m in enumerate(msgs)]
    sigs = [p.sign(m) for p, m in zip(privs[:n], msgs)]
    bad = set(rng.sample(range(n), k=max(1, n // 7)))
    for b in bad:
        sigs[b] = bytes(64) if b % 2 else sigs[b][:-1] + bytes([sigs[b][-1] ^ 1])
    # force the multi-threaded chunking path even on a 1-core box
    oks = hp.batch_verify_native(pubs[:n], msgs, sigs, n_threads=4)
    assert oks is not None
    got_bad = {i for i, v in enumerate(oks) if not v}
    assert got_bad == bad, (case, got_bad, bad)
print("BATCHVERIFY-OK")
"""


@pytest.mark.slow
def test_batch_verify_kernel_under_asan(tmp_path):
    """tmed_batch_verify under ASan+UBSan: mixed-validity batches, odd
    sizes, zero-length and long messages, forced 4-thread chunking (the
    path a 1-core box never takes naturally) — verdict correctness
    asserted inside the sanitized process."""
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    asan = _libasan()
    if asan is None:
        pytest.skip("libasan not found")
    build = subprocess.run(["make", "-C", SRC, "asan"],
                           capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr

    env = dict(os.environ)
    env["LD_PRELOAD"] = asan
    env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", BATCH_VERIFY_STRESS],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(SRC.rstrip(os.sep).rsplit(os.sep, 1)[0]),
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert ("BATCHVERIFY-OK" in proc.stdout) or ("NO-LIBCRYPTO" in proc.stdout)
    for marker in ("ERROR: AddressSanitizer", "runtime error:"):
        assert marker not in proc.stderr, proc.stderr[-3000:]


SIGCACHE_STRESS = r"""
import os, random
import tendermint_tpu.crypto.sigcache_native as sn
sn._LIB_NAME = "libedhost_asan.so"
from tendermint_tpu.crypto import async_verify as av

assert sn.available(), "sanitized kernel must load"
rng = random.Random(3)
c = av.VerifiedSigCache(maxsize=50)
for rnd in range(300):
    n = rng.randrange(0, 300)
    pubs = [os.urandom(rng.choice([32, 33, 0, 300])) for _ in range(n)]
    sigs = [os.urandom(rng.choice([64, 65, 0])) for _ in range(n)]
    msgs = [os.urandom(rng.choice([0, 120, 5000])) for _ in range(n)]
    keys = av.VerifiedSigCache.keys(pubs, msgs, sigs)
    for i in range(n):
        assert keys[i].tobytes() == av.VerifiedSigCache.key(pubs[i], msgs[i], sigs[i])
    c._pinned_maxsize = rng.choice([0, 1, 5, 50, 2000])   # shrinks and growths
    c.probe(keys)
    c.put_many(keys, bytes(rng.random() < 0.5 for _ in range(n)))
    assert c.maxsize == 0 or len(c) <= c.maxsize   # 0: the store untouched
print("SIGCACHE-OK")
"""


@pytest.mark.slow
def test_sigcache_kernels_under_asan(tmp_path):
    """tmed_row_keys and tmed_sigcache_* under ASan+UBSan: rows wider than
    the one-update buffer, empty columns, masked puts, the capacity
    shrinking and growing between calls (evictions, table growth) — keys
    asserted against hashlib inside the sanitized process."""
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    asan = _libasan()
    if asan is None:
        pytest.skip("libasan not found")
    build = subprocess.run(["make", "-C", SRC, "asan"],
                           capture_output=True, text=True, timeout=180)
    assert build.returncode == 0, build.stderr

    env = dict(os.environ)
    env["LD_PRELOAD"] = asan
    env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", SIGCACHE_STRESS],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(SRC.rstrip(os.sep).rsplit(os.sep, 1)[0]),
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert "SIGCACHE-OK" in proc.stdout
    for marker in ("ERROR: AddressSanitizer", "runtime error:"):
        assert marker not in proc.stderr, proc.stderr[-3000:]
