"""Native host-side batch preprocessing for the Ed25519 verifier.

ctypes binding for src/native/edhost.cpp: one C call computes
k = SHA-512(R || A || M) mod L for the whole batch, threaded across
cores.  The Python fallback (hashlib + bigint per row) costs ~4.7us/row
— ~50ms for a 10k-validator commit, 25x the BASELINE.md 2ms end-to-end
target — so the native path is what keeps host prep out of the latency
budget.  Built by `make -C src/native` (attempted automatically, same
pattern as store/native_db.py).
"""

from __future__ import annotations

import ctypes
import threading

import numpy as np

from tendermint_tpu.utils.native_loader import load_native_lib

_LIB_NAME = "libedhost.so"
_lib = None
_lib_failed = False
_lib_lock = threading.Lock()


def load_lib():
    """Returns the loaded library or None (never raises): callers fall
    back to the Python loop when the toolchain is unavailable."""
    global _lib, _lib_failed
    with _lib_lock:
        if _lib is not None or _lib_failed:
            return _lib
        lib = load_native_lib(_LIB_NAME, "edhost", required=False)
        if lib is None:
            _lib_failed = True
            return None
        lib.tmed_batch_k.argtypes = [
            ctypes.c_uint64,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
        ]
        lib.tmed_batch_k.restype = None
        if hasattr(lib, "tmed_batch_verify"):
            lib.tmed_batch_verify.argtypes = [
                ctypes.c_uint64,
                ctypes.c_char_p,
                ctypes.c_char_p,
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_int,
            ]
            lib.tmed_batch_verify.restype = ctypes.c_int
        _lib = lib
        return _lib


def batch_k_native(r_rows: np.ndarray, pub_rows: np.ndarray,
                   msgs, n_threads: int = 0) -> np.ndarray | None:
    """k rows [N,32] (little-endian scalars mod L), or None when the
    native kernel is unavailable.  r_rows/pub_rows: [N,32] uint8."""
    lib = load_lib()
    if lib is None:
        return None
    n = len(msgs)
    lens = np.fromiter((len(m) for m in msgs), dtype=np.uint64, count=n)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(lens, out=offsets[1:])
    msg_buf = b"".join(msgs)
    out = np.zeros((n, 32), dtype=np.uint8)
    r_c = np.ascontiguousarray(r_rows)
    pub_c = np.ascontiguousarray(pub_rows)
    lib.tmed_batch_k(
        ctypes.c_uint64(n),
        ctypes.cast(r_c.ctypes.data, ctypes.c_char_p),
        ctypes.cast(pub_c.ctypes.data, ctypes.c_char_p),
        msg_buf,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int(n_threads),
    )
    return out


def batch_verify_native(pubs, msgs, sigs, n_threads: int = 0) -> list[bool] | None:
    """Whole-batch libcrypto Ed25519 verification in ONE C call
    (src/native/edhost.cpp tmed_batch_verify): no per-item Python
    dispatch, GIL released, threaded across cores.  Returns per-item
    verdicts with libcrypto's strict RFC-8032 semantics — the CALLER
    must re-check rejected rows against the permissive pure ZIP-215
    reference (ed25519.verify) to keep consensus verdicts bit-identical
    (same contract as ed25519.verify_fast).  Returns None when the
    native kernel or libcrypto is unavailable, or when any row has
    malformed sizes (those batches take the Python path, which handles
    them item by item)."""
    lib = load_lib()
    if lib is None or not hasattr(lib, "tmed_batch_verify"):
        return None
    n = len(msgs)
    if n == 0:
        return []
    # the C kernel indexes pub32+32*i and sig64+64*i for i < n: a
    # length mismatch would read past the concatenated buffers
    if len(pubs) != n or len(sigs) != n:
        return None
    if any(len(p) != 32 for p in pubs) or any(len(s) != 64 for s in sigs):
        return None
    lens = np.fromiter((len(m) for m in msgs), dtype=np.uint64, count=n)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    np.cumsum(lens, out=offsets[1:])
    msg_buf = b"".join(msgs)
    pub_cat = b"".join(pubs)
    sig_cat = b"".join(sigs)
    out = np.zeros(n, dtype=np.uint8)
    rc = lib.tmed_batch_verify(
        ctypes.c_uint64(n),
        pub_cat,
        sig_cat,
        msg_buf,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int(n_threads),
    )
    if rc != 0:
        return None
    return [bool(v) for v in out]
