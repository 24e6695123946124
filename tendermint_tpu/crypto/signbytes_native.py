"""Native batched canonical sign-bytes assembly (jax-free wrapper).

Binds src/native/edhost.cpp's `tmed_batch_sign_bytes`: one C call emits
every delimited canonical precommit row for a commit (~40 ns/row vs
~4 µs/row for the Python template path — 0.4 ms vs 40 ms on a 10k
commit).  Lives under crypto/ (not ops/) so the types layer can use it
without importing the jax-backed ops package.
"""

from __future__ import annotations

import ctypes
import threading

import numpy as np

from tendermint_tpu.utils.native_loader import load_native_lib

_LIB_NAME = "libedhost.so"
_lock = threading.Lock()
_lib = None
_failed = False


def _load():
    global _lib, _failed
    with _lock:
        if _lib is not None or _failed:
            return _lib
        lib = load_native_lib(_LIB_NAME, "edhost", required=False)
        if lib is None or not hasattr(lib, "tmed_batch_sign_bytes"):
            _failed = True
            return None
        lib.tmed_batch_sign_bytes.argtypes = [
            ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.tmed_batch_sign_bytes.restype = ctypes.c_uint64
        _lib = lib
        return _lib


_NS = 1_000_000_000


def _split_exact(ts_ns) -> tuple[np.ndarray, np.ndarray]:
    """(seconds, nanos) by Python's divmod, a row at a time: exact for
    timestamps beyond the int64-nanosecond range (Go's zero time is
    ~-6.2e19 ns)."""
    n = len(ts_ns)
    secs = np.empty(n, dtype=np.int64)
    nanos = np.empty(n, dtype=np.int32)
    for i, t in enumerate(ts_ns):
        s, nan = divmod(t, _NS)
        # wrap into int64 two's complement exactly like the Python
        # path's encode_varint_signed: adversarially decoded timestamps
        # (seconds=2^63-1 with nanos >= 1e9) push s past int64 and must
        # produce the same bytes — and a clean bad-signature rejection —
        # not an OverflowError out of the verify path
        secs[i] = ((s + (1 << 63)) % (1 << 64)) - (1 << 63)
        nanos[i] = nan
    return secs, nanos


def batch_sign_bytes(prefix_block: bytes, prefix_nil: bytes, suffix: bytes,
                     flags, ts_ns) -> tuple[bytes, list[int], bool] | None:
    """(buffer, offsets[n+1], exact_ts) of delimited rows, or None when
    the native kernel is unavailable (callers fall back to the Python
    template).  flags: per-row bool, True = COMMIT prefix; ts_ns: per-row
    nanoseconds, any Python int.  `exact_ts`: a timestamp lay outside
    int64, so the split ran row by row."""
    lib = _load()
    if lib is None:
        return None
    n = len(ts_ns)
    try:
        # every timestamp inside int64: ONE conversion and ONE floored
        # divmod (numpy's floors like Python's), and the seconds need no
        # wrap because they are smaller still
        ts = np.array(ts_ns, dtype=np.int64)
    except OverflowError:
        exact_ts = True
        secs, nanos = _split_exact(ts_ns)
    else:
        exact_ts = False
        secs, nanos = np.divmod(ts, _NS)
        nanos = nanos.astype(np.int32)
    flags = bytes(flags)  # one byte a row, no array built
    if len(flags) != n:
        raise ValueError("batch_sign_bytes: flags and ts_ns of unequal length")
    cap = n * (max(len(prefix_block), len(prefix_nil)) + len(suffix) + 40) + 16
    out = np.empty(cap, dtype=np.uint8)
    offsets = np.zeros(n + 1, dtype=np.uint64)
    total = lib.tmed_batch_sign_bytes(
        ctypes.c_uint64(n),
        prefix_block, ctypes.c_uint64(len(prefix_block)),
        prefix_nil, ctypes.c_uint64(len(prefix_nil)),
        suffix, ctypes.c_uint64(len(suffix)),
        flags,
        secs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        nanos.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_uint64(cap),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    if total == 0:
        return None
    return out[:total].tobytes(), offsets.tolist(), exact_ts
