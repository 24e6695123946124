"""Native key pass and LRU store of the verified-signature cache.

Binds src/native/edhost.cpp's `tmed_row_keys` (one SHA-256 a row over
three columns, libcrypto's low-level SHA-256) and `tmed_sigcache_*` (a
fixed-capacity LRU of 32-byte keys): a submit's key pass, its probe and
a flush's put are one C call each, with no Python object made per row.
`crypto/async_verify.VerifiedSigCache` uses them where the library and
its SHA-256 load, and its Python path (hashlib, an OrderedDict)
elsewhere.  Jax-free, like crypto/signbytes_native.
"""

from __future__ import annotations

import ctypes
import threading
from array import array

import numpy as np

from tendermint_tpu.utils.native_loader import load_native_lib

_LIB_NAME = "libedhost.so"
_lock = threading.Lock()
_lib = None
_failed = False

_P = ctypes.c_void_p
_U64 = ctypes.c_uint64


def _load():
    """The library with the key pass and the store bound, or None (never
    raises): no toolchain, a stale binary without the symbols, or no
    SHA-256 in libcrypto."""
    global _lib, _failed
    with _lock:
        if _lib is not None or _failed:
            return _lib
        lib = load_native_lib(_LIB_NAME, "edhost", required=False)
        if (lib is None or not hasattr(lib, "tmed_row_keys")
                or not lib.tmed_have_sha256()):
            _failed = True
            return None
        lib.tmed_row_keys.argtypes = [_U64, _P, _P, _P, _P, _P, _P, _P]
        lib.tmed_row_keys.restype = ctypes.c_int
        lib.tmed_sigcache_new.argtypes = []
        lib.tmed_sigcache_new.restype = _P
        lib.tmed_sigcache_free.argtypes = [_P]
        lib.tmed_sigcache_free.restype = None
        lib.tmed_sigcache_len.argtypes = [_P]
        lib.tmed_sigcache_len.restype = _U64
        lib.tmed_sigcache_probe.argtypes = [_P, _U64, _U64, _P, _P]
        lib.tmed_sigcache_probe.restype = _U64
        lib.tmed_sigcache_put.argtypes = [_P, _U64, _U64, _P, _P]
        lib.tmed_sigcache_put.restype = _U64
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _column(col) -> tuple[bytes, array]:
    """A column of `bytes` as (its rows joined, uint32 lengths)."""
    return b"".join(col), array("I", map(len, col))


def row_keys(pubs, msgs, sigs) -> np.ndarray | None:
    """uint8[n, 32]: the cache key of each row, in one C call; None when
    the library is unavailable.  The columns hold `bytes`."""
    lib = _load()
    if lib is None:
        return None
    n = len(msgs)
    if not len(pubs) == n == len(sigs):
        raise ValueError("row_keys: columns of unequal length")
    out = np.empty((n, 32), dtype=np.uint8)
    if n:
        (pb, pl), (sb, sl), (mb, ml) = map(_column, (pubs, sigs, msgs))
        lib.tmed_row_keys(n, pb, pl.buffer_info()[0], sb, sl.buffer_info()[0],
                          mb, ml.buffer_info()[0], out.ctypes.data)
    return out


class NativeStore:
    """The LRU store of 32-byte keys in native code.  Not thread-safe:
    the cache holds its lock around every call.  `keys`: a C-contiguous
    uint8[n, 32] array."""

    def __init__(self):
        lib = _load()
        self._lib = lib
        self._h = lib.tmed_sigcache_new()
        self._free = lib.tmed_sigcache_free

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._free(h)

    def probe(self, keys: np.ndarray, cap: int) -> tuple[np.ndarray, int]:
        _check(keys)
        found = np.empty(len(keys), dtype=np.uint8)
        hits = self._lib.tmed_sigcache_probe(self._h, cap, len(keys),
                                             keys.ctypes.data,
                                             found.ctypes.data)
        return found, hits

    def put(self, keys: np.ndarray, mask: bytes | None, cap: int) -> int:
        _check(keys)
        if mask is not None and len(mask) != len(keys):
            raise ValueError("sigcache put: a mask byte a key row")
        return self._lib.tmed_sigcache_put(self._h, cap, len(keys),
                                           keys.ctypes.data, mask)

    def __len__(self) -> int:
        return self._lib.tmed_sigcache_len(self._h)


def _check(keys: np.ndarray) -> None:
    """The native side reads `len(keys)` rows of 32 bytes from one
    pointer."""
    if (keys.dtype != np.uint8 or keys.ndim != 2 or keys.shape[1] != 32
            or not keys.flags.c_contiguous):
        raise ValueError("sigcache: keys must be C-contiguous uint8[n, 32]")
