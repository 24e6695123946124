"""Shared loader for the in-tree C++ libraries.

Both native boundaries (the KV engine, store/native_db.py, and the
crypto host-prep kernel, ops/host_prep.py) follow the same pattern:
the .so lives in tendermint_tpu/native/, is built from src/native/ by a
named make target on first use, and is bound via ctypes.  This helper
owns that pattern so diagnostics and build behavior can't drift between
the two (they already had once).

Staleness: the .so files are build outputs (gitignored), but a copied
tree carries them along, source edits or not.  Every build therefore
leaves a stamp beside the library holding the SHA-256 of what it was
built from (the source file and the Makefile); a library whose stamp
does not match the tree's current sources is rebuilt before it is
loaded.  A hash, not an mtime: a copy resets mtimes in arbitrary order.
`build_report()` says what happened to each library in this process.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import subprocess

_log = logging.getLogger("tendermint_tpu.utils.native_loader")

# make target -> (the library it produces, the one source file it
# compiles) — src/native/Makefile
_TARGETS = {"tmdb": ("libtmdb.so", "tmdb.cpp"),
            "edhost": ("libedhost.so", "edhost.cpp")}

# lib_name -> what happened on this process's load: "loaded" (stamp
# matched), "built" (no library was present), "rebuilt" (library was
# stale), "loaded-variant" (a sanitizer build the caller made itself),
# "loaded-unverified: ..." (no source tree to compare against, or the
# rebuild failed and the old binary was kept), "failed: ..."
_REPORT: dict[str, str] = {}


def build_report() -> dict[str, str]:
    return dict(_REPORT)


def native_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")


def src_dir() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
        "src",
        "native",
    )


def _source_digest(make_target: str) -> str | None:
    """SHA-256 over the Makefile and the target's source, or None when
    the source tree is not present (an installed package)."""
    h = hashlib.sha256()
    try:
        for name in ("Makefile", _TARGETS[make_target][1]):
            with open(os.path.join(src_dir(), name), "rb") as fh:
                h.update(fh.read())
    except OSError:
        return None
    return h.hexdigest()


def _read_stamp(path: str) -> str | None:
    try:
        with open(path + ".src-sha256") as fh:
            return fh.read().strip()
    except OSError:
        return None


def _build(lib_name: str, make_target: str, digest: str) -> str | None:
    """Force-build `make_target` and stamp it; returns an error string
    on failure.  -B: make's own mtime test is exactly what a copied tree
    defeats."""
    try:
        subprocess.run(
            ["make", "-B", "-C", src_dir(), make_target],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as e:
        detail = ""
        if isinstance(e, subprocess.CalledProcessError) and e.stderr:
            detail = ": " + e.stderr.decode(errors="replace")[-500:]
        return f"{e}{detail}"
    with open(os.path.join(native_dir(), lib_name) + ".src-sha256", "w") as fh:
        fh.write(digest + "\n")
    return None


def _ensure_fresh(lib_name: str, make_target: str) -> str:
    """Bring tendermint_tpu/native/<lib_name> up to date with its
    source; returns the build_report() status.  Serialized across
    processes by a lock file: nodes of one test net start together and
    must not run two compilers over one output."""
    path = os.path.join(native_dir(), lib_name)
    if lib_name != _TARGETS[make_target][0] and os.path.exists(path):
        # e.g. libtmdb_asan.so: built by the sanitizer suite's own
        # `make asan`, not by this target — nothing here to keep fresh
        return "loaded-variant"
    digest = _source_digest(make_target)
    if digest is None:
        if os.path.exists(path):
            return "loaded-unverified: no source tree to compare against"
        return f"failed: {lib_name} missing and source tree {src_dir()} not present"
    os.makedirs(native_dir(), exist_ok=True)
    with open(os.path.join(native_dir(), ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        existed = os.path.exists(path)
        if existed and _read_stamp(path) == digest:
            return "loaded"
        err = _build(lib_name, make_target, digest)
    if err is None:
        return "rebuilt" if existed else "built"
    hint = f"run `make -C {src_dir()} {make_target}`"
    if existed:
        return f"loaded-unverified: stale library kept, rebuild failed: {err}; {hint}"
    return f"failed: not built and build failed: {err}; {hint}"


def load_native_lib(lib_name: str, make_target: str, required: bool):
    """Load tendermint_tpu/native/<lib_name>, building `make_target` in
    src/native/ first when missing or stale.

    required=True: raise RuntimeError with the build diagnostic on any
    failure (the KV engine — the caller asked for db_backend=native).
    required=False: return None on any failure (optional fast-path
    kernels fall back to pure Python) — logged at warning level and
    kept in build_report(), never silent."""
    status = _ensure_fresh(lib_name, make_target)
    if not status.startswith("failed"):
        try:
            lib = ctypes.CDLL(os.path.join(native_dir(), lib_name))
        except OSError as e:
            status = f"failed: cannot load: {e}"
    _REPORT[lib_name] = status
    if status.startswith("failed"):
        if required:
            raise RuntimeError(f"{lib_name}: {status}")
        _log.warning("%s unavailable, falling back to pure Python: %s",
                     lib_name, status)
        return None
    if status.startswith("loaded-unverified"):
        _log.warning("%s: %s", lib_name, status)
    return lib
