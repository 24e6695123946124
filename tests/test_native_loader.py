"""utils/native_loader staleness: a library is rebuilt when the SHA-256
stamp beside it does not match the tree's current source (a copied tree
carries stale binaries along, and a copy resets mtimes), and
build_report() says which happened.  The compiler is stubbed — the real
build is exercised by every suite that loads the libraries."""

import os

from tendermint_tpu.utils import native_loader as nl


def test_stale_library_is_rebuilt_and_fresh_one_is_not(tmp_path, monkeypatch):
    native = tmp_path / "native"
    native.mkdir()
    monkeypatch.setattr(nl, "native_dir", lambda: str(native))
    built = []

    def fake_build(lib_name, make_target, digest):
        built.append(make_target)
        (native / lib_name).write_bytes(b"\x7fELF")
        (native / (lib_name + ".src-sha256")).write_text(digest + "\n")
        return None

    monkeypatch.setattr(nl, "_build", fake_build)
    assert nl._ensure_fresh("libedhost.so", "edhost") == "built"
    assert nl._ensure_fresh("libedhost.so", "edhost") == "loaded"
    # the source changed under a library that came along with the tree
    (native / "libedhost.so.src-sha256").write_text("0" * 64 + "\n")
    assert nl._ensure_fresh("libedhost.so", "edhost") == "rebuilt"
    # a library with no stamp at all (built before stamps existed)
    os.unlink(native / "libedhost.so.src-sha256")
    assert nl._ensure_fresh("libedhost.so", "edhost") == "rebuilt"
    assert built == ["edhost"] * 3

    # a failed rebuild keeps the old binary but says so; with no binary
    # at all it is a failure
    monkeypatch.setattr(nl, "_build", lambda *a: "g++: not found")
    os.unlink(native / "libedhost.so.src-sha256")
    assert nl._ensure_fresh("libedhost.so", "edhost").startswith(
        "loaded-unverified: stale library kept, rebuild failed: g++")
    assert nl._ensure_fresh("libtmdb.so", "tmdb").startswith("failed:")

    # a sanitizer variant the caller built itself is not this target's
    (native / "libtmdb_asan.so").write_bytes(b"\x7fELF")
    assert nl._ensure_fresh("libtmdb_asan.so", "tmdb") == "loaded-variant"
