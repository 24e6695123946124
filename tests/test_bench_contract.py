"""bench.py output contract: EXACTLY one JSON line with
metric/value/unit/vs_baseline on stdout, whatever happens to the
backend — and an exit code that tells a measured run (0) from a failed
one (non-zero).  A run that finds no TPU fails; CPU is what you get only
by asking for it, and its numbers never carry a device metric's name.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REQUIRED = {"metric", "value", "unit", "vs_baseline"}


def _run_bench(env_extra: dict, timeout: float, want_ok: bool = True) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "TM_BENCH_BACKENDS"}
    env.update(env_extra)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=ROOT,
        env=env,
    )
    assert (out.returncode == 0) == want_ok, (out.returncode,
                                              out.stderr[-500:])
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"want exactly 1 stdout line, got {lines!r}"
    doc = json.loads(lines[0])
    assert REQUIRED <= set(doc), doc
    return doc


def test_partial_flush_lands_after_every_stage(tmp_path, monkeypatch):
    """ISSUE 8 satellite: `_stage_set` flushes the stages measured so
    far to disk, so a watchdog KILL mid-stage (the round-5 driver run failure:
    tail stages vanished) loses at most the in-flight stage."""
    import bench

    out = tmp_path / "partial.json"
    monkeypatch.setenv("TM_BENCH_PARTIAL", str(out))
    monkeypatch.setattr(bench, "_partial", {"value": 123.4})
    monkeypatch.setattr(bench, "_stage", "stage-one")
    bench._stage_set("stage-two")  # flushes everything measured so far
    doc = json.loads(out.read_text())
    assert doc["stage"] == "stage-one"  # the last COMPLETED stage
    assert doc["value"] == 123.4
    assert doc["elapsed_s"] >= 0

    # atomic replace: the next stage overwrites, no .tmp litter
    bench._partial["more"] = 1
    bench._stage_set("stage-three")
    doc = json.loads(out.read_text())
    assert doc["stage"] == "stage-two" and doc["more"] == 1
    assert list(tmp_path.iterdir()) == [out]

    # TM_BENCH_PARTIAL=0 disables the flush entirely
    out.unlink()
    monkeypatch.setenv("TM_BENCH_PARTIAL", "0")
    bench._stage_set("stage-four")
    assert not out.exists()


def test_partial_flush_survives_unwritable_path(monkeypatch):
    """A read-only cwd must not cost the bench (the flush is advisory)."""
    import bench

    monkeypatch.setenv("TM_BENCH_PARTIAL", "/nonexistent-dir/partial.json")
    monkeypatch.setattr(bench, "_partial", {})
    bench._stage_set("whatever")  # must not raise


@pytest.mark.slow
def test_bench_emits_one_json_line_on_cpu():
    """Happy-ish path: tiny batch on the CPU backend (compile cache makes
    this a few minutes at worst, seconds when warm)."""
    doc = _run_bench(
        {
            "TM_BENCH_BACKENDS": "cpu",
            "TM_BENCH_N": "8",
            "TM_BENCH_RUNS": "1",
            "TM_BENCH_DEADLINE": "420",
        },
        timeout=460,
    )
    # a host number under a host name: never the device metric's
    assert doc["metric"] == "host_ed25519_sig_verifies_per_sec"
    assert doc["backend"] == "cpu"
    assert doc["value"] > 0
    assert "host_commit8_p50_ms" in doc  # honest label for the tiny batch
    assert not any(k.startswith("commit") for k in doc)


@pytest.mark.slow
@pytest.mark.parametrize("backends", [None, "no_such_platform"])
def test_bench_fails_nonzero_without_the_backend_it_is_for(backends):
    """Failure path: no TPU (the default platform — the suite runs with
    JAX held to the CPU) or an impossible platform name still produces
    one parseable JSON line (value 0 + error + stage), exits NON-ZERO,
    and falls back to no CPU number."""
    doc = _run_bench(
        {"TM_BENCH_DEADLINE": "120",
         **({"TM_BENCH_BACKENDS": backends} if backends else {})},
        timeout=150, want_ok=False,
    )
    assert doc["value"] == 0 and doc["vs_baseline"] == 0
    assert "error" in doc and doc["stage"] == "backend-init"
    assert doc["metric"] == "ed25519_sig_verifies_per_sec"
