"""chip_smoke.py's contract, as far as a machine without a chip can show
it: with no accelerator and no dry-run argument it exits non-zero and
prints no result line; outside a checkout likewise; and the explicit CPU
dry run — the same control flow at toy sizes — passes every stage and
says what it is (`dry_run: true`, `platform: "cpu"`), so it can never be
mistaken for a run on the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd=ROOT, timeout=120.0):
    return subprocess.run([sys.executable, os.path.basename(SMOKE), *args],
                          cwd=cwd, capture_output=True, text=True,
                          timeout=timeout)


def _result_lines(stdout: str) -> list[str]:
    return [ln for ln in stdout.splitlines() if ln.lstrip().startswith("{")]


def test_no_chip_means_nonzero_exit_and_no_result(tmp_path):
    # the suite holds JAX to the CPU: no accelerator, no dry-run argument
    out = _run([])
    assert out.returncode == 3, (out.returncode, out.stderr[-400:])
    assert _result_lines(out.stdout) == []
    assert "no accelerator" in out.stderr

    # a directory that holds chip_smoke.py and nothing else of the repo
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(["--dry-run-cpu"], cwd=tmp_path)
    assert out.returncode != 0
    assert _result_lines(out.stdout) == []


@pytest.mark.slow
def test_dry_run_cpu_passes_every_stage_and_says_what_it_is():
    """~30 s on a warm compile cache (its one program, rung 64, is one
    the suite compiles); about a minute more on a cold one."""
    out = _run(["--dry-run-cpu"], timeout=900.0)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    lines = out.stdout.strip().splitlines()
    # the result line: last on stdout, these keys and no others
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert _result_lines(out.stdout) == [lines[-1]]
    # the full summary: the line before it
    assert lines[-2].startswith("summary: ")
    doc = json.loads(lines[-2][len("summary: "):])
    assert doc["device"] == result["device"]
    assert doc["ok"] is True and doc["checks_failed"] == []
    assert doc["dry_run"] is True
    assert doc["device"]["platform"] == "cpu"
    assert doc["stages_run"] == [0, 1, 2, 3]
    assert list(doc)[-1] == "claim" and doc["claim"] is None
    assert doc["programs_total"] == 1 and doc["programs"][0]["rung"] == 64
    s3 = doc["stages"]["3_node"]
    assert s3["follower_height"] >= 19
    assert set(doc["stages"]["2_full_width"]["small_sets"]) == {
        "verify_commit_8", "verify_commit_light_16"}
    assert s3["rpc_status_verify_service"]["platform"] == "cpu"
