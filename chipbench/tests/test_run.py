"""The rest of a run, driven without the look for a chip (XLA-CPU, the
configurations' rehearse sizes) through the entry points of
chipbench/entries/: the result line's shape, the control, and `correct`
coming out false once for each fault a cell can have — half of the batch
left out, and an answer altered where it is produced.  (A state left
unchanged does not exist on this path; the verdicts of a sharded flush
are gathered by the same `_resolve` the faults are planted in.)

One Bench is started for the whole file: the first warm-up traces and
compiles the rung-96 program on XLA-CPU (~2 min, then cached in
.jax_cache).  The pool is cut to 8 commits and each window walks it once,
so every special commit is called and the verified-signature cache is
never met twice.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import control, correct, generator, manifest
from chipbench import run as runner

# the first cell of each entry point: on XLA-CPU a four-chip cell
# rehearses as the one-chip cell of its entry point does
_M = manifest.load()
CELLS = list({manifest.cell(_M, w["name"])["config_file"]["entry"]: w["name"]
              for w in reversed(_M["workloads"])}.values())


@pytest.fixture(scope="module")
def benches():
    out = {}
    first = True
    for name in CELLS:
        b = runner.Bench(name, rehearse=True)
        b.traffic = {**b.traffic, "pool": {"min_commits": 8, "cache_factor": 0.0}}
        if first:
            b.find_device()
            b.start(True)
        else:
            b.device, b.system, b.watch = (out[CELLS[0]].device, out[CELLS[0]].system,
                                           out[CELLS[0]].watch)
        out[name] = b
        first = False
    d = out[CELLS[0]].build(1)
    out[CELLS[0]].ready(1)
    out[CELLS[0]].warm(d)
    return out


def _window(bench, seed, plant=None):
    """Set-up as a run makes it, then (a fault planted underneath, and) one
    window over the whole pool."""
    d = bench.build(seed)
    bench.warm(d)
    if plant:
        plant()
    return bench.window(d, seed, 0.0, False, min_calls=len(d.pool))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_line_has_the_contract_keys(benches, cell):
    b = benches[cell]
    w = _window(b, 2**31 + 11)
    assert w["ok"], (w["compared"], w["detail"])
    res = b.result(w, 12.5, trace=False)
    assert list(res)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert set(res["metrics"]) == {m["name"] for m in manifest.end_to_end(b.manifest, cell)}
    assert {"verify_p50_ms", "verify_p95_ms", "setup_s"} <= set(res["metrics"])
    assert all(set(v) == {"value", "unit"} for v in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    assert res["attempted"] == 8 and res["failed"] == 0
    kinds = {c.outcome[0] for c in w["obs"].calls}
    assert kinds == {"accept", "wrong_signature"}
    json.dumps(res)


@pytest.mark.parametrize("cell", CELLS)
def test_fault_half_of_the_batch_left_out(benches, cell, monkeypatch):
    from tendermint_tpu.crypto import async_verify as av

    orig = av.VerifyService._resolve

    def half(self, reqs, oks, path="host"):
        oks = [bool(v) for v in oks]
        oks[len(oks) // 2:] = [True] * (len(oks) - len(oks) // 2)
        return orig(self, reqs, oks, path)

    w = _window(benches[cell], 2**31 + 12,
                lambda: monkeypatch.setattr(av.VerifyService, "_resolve", half))
    assert not w["ok"] and w["compared"]["calls_wrong"]["value"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_fault_an_answer_altered_where_it_is_produced(benches, cell, monkeypatch):
    from tendermint_tpu.crypto import async_verify as av

    orig = av.VerifyService._resolve

    def flipped(self, reqs, oks, path="host"):
        oks = [bool(v) for v in oks]
        oks[0] = not oks[0]
        return orig(self, reqs, oks, path)

    w = _window(benches[cell], 2**31 + 13,
                lambda: monkeypatch.setattr(av.VerifyService, "_resolve", flipped))
    assert not w["ok"] and w["compared"]["calls_wrong"]["value"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_fault_flush_resolved_on_the_host(benches, cell, monkeypatch):
    """A host fallback is a failed run, not a slow one."""
    from tendermint_tpu.crypto import async_verify as av

    def refuse(self, reqs, inflight):
        raise RuntimeError("device refused (planted)")

    w = _window(benches[cell], 2**31 + 14,
                lambda: monkeypatch.setattr(av.VerifyService, "_enqueue_device", refuse))
    assert not w["ok"]
    assert w["compared"]["calls_wrong"]["value"] == 0   # the host's verdicts are right
    assert w["compared"]["rows_off_device"]["value"] > 0
    assert w["compared"]["host_flushes"]["value"] > 0
    av.get_service().stats["device_errors"] = 0  # do not leak into the next test


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [2**31 + 21, 2**31 + 22, 2**31 + 23])
def test_control_comes_out_not_correct(benches, cell, seed):
    b = benches[cell]
    d = b.build(seed)
    calls, _, _ = generator.run_window(b.traffic, d.pool, control.bound(b.entry, d), 0.0,
                                       min_calls=len(d.pool))
    ok, compared = correct.compared(correct.check_calls(b.entry, d, calls, seed))
    assert not ok and compared["calls_wrong"]["value"] >= 1


def test_second_lap_meets_the_cache_and_says_so(benches):
    b = benches[CELLS[0]]
    d = b.build(2**31 + 15)
    b.warm(d)
    w = b.window(d, 2**31 + 15, 0.0, False, min_calls=2 * len(d.pool))
    assert not w["ok"] and w["compared"]["cache_hits"]["value"] > 0


def test_without_a_chip_there_is_no_result_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=manifest.ROOT, timeout=300)
    assert p.returncode == runner.EXIT_NO_CHIP
    assert p.stdout.strip() == ""
    assert "stage 'device' failed" in p.stderr and "jax.devices() returned" in p.stderr


def test_unknown_workload_is_a_usage_error():
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload", "nope",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=manifest.ROOT, timeout=120)
    assert p.returncode == runner.EXIT_USAGE and p.stdout.strip() == ""
