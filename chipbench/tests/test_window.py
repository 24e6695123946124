"""The cell `replay-200.window` (entry `blocksync_window`): its data from
the seed, its sizes against the plain reference's rule, its two readers on
hand-made spans, and — rehearsed on XLA-CPU in a process of its own (the
rung-96 program of the rehearse sizes) — a sound run that is `correct`,
and `correct` coming out false once for each fault a window step can have:
a job dropped from the step, the cut ignored (the service then cuts the
step into two flushes), the count of blocks taken altered, and a step that
refuses a run whose corrupted block lies past the cut."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from chipbench import correct, generator, manifest
from chipbench.observe import Observation
from chipbench.reference import window_rules

M = manifest.load()
CELL = manifest.cell(M, "replay-200.window")
CFG = CELL["config_file"]
ENTRY = manifest.entry(CFG["entry"])
SMALL_POOL = {"min_commits": 8, "cache_factor": 0.0}
MS = 1_000_000

# recorded from `build` when the entry was written (PR 28; rehearse sizes, a
# pool of 8 runs, 2 warm-ups): a later change to the entry's data shows here
DIGESTS = {
    7: "59213b61111512d704250433d9402ec06f52f443c56875b66022a114067a5ceb",
    2**31 + 5: "91264ce9f2c7f05873cdbf591880866c148b18f5b3e22c65ef73c7aff71540ec",
}


def _build(seed, sizes):
    return ENTRY.build(seed, CFG, sizes, 65536, SMALL_POOL, 2)


def _digest(d) -> str:
    h = hashlib.sha256()

    def put(*parts):
        for p in parts:
            b = p if isinstance(p, bytes) else repr(p).encode()
            h.update(len(b).to_bytes(4, "big"))
            h.update(b)

    put(d.powers, d.step_args, *d.pubs)
    for run in d.pool + d.warmup:
        put(run.step, run.offsets, run.n_rows, sorted(run.suspects.items()), run.warm,
            run.state.last_block_height, run.state.last_block_id.hash)
        for b in run.blocks:
            put(b.header.height, b.hash(), b.encode())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_seeded_data_is_what_was_recorded(seed):
    d = _build(seed, {**CFG, **CFG["rehearse"]})
    assert _digest(d) == DIGESTS[seed]
    assert _digest(_build(seed, {**CFG, **CFG["rehearse"]})) == DIGESTS[seed]


def test_rehearse_sizes_against_the_rule():
    d = _build(7, {**CFG, **CFG["rehearse"]})
    assert d.step_args == (96,)
    for run in d.pool + d.warmup:
        assert run.n_rows == 3 * 24 + 17 == 89 and len(run.blocks) == 6
        assert run.step == [("full", 0), ("full", 1), ("full", 2), ("light", 3)]
    kinds = sorted(k for run in d.pool for k in run.suspects.values() if k != "small_order")
    assert kinds == ["sig_bit", "timestamp"]          # the third lies past the cut:
    assert [k for run in d.pool for pc in run.commits[4:] for k in pc.suspects.values()
            if k != "small_order"] == ["sig_bit"]     # no consulted row of its run


def test_published_sizes_are_what_the_configuration_expects():
    """200 validators, 96 blocks offered: 80 taken, 81 jobs, 16,134 rows
    at rung 16,384 — the program's default flush, no `max_rows` passed."""
    from tendermint_tpu.crypto.async_verify import MAX_COALESCE
    from tendermint_tpu.ops.ed25519_jax import _bucket

    d = _build(2**31 + 9, CFG)
    exp = CFG["expect"]
    assert d.step_args == () and len(d.pool) == 8 and len(d.warmup) == 2
    for run in d.pool + d.warmup:
        assert len(run.blocks) == CFG["downloaded_blocks"] == 96
        assert run.n_rows == exp["rows_per_call"] == 80 * 200 + 134
        assert len(run.step) == exp["jobs_per_call"] == exp["applied_per_call"] + 1
        assert _bucket(run.n_rows) == exp["rung"] == MAX_COALESCE
        # the rule's count of the step (an upper bound on the rows) fits one flush
        assert 81 * 200 <= MAX_COALESCE < 82 * 200
    # small-order rows sit in every commit; in the pair check only those under +2/3
    assert all(len(run.suspects) <= 2 * 81 + 1 for run in d.pool)
    # every block of a run chains to the one before, by the program's own hash
    run = d.pool[3]
    for a, b in zip(run.blocks, run.blocks[1:]):
        assert b.header.last_block_id.hash == a.hash()
        assert b.header.validators_hash == d.vset.hash()
        assert b.last_commit.block_id == b.header.last_block_id


def test_a_step_that_refuses_a_run_corrupted_past_the_cut_is_wrong():
    """The answers of a sound step by the rule alone, then the planted one."""
    seed = 2**31 + 31
    d = _build(seed, {**CFG, **CFG["rehearse"]})
    from chipbench.reference import ed25519_zip215 as ref

    def sound(k, run):
        out = ENTRY.expected(d, run, lambda i: ref.verify(*run.row(i)))
        return generator.Call(k, 0.0, 0.1, out, run.n_rows)

    calls = [sound(k, run) for k, run in enumerate(d.pool)]
    assert sorted(c.outcome[0] for c in calls) == ["accept"] * 6 + ["wrong_signature"] * 2
    numbers = correct.check_calls(ENTRY, d, calls, seed)
    assert numbers["calls_wrong"] == 0 and numbers["sampled_rows_wrong"] == 0
    past = next(k for k, run in enumerate(d.pool) if any(
        kind != "small_order" for pc in run.commits[4:] for kind in pc.suspects.values()))
    assert calls[past].outcome == ("accept", 3)
    bad = d.pool[past].commits[4:]
    height, row = next((pc.height, r) for pc in bad for r, kind in pc.suspects.items()
                       if kind != "small_order")
    calls[past].outcome = ("wrong_signature", (height, row))
    assert correct.check_calls(ENTRY, d, calls, seed)["calls_wrong"] == 1


# -- the two readers --------------------------------------------------------


def _span(name, t0_ms, dur_ms, tid=1, **attrs):
    return {"name": name, "id": 0, "parent": None, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "tid": tid, "attrs": attrs}


def _step(t0, jobs, build_ms, sign_ms):
    spans = [_span("blocksync.window", t0, 100.0, downloaded=6),
             _span("blocksync.window_jobs", t0, build_ms, downloaded=6)]
    for j in range(jobs):
        spans.append(_span("commit.sign_bytes", t0 + build_ms + j, sign_ms, n=24))
    return spans + [_span("commit.verify", t0 + 50, 40.0, n=89)]


def _obs(spans):
    return Observation(cell={}, device={}, calls=[], window_s=1.0, before={}, after={},
                       compiles_in_window=0, spans=spans, trace=None, slice=None)


def test_window_readers_on_hand_made_spans():
    spans = (_step(0, 4, 2.0, 0.5) + _step(200, 4, 4.0, 0.25)
             + _step(400, 2, 3.0, 1.0)[:-1])      # a step refused before its verify
    obs = _obs(spans)
    assert manifest.reader("window_build_ms")(obs) == pytest.approx(3.0)
    # sums of 4 x 0.5 and 4 x 0.25: a mean of one span would read 0.5
    assert manifest.reader("window_signbytes_ms")(obs) == pytest.approx(1.5)
    assert manifest.reader("signbytes_ms")(obs) == pytest.approx(0.5)
    # a program without the window's spans (the parent) reads nothing
    old = _obs([_span("verify.submit", 0, 10.0, n=10)])
    assert manifest.reader("window_build_ms")(old) is None
    assert manifest.reader("window_signbytes_ms")(old) is None
    entries = {m["name"]: m for m in M["per_layer"]}
    for name in ("window_build_ms", "window_signbytes_ms"):
        assert entries[name]["workloads"] == ["replay-200.window"]
        assert entries[name]["source"] == "program_span"


# -- the rehearsal and the planted faults, in a process of its own ----------

SCRIPT = r'''
import json, os, sys
sys.path.insert(0, os.getcwd())
from chipbench import run as runner

b = runner.Bench("replay-200.window", rehearse=True)
b.traffic = {**b.traffic, "pool": {"min_commits": 8, "cache_factor": 0.0}}
b.find_device()
b.start(True)


def window(seed, plant=None, undo=None, traced=False):
    d = b.build(seed)
    if seed == 1:
        b.ready(seed)
    b.warm(d)
    if plant:
        plant()
    try:
        w = b.window(d, seed, 0.0, False, min_calls=len(d.pool))
    finally:
        if undo:
            undo()
    obs = w["obs"]
    return {"ok": w["ok"], "route": w["route"], "calls": len(obs.calls),
            "rows": obs.rows(), "outcomes": sorted(c.outcome[0] for c in obs.calls),
            "taken": sorted({c.outcome[1] for c in obs.calls if c.outcome[0] == "accept"}),
            "flushes": obs.after["flushes"] - obs.before["flushes"],
            "compared": {k: v["value"] for k, v in w["compared"].items()}}


from tendermint_tpu.blocksync import reactor as bsync
from tendermint_tpu.crypto import async_verify as av

sound = window(1)
jobs, cut, step, cap = bsync.window_jobs, bsync._cut, bsync.verify_window, av.MAX_COALESCE


def job_dropped():
    def fewer(state, window, max_rows):
        applied, js = jobs(state, window, max_rows)
        return applied, js[1:]
    bsync.window_jobs = fewer


def cut_ignored():
    # the service's flush holds what the rehearsal's step may count, and the
    # step takes the whole prefix: 5 x 24 + 17 rows, two flushes
    av.MAX_COALESCE = 96
    bsync._cut = lambda state, window, max_rows: bsync._static_valset_prefix(state, window)


def count_altered():
    bsync.verify_window = lambda *a: step(*a)[:-1]


def undo():
    bsync.window_jobs, bsync._cut, bsync.verify_window = jobs, cut, step
    av.MAX_COALESCE = cap


print(json.dumps({"sound": sound,
                  "job_dropped": window(2, job_dropped, undo),
                  "cut_ignored": window(3, cut_ignored, undo),
                  "count_altered": window(4, count_altered, undo),
                  "sound_again": window(5)}), flush=True)
os._exit(0)
'''


@pytest.fixture(scope="module")
def rehearsed():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True, text=True,
                       env=env, cwd=manifest.ROOT, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("which", ["sound", "sound_again"])
def test_rehearsal_is_correct_with_the_expected_numbers_scaled(rehearsed, which):
    w = rehearsed[which]
    assert w["ok"] and not any(w["compared"].values()), w["compared"]
    assert set(w["compared"]) == {
        "calls_wrong", "sampled_rows_wrong", "rows_off_device", "host_flushes",
        "device_errors", "cache_hits", "compiles_in_window", "route_other",
        "flushes_per_call_off"}
    # 8 runs walked once: 89 rows and one flush a call, three blocks taken
    assert w["calls"] == w["flushes"] == 8 and w["rows"] == 8 * 89
    assert w["route"] == ["device", "pipelined"] and w["taken"] == [3]
    # the run corrupted past the cut is accepted, the other two refused
    assert w["outcomes"] == ["accept"] * 6 + ["wrong_signature"] * 2


def test_fault_a_job_dropped_from_the_step(rehearsed):
    w = rehearsed["job_dropped"]
    assert not w["ok"] and w["compared"]["rows_off_device"] == 8 * 24
    assert w["compared"]["flushes_per_call_off"] == 0


def test_fault_the_cut_ignored(rehearsed):
    w = rehearsed["cut_ignored"]
    assert not w["ok"] and w["compared"]["flushes_per_call_off"] >= 1
    assert w["compared"]["calls_wrong"] >= 6      # five blocks taken where three fit


def test_fault_the_count_of_blocks_taken_altered(rehearsed):
    w = rehearsed["count_altered"]
    assert not w["ok"] and w["compared"]["calls_wrong"] == 6 and w["taken"] == [2]
    assert w["compared"]["rows_off_device"] == 0 == w["compared"]["flushes_per_call_off"]
