"""The cell `votes-128.live` (entry `consensus_votes`): its data from the
seed, its sizes against the configuration's `expect`, the entry's shape —
a lap's first call on a spare node, wire bytes decoded per call, a loud
error when the spares run out, a wait that times out raising — on the
host path at 8 validators, its three readers on hand-made spans, and —
rehearsed on XLA-CPU in a process of its own (96 validators: two flushes
of 96 rows a height) — a sound run that is `correct`, and `correct`
coming out false for each fault the consensus path can have: a refused
verdict not recorded (a forged vote verified again alone), and no tick
precheck at all (every vote verified alone)."""

import asyncio
import json
import math
import os
import subprocess
import sys
import tempfile

import pytest

from chipbench import control, correct, generator, manifest
from chipbench.observe import Observation
from chipbench.reference import vote_rules as rules

M = manifest.load()
CELL = manifest.cell(M, "votes-128.live")
CFG = CELL["config_file"]
TRAFFIC = CELL["traffic_file"]
ENTRY = manifest.entry(CFG["entry"])
SMALL = {**CFG, "validators": 8, "height_floor_s": 60.0}
SMALL_POOL = {"min_commits": 4, "cache_factor": 0.0}
MS = 1_000_000
NEW = ("precheck_ms", "vote_handle_ms", "finalize_ms")


@pytest.fixture(autouse=True)
def node_homes(tmp_path, monkeypatch):
    """The spare nodes' homes, which `build` makes in the temp dir."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))


@pytest.fixture
def host_service():
    from tendermint_tpu.crypto import async_verify as av
    from tendermint_tpu.crypto.batch import set_default_backend

    set_default_backend("cpu")
    av.clear_service()
    yield
    av.clear_service()
    set_default_backend("auto")


def _build(seed, sizes=SMALL, pool=SMALL_POOL):
    return ENTRY.build(seed, CFG, sizes, 65536, pool, TRAFFIC["warmup_commits"])


def _bytes(d):
    return [[it.row(i) for i in range(it.n_rows)] + [it.bursts] for it in d.pool + d.warmup]


def test_seeded_data_is_the_same_twice_and_differs_by_seed():
    a, b, c = _build(7), _build(7), _build(2**31 + 5)
    assert _bytes(a) == _bytes(b) != _bytes(c)
    assert [len(x.pool) for x in (a, b, c)] == [4] * 3
    assert all(isinstance(raw, bytes) for it in a.pool for burst in it.bursts for raw in burst)


def test_published_sizes_are_what_the_configuration_expects():
    """128 validators, 256 rows a height in two bursts of 128 (rung 128),
    a pool of consecutive heights that outgrows the cache, the forged rows
    of a lap, and spare nodes for the laps a window can make."""
    from tendermint_tpu.ops.ed25519_jax import _bucket

    exp = CFG["expect"]
    assert TRAFFIC["callers"] == 1 and TRAFFIC["think_ms"] == 0
    d = _build(2**31 + 9, CFG, TRAFFIC["pool"])
    assert len(d.pool) == exp["pool_heights"] == 320
    assert [it.height for it in d.pool] == list(range(1, 321))
    assert {it.n_rows for it in d.pool} == {exp["rows_per_call"]}
    assert {tuple(map(len, it.bursts)) for it in d.pool} == {(2, 128, 128)}
    assert _bucket(exp["rows_per_flush"]) == exp["rung"] == 128
    assert len(d.pool) * exp["rows_per_call"] >= 1.25 * 65536
    forged = [r for it in d.pool for r, k in it.suspects.items() if k == "sig_bit"]
    assert len(forged) == 2 * CFG["adversarial"]["forged_heights"] + 1
    small = {r for r, k in d.pool[0].suspects.items() if k == "small_order"}
    assert len(small) == 2 * CFG["adversarial"]["small_order_validators"]
    last_honest = max(i for i in range(128) if i not in small)
    assert 128 + last_honest in forged          # a forged precommit after the commit
    assert all(it.planned[0] == "committed" for it in d.pool + d.warmup)
    nodes = d.pool[0].chain.nodes
    assert len(nodes) == math.ceil(M["run_seconds"] * 1.5 / (0.008 * 320)) >= 24
    assert [it.height for it in d.warmup] == [1, 2] and all(it.warm for it in d.warmup)


def test_a_lap_runs_on_a_spare_node_and_the_spares_run_out_loudly(host_service):
    d = _build(11, {**SMALL, "height_floor_s": 40.0 * 1.5 / (2 * 4)})   # two laps
    chain = d.pool[0].chain
    assert len(chain.nodes) == 2
    call = ENTRY.bind(d)
    laps = []
    for _ in range(2):
        laps.append([call(it) for it in d.pool])
        assert chain.taken == len(laps) and chain.nodes[len(laps) - 1] is None
    assert laps[0] == laps[1] == [it.planned for it in d.pool]
    assert any(said[1][3] for said in laps[0])      # the forged rows refused
    with pytest.raises(RuntimeError, match="all 2 spare nodes used"):
        call(d.pool[0])
    with pytest.raises(RuntimeError, match="the node is at height 5, the call brings 2"):
        call(d.pool[1])


def test_a_wait_that_times_out_raises():
    with pytest.raises(RuntimeError, match="no answer from the consensus loop in 0.05s"):
        ENTRY._on_loop(asyncio.sleep(5), "a probe", 0.05)


def test_the_rule_and_the_control(host_service):
    """The reference's answer for every height, and the strict verifier in
    the program's place refusing the small-order rows: calls_wrong > 0."""
    d = _build(13)
    calls = generator.run_window(TRAFFIC, d.pool, control.bound(ENTRY, d), 0.0,
                                 min_calls=len(d.pool))[0]
    numbers = correct.check_calls(ENTRY, d, calls, 13)
    assert numbers["calls_wrong"] == len(d.pool)
    sound = [ENTRY.expected(d, it, lambda i, it=it: i not in it.suspects
                            or it.suspects[i] == "small_order") for it in d.pool]
    assert sound == [it.planned for it in d.pool]
    assert rules.implied(sound[0], 0) is (0 not in sound[0][1][3])
    assert rules.cache_hits(127, True) == 4 * 127 and rules.cache_hits(127, False) == 3 * 127


# -- the readers ---------------------------------------------------------------


def _span(name, t0_ms, dur_ms, sid=0, parent=None, **attrs):
    return {"name": name, "id": sid, "parent": parent, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "tid": 1, "attrs": attrs}


def _obs(spans, calls=2):
    return Observation(cell={}, device={}, calls=[object()] * calls, window_s=1.0,
                       before={}, after={}, compiles_in_window=0, spans=spans,
                       trace=None, slice=None)


def test_readers_on_hand_made_spans():
    """Two heights: three ticks each (proposal, prevotes, precommits), two
    prechecks, the per-message loops, one commit inside the last loop."""
    spans = []
    for h, t in ((1, 0.0), (2, 50.0)):
        spans += [
            _span("consensus.tick", t, 3.0, msgs=2, votes=0),
            _span("consensus.handle", t + 0.5, 2.0, msgs=2),
            _span("consensus.tick", t + 5, 20.0, msgs=128, votes=128),
            _span("consensus.precheck", t + 5.5, 8.0, rows=128, valid=127, refused=1),
            _span("consensus.handle", t + 14, 10.0, msgs=128),
            _span("consensus.tick", t + 30, 21.0, msgs=128, votes=128),
            _span("consensus.precheck", t + 30.5, 9.0, rows=128, valid=128, refused=0),
            _span("consensus.handle", t + 40, 11.0, msgs=128),
            _span("consensus.finalize", t + 44, 4.0 + h, height=h, txs=0),
        ]
    obs = _obs(spans)
    read = lambda name: manifest.reader(name)(obs)  # noqa: E731
    assert read("precheck_ms") == pytest.approx((8 + 9) * 2 / 2)
    assert read("vote_handle_ms") == pytest.approx((2 + 10 + 11) * 2 / 2)
    assert read("finalize_ms") == pytest.approx(5.5)
    # a program without the consensus's spans (the parent) reads nothing
    old = _obs([_span("verify.submit", 0, 10.0, n=128), _span("consensus.step", 0, 1.0)])
    entries = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:
        assert manifest.reader(name)(old) is None
        assert entries[name]["workloads"] == ["votes-128.live"]
        assert entries[name]["layer"] == "consensus" and entries[name]["moves"] == "verify_p50_ms"
    mine = [m["name"] for m in manifest.per_layer(M, "votes-128.live")]
    assert set(NEW) <= set(mine)


# -- the rehearsal and the planted faults, in a process of its own ----------

SCRIPT = r'''
import json, os, sys
sys.path.insert(0, os.getcwd())
from chipbench import run as runner

b = runner.Bench("votes-128.live", rehearse=True)
b.traffic = {**b.traffic, "pool": {"min_commits": 6, "cache_factor": 0.0}}
b.find_device()
b.start(True)


def window(seed, plant=None, undo=None):
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
            "rows": obs.rows(), "outcomes": sorted({c.outcome[0] for c in obs.calls}),
            "flushes": obs.after["flushes"] - obs.before["flushes"],
            "compared": {k: v["value"] for k, v in w["compared"].items()}}


from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.types.vote import Vote

mark, precheck = Vote.mark_sig_verdict, ConsensusState._precheck_vote_sigs


def unrecorded():
    # the parent's precheck: only a valid verdict is carried on the vote
    Vote.mark_sig_verdict = lambda v, c, pk, ok: mark(v, c, pk, ok) if ok else None


def no_precheck():
    ConsensusState._precheck_vote_sigs = lambda self, batch: None


def undo():
    Vote.mark_sig_verdict, ConsensusState._precheck_vote_sigs = mark, precheck


print(json.dumps({"sound": window(1), "unrecorded": window(2, unrecorded, undo),
                  "no_precheck": window(3, no_precheck, undo)}), flush=True)
os._exit(0)
'''


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "TMPDIR": str(tmp_path_factory.mktemp("homes"))}
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True, text=True,
                       env=env, cwd=manifest.ROOT, timeout=2400)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_is_correct_with_every_number_zero(rehearsed):
    w = rehearsed["sound"]
    assert w["ok"] and not any(w["compared"].values()), w["compared"]
    assert list(w["compared"]) == [
        "calls_wrong", "sampled_rows_wrong", "rows_off_device", "host_flushes",
        "device_errors", "cache_hits_off", "flushes_off", "host_verifies_off",
        "reverified", "compiles_in_window", "route_other"]
    assert w["calls"] == 6 and w["rows"] == 6 * 192 and w["flushes"] == 12
    assert w["route"] == ["device", "pipelined"] and w["outcomes"] == ["committed"]


def test_fault_a_refused_verdict_not_recorded(rehearsed):
    """A forged vote verified a second time, alone, on the loop's thread:
    the answers are right, the once-counter is not."""
    w = rehearsed["unrecorded"]
    assert not w["ok"] and w["compared"]["calls_wrong"] == 0
    assert w["compared"]["reverified"] >= 1
    assert w["compared"]["host_verifies_off"] == w["compared"]["reverified"]


def test_fault_no_tick_precheck(rehearsed):
    """Every vote verified alone, on the loop's thread: the answers are
    right, the once-counter counts every vote."""
    w = rehearsed["no_precheck"]
    assert not w["ok"] and w["compared"]["calls_wrong"] == 0
    assert w["compared"]["reverified"] == 6 * 192
