"""The cell `light-1000.fanin` (entry `gateway_fanin`): its data from the
seed, its sizes against the configuration's `expect`, the rule file, its
four readers on hand-made spans, the control, and — rehearsed on XLA-CPU
in a process of its own (120 validators: flushes of 81 to 486 rows, five
rungs) — a sound run that is `correct`, and `correct` coming out false for
each fault a gateway can have: a refused flush verified again job by job
(what `_flush_individually` did), a header two clients ask for at once."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import control, correct, generator, manifest
from chipbench.observe import Observation
from chipbench.reference import fanin_rules as rules

M = manifest.load()
CELL = manifest.cell(M, "light-1000.fanin")
CFG = CELL["config_file"]
TRAFFIC = CELL["traffic_file"]
ENTRY = manifest.entry(CFG["entry"])
SMALL_POOL = {"min_commits": 8, "cache_factor": 0.0}
REHEARSE = {**CFG, **CFG["rehearse"]}
MS = 1_000_000


def _build(seed, sizes, pool=SMALL_POOL):
    return ENTRY.build(seed, CFG, sizes, 65536, pool, TRAFFIC["warmup_commits"])


def _bytes(d):
    return [[pc.row(i) for i in range(pc.n_rows)] for pc in d.pool
            + [pc for b in d.warmup for pc in b.commits]]


def test_seeded_data_is_the_same_twice_and_differs_by_seed():
    a, b, c = _build(7, REHEARSE), _build(7, REHEARSE), _build(2**31 + 5, REHEARSE)
    assert _bytes(a) == _bytes(b) != _bytes(c)
    assert [len(x.pool) for x in (a, b, c)] == [12] * 3     # 8, topped up to a multiple of 6


def test_published_sizes_are_what_the_configuration_expects():
    """1,000 validators, 667 rows a header; a pool the six callers share
    out evenly and that outgrows the cache; warm-up item k = k headers no
    pool item holds, meeting the rungs the configuration lists."""
    from tendermint_tpu.ops.ed25519_jax import _bucket

    exp, fan = CFG["expect"], CFG["clients_in_flight"]
    assert TRAFFIC["callers"] == fan == exp["jobs_per_flush"] and TRAFFIC["think_ms"] == 0
    d = _build(2**31 + 9, CFG, TRAFFIC["pool"])
    assert len(d.pool) == 126 and len(d.pool) % fan == 0
    assert {pc.n_rows for pc in d.pool} == {exp["rows_per_call"]} == {rules.consulted(d.powers)}
    assert len(d.pool) * exp["rows_per_call"] >= 1.25 * 65536
    assert exp["rows_per_flush"] == fan * exp["rows_per_call"]
    assert _bucket(exp["rows_per_flush"]) == exp["rung"]
    assert [len(b.commits) for b in d.warmup] == list(range(1, fan + 1))
    assert [b.n_rows for b in d.warmup] == [k * 667 for k in range(1, fan + 1)]
    assert sorted({_bucket(b.n_rows) for b in d.warmup}) == exp["rungs_warmed"]
    heights = [pc.height for b in d.warmup for pc in b.commits]
    assert len(set(heights)) == 21 and not set(heights) & {pc.height for pc in d.pool}
    # the adversarial rows are light-1000's: 8 small-order keys in every
    # header, 3 headers with one corrupted row, one of them past the cut
    bad = [(pc.n_rows, {r: k for r, k in pc.suspects.items() if k != "small_order"})
           for pc in d.pool if set(pc.suspects.values()) != {"small_order"}]
    assert sorted(k for _, s in bad for k in s.values()) == ["sig_bit", "sig_bit", "timestamp"]
    assert sum(r >= n for n, s in bad for r in s) == 1
    assert all(sum(k == "small_order" for k in pc.suspects.values()) == 8 for pc in d.pool)


def test_the_rule_alone_isolation_and_once():
    seed = 2**31 + 31
    d = _build(seed, REHEARSE)
    from chipbench.reference import ed25519_zip215 as ref

    calls = [generator.Call(k, 0.0, 0.1, ENTRY.expected(
        d, pc, lambda i, pc=pc: ref.verify(*pc.row(i))), pc.n_rows)
        for k, pc in enumerate(d.pool)]
    assert sorted(c.outcome[0] for c in calls) == ["accept"] * 10 + ["wrong_signature"] * 2
    numbers = correct.check_calls(ENTRY, d, calls, seed)
    assert numbers["calls_wrong"] == 0 == numbers["sampled_rows_wrong"]
    # a gateway that fails a whole flush for one forged header
    k = next(k for k, c in enumerate(calls) if c.outcome[0] == "accept")
    calls[k].outcome = ("wrong_signature", 3)
    assert correct.check_calls(ENTRY, d, calls, seed)["calls_wrong"] == 1
    # once, from the calls made alone
    seen = {"rows_resolved_on_device": 12 * 81, "cache_hits": 0, "service_flushes": 2,
            "gateway_flushes": 2, "gateway_jobs_flushed": 12, "gateway_coalesced": 0,
            "gateway_shed": 0}
    assert not any(rules.once(12, 12 * 81, seen).values())
    # a refused flush of six verified again job by job: six more service
    # flushes, the five proven headers answered from the cache
    again = {**seen, "service_flushes": 8, "cache_hits": 5 * 81 + 40,
             "rows_resolved_on_device": 12 * 81 + 1}
    off = rules.once(12, 12 * 81, again)
    assert off["flushes_off"] == 6 and off["cache_hits"] == 445 and off["rows_off_device"] == -1
    assert rules.once(12, 12 * 81, {**seen, "gateway_jobs_flushed": 11,
                                    "gateway_coalesced": 1}) == {
        "rows_off_device": 0, "cache_hits": 0, "flushes_off": 0, "jobs_off": -1,
        "coalesced": 1, "shed": 0}


def test_the_rule_file_imports_nothing_of_the_program():
    src = open(rules.__file__).read()
    assert "tendermint_tpu" not in src
    assert [ln for ln in src.splitlines() if ln.startswith(("import ", "from "))] == [
        "from __future__ import annotations",
        "from chipbench.reference.commit_rules import consulted_rows, expected_outcome"]


@pytest.mark.parametrize("seed", [2**31 + 21, 2**31 + 22])
def test_control_comes_out_not_correct(seed):
    d = _build(seed, REHEARSE)
    calls, _, _ = generator.run_window(TRAFFIC, d.pool, control.bound(ENTRY, d), 0.0,
                                       min_calls=len(d.pool) // TRAFFIC["callers"])
    assert len(calls) == len(d.pool) == len({c.item for c in calls})   # dealt, none twice
    ok, compared = correct.compared(correct.check_calls(ENTRY, d, calls, seed))
    # every header whose consulted rows hold a small-order key
    assert not ok and compared["calls_wrong"]["value"] >= 10


# -- the readers --------------------------------------------------------------


def _span(name, t0_ms, dur_ms, tid=1, **attrs):
    return {"name": name, "id": 0, "parent": None, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "tid": tid, "attrs": attrs}


def _obs(spans):
    return Observation(cell={}, device={}, calls=[], window_s=1.0, before={}, after={},
                       compiles_in_window=0, spans=spans, trace=None, slice=None)


NEW = ("fanin_jobs_per_flush", "gateway_wait_ms", "gateway_flush_ms", "gateway_wake_ms")


def test_readers_on_hand_made_spans():
    spans = [
        # flush 1: six jobs that waited 2, 2, 2, 1, 1, 1 ms; the verify call
        # 20 ms; the clients woken 0.5 … 3.0 ms after it
        _span("gateway.linger", 0, 2.0, tid=9, flush=1, jobs=6),
        _span("gateway.flush", 2, 20.0, tid=9, flush=1, jobs=6, rows=6000,
              wait_sum_ns=9 * MS),
        _span("gateway.resolve", 22, 1.0, tid=9, flush=1, jobs=6, refused=1),
        *[_span("gateway.wait", 0.1 * k, 22.5 + 0.5 * k - 0.1 * k, tid=k, jobs=1, flush=1)
          for k in range(6)],
        # flush 2: two jobs that waited 3 ms each; 10 ms; woken 1 and 2 ms after
        _span("gateway.flush", 30, 10.0, tid=9, flush=2, jobs=2, rows=2000,
              wait_sum_ns=6 * MS),
        _span("gateway.wait", 27, 14.0, tid=1, jobs=1, flush=2),
        _span("gateway.wait", 27, 15.0, tid=2, jobs=1, flush=2),
        # a wait whose flush fell out of the window: paired with nothing
        _span("gateway.wait", 1, 1.0, tid=3, jobs=1, flush=0),
        # a wait that ended before its flush's span did (a clock's grain): 0
        _span("gateway.wait", 30, 9.9, tid=4, jobs=1, flush=2),
    ]
    obs = _obs(spans)
    read = lambda name: manifest.reader(name)(obs)  # noqa: E731
    assert read("fanin_jobs_per_flush") == 4.0
    assert read("gateway_wait_ms") == pytest.approx(15 / 8)
    assert read("gateway_flush_ms") == pytest.approx(15.0)
    wakes = [0.5 + 0.5 * k for k in range(6)] + [1.0, 2.0, 0.0]
    assert read("gateway_wake_ms") == pytest.approx(sum(wakes) / len(wakes))
    # a program without the gateway's spans (the parent) reads nothing
    old = _obs([_span("verify.submit", 0, 10.0, n=10), _span("commit.select", 0, 1.0)])
    entries = {m["name"]: m for m in M["per_layer"]}
    for name in NEW:
        assert manifest.reader(name)(old) is None
        assert entries[name]["workloads"] == ["light-1000.fanin"]
        assert entries[name]["layer"] == "gateway" and entries[name]["moves"] == "verify_p50_ms"
    # the cell reports its own four and the fifteen no list keeps from it
    mine = [m["name"] for m in manifest.per_layer(M, "light-1000.fanin")]
    assert len(mine) == 19 and set(NEW) <= set(mine)


# -- the rehearsal and the planted faults, in a process of its own ----------

SCRIPT = r'''
import json, os, sys
sys.path.insert(0, os.getcwd())
from chipbench import run as runner

b = runner.Bench("light-1000.fanin", rehearse=True)
b.traffic = {**b.traffic, "pool": {"min_commits": 8, "cache_factor": 0.0}}
b.find_device()
b.start(True)


def window(seed, plant=None, undo=None):
    d = b.build(seed)
    if seed == 1:
        b.ready(seed)
    b.warm(d)
    if plant:
        plant(d)
    try:
        # one lap: each of the six callers walks its two headers once
        w = b.window(d, seed, 0.0, False, min_calls=len(d.pool) // 6)
    finally:
        if undo:
            undo()
    obs = w["obs"]
    return {"ok": w["ok"], "route": w["route"], "calls": len(obs.calls),
            "rows": obs.rows(), "outcomes": sorted(c.outcome[0] for c in obs.calls),
            "flushes": obs.after["flushes"] - obs.before["flushes"],
            "programs": sorted({p["rung"] for p in b.system["programs"]}),
            "compared": {k: v["value"] for k, v in w["compared"].items()}}


from tendermint_tpu.types.validator import batch_verify_commits

sound = window(1)
gw = b.entry._the_gateway()


def reverify(d):
    # what _flush_individually did: the raise-only verifier, and a refused
    # flush verified again job by job
    gw.coalescer._verify_fn = batch_verify_commits


def duplicate(d):
    # two callers ask for one header at the same moment
    d.pool[1] = d.pool[0]


def undo():
    gw.coalescer._verify_fn = None


print(json.dumps({"sound": sound, "reverify": window(2, reverify, undo),
                  "duplicate": window(3, duplicate, undo),
                  "sound_again": window(4)}), flush=True)
os._exit(0)
'''


@pytest.fixture(scope="module")
def rehearsed():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True, text=True,
                       env=env, cwd=manifest.ROOT, timeout=2400)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("which", ["sound", "sound_again"])
def test_rehearsal_is_correct_with_every_number_zero(rehearsed, which):
    w = rehearsed[which]
    assert w["ok"] and not any(w["compared"].values()), w["compared"]
    assert list(w["compared"]) == [
        "calls_wrong", "sampled_rows_wrong", "rows_off_device", "host_flushes",
        "device_errors", "cache_hits", "compiles_in_window", "route_other",
        "flushes_off", "jobs_off", "coalesced", "shed"]
    assert w["calls"] == 12 and w["rows"] == 12 * 81 and w["flushes"] <= 12
    assert w["route"] == ["device", "pipelined"]
    assert w["outcomes"] == ["accept"] * 10 + ["wrong_signature"] * 2
    # the warm-up went through the gateway: one rung a fan-in, k x 81 rows
    assert set(w["programs"]) >= {96, 192, 256, 384, 512}


def test_fault_a_refused_flush_verified_again(rehearsed):
    w = rehearsed["reverify"]
    assert not w["ok"] and w["compared"]["calls_wrong"] == 0   # the answers are right
    assert w["compared"]["flushes_off"] >= 2                   # … and cost a second pass
    assert w["compared"]["cache_hits"] + 81 * w["compared"]["host_flushes"] >= 81


def test_fault_a_header_two_clients_ask_for_at_once(rehearsed):
    w = rehearsed["duplicate"]
    assert not w["ok"]
    assert w["compared"]["coalesced"] >= 1 or w["compared"]["cache_hits"] >= 81
