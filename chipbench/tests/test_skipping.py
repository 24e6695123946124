"""The cell `skip-1000.bisect` (entry `light_skipping`): its data from the
seed, its sizes against the plain reference's rule, its readers on
hand-made spans, the control, and — rehearsed on XLA-CPU in a process of
its own (200 validators, 2 changed a block: the same geometry, flushes of
67 and ~86 rows at rung 96) — a sound run that is `correct`, and `correct`
coming out false once for each fault a skipping client can have: the error
mapping undone (a wrong signature in the trusting check pivots), the
verified-signature cache switched off, a hop's flush forced to the host,
and a pivot rule off by one."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import control, correct, generator, manifest
from chipbench.observe import Observation
from chipbench.reference import skipping_rules as rules

M = manifest.load()
CELL = manifest.cell(M, "skip-1000.bisect")
CFG = CELL["config_file"]
ENTRY = manifest.entry(CFG["entry"])
SMALL_POOL = {"min_commits": 8, "cache_factor": 0.0}
REHEARSE = {**CFG, **CFG["rehearse"]}
MS = 1_000_000


def _build(seed, sizes, pool=SMALL_POOL):
    return ENTRY.build(seed, CFG, sizes, 65536, pool, 2)


def test_seeded_data_is_the_same_twice_and_differs_by_seed():
    a, b, c = _build(7, REHEARSE), _build(7, REHEARSE), _build(2**31 + 5, REHEARSE)
    wire = lambda d: [sorted(ch.wire.items()) for ch in d.pool + d.warmup]  # noqa: E731
    assert wire(a) == wire(b) != wire(c)
    assert [ch.stored for ch in a.pool] == [ch.stored for ch in b.pool]


def test_rehearse_sizes_against_the_rule():
    d = _build(7, REHEARSE)
    assert (d.trusted_height, d.target, d.trust) == (1, 257, (1, 3))
    assert len(d.pool) == 8 and len(d.warmup) == 2
    for ch in d.warmup:
        assert ch.walk.answer == ("accept", (65, 129, 193, 257)) and ch.warm
    answers = sorted(ch.walk.answer[0] for ch in d.pool)
    assert answers == ["accept"] * 6 + ["failed"] * 2
    for ch in d.pool + d.warmup:
        w = ch.walk
        assert w.fetched == [257, 129, 65, 193] and sorted(ch.wire) == [65, 129, 193, 257]
        assert [a[2] for a in w.attempts][:6] == [
            "refused", "refused", "accepted", "accepted", "refused", "accepted"]
        # a refused jump shares no validator: no row, no flush
        assert all(not c.rows for c, (_, _, out) in zip(
            [c for c in w.checks if c.kind == "trusting"],
            [a for a in w.attempts]) if out == "refused")
        assert ch.n_rows == len(ch.flat) == sum(len(c.rows) for c in w.checks)
        # flushes of 67 rows (a third of 200 x 10, by address) and of the
        # light check's rows the trusting check had not verified: both over
        # the 64-row floor, at one rung
        for c in w.checks:
            if c.fresh:
                assert 64 <= len(c.fresh) <= 96
            if c.kind == "trusting" and c.rows:
                assert len(c.rows) == 67 and not c.shared
            if c.kind == "light":
                assert len(c.rows) == 134 and c.shared
    failed = {ch.walk.answer[1:3] + (ch.walk.flushes,) for ch in d.pool
              if ch.walk.answer[0] == "failed"}
    assert failed == {(193, 257, 8), (193, 257, 7)}    # in the light check; in the trusting one


def test_published_sizes_are_what_the_configuration_expects():
    """1,000 validators, 10 changed a block, 1 -> 257: 4 accepted hops, 3
    refused jumps, 8 flushes at rungs 384 and 512, ~4,000 rows consulted,
    and a pool that outgrows the cache in UNIQUE rows."""
    from tendermint_tpu.ops.ed25519_jax import _bucket

    d = _build(2**31 + 9, CFG, CELL["traffic_file"]["pool"])
    exp = CFG["expect"]
    assert len(d.warmup) == 2
    assert sum(ch.walk.fresh() for ch in d.pool) >= 1.25 * 65536 and len(d.pool) in (27, 28)
    for ch in d.pool + d.warmup:
        w = ch.walk
        assert w.fetched == exp["fetched"]
        assert [a[2] for a in w.attempts].count("refused") == exp["refused_jumps"]
        rungs = {_bucket(len(c.fresh)) for c in w.checks if c.fresh}
        assert rungs == set(exp["rungs"])
        assert [len(c.rows) for c in w.checks if c.kind == "trusting" and c.rows] in (
            [334] * 4, )
        if w.answer[0] == "accept":
            assert w.flushes == exp["flushes_per_call"] and ch.n_rows == exp["rows_per_call"]
            assert len(w.answer[1]) == exp["accepted_hops"]
            assert abs(w.fresh() - exp["fresh_rows_per_call"]) < 60
            assert abs(w.shared() - exp["cache_hit_rows_per_call"]) < 60
    # small-order rows sit in every fetched set and in no refused jump
    for ch in d.pool:
        small = {h for (h, _), kind in ch.kinds.items() if kind == "small_order"}
        assert small == {65, 129, 193, 257}
        assert sum(kind == "small_order" for kind in ch.kinds.values()) == 16
    # the light blocks chain to nothing but themselves: each is valid alone
    from tendermint_tpu.types.light import LightBlock
    ch = d.pool[3]
    for h, raw in ch.wire.items():
        lb = LightBlock.decode(raw)
        lb.validate_basic("chipbench")
        assert lb.height == h and len(lb.validator_set) == 1000


def test_the_three_adversarial_chains_by_the_rule_alone():
    """The answers of a sound client by the rule alone, then a planted one."""
    seed = 2**31 + 31
    d = _build(seed, REHEARSE)
    from chipbench.reference import ed25519_zip215 as ref

    def sound(k, ch):
        out = ENTRY.expected(d, ch, lambda i: ref.verify(*ch.row(i)))
        return generator.Call(k, 0.0, 0.1, out, ch.n_rows)

    calls = [sound(k, ch) for k, ch in enumerate(d.pool)]
    bad = {k: next(kind for kind in ch.kinds.values() if kind != "small_order")
           for k, ch in enumerate(d.pool) if set(ch.kinds.values()) != {"small_order"}}
    assert sorted(bad.values()) == ["sig_bit", "sig_bit", "timestamp"]
    failed = [c for c in calls if c.outcome[0][0] == "failed"]
    assert len(failed) == 2 and all(c.outcome[0][1:3] == (193, 257) for c in failed)
    assert {c.outcome[0][3][0] for c in failed} == {"wrong_signature"}
    numbers = correct.check_calls(ENTRY, d, calls, seed)
    assert numbers["calls_wrong"] == 0 and numbers["sampled_rows_wrong"] == 0
    # a client that PIVOTS on the corrupted trusting row asks for 225
    k = next(k for k, kind in bad.items() if kind == "timestamp")
    calls[k].outcome = (("failed", 193, 225, ("error", "no light block")), (257, 129, 65, 193, 225))
    assert correct.check_calls(ENTRY, d, calls, seed)["calls_wrong"] == 1


@pytest.mark.parametrize("seed", [2**31 + 21, 2**31 + 22])
def test_control_comes_out_not_correct(seed):
    d = _build(seed, REHEARSE)
    calls, _, _ = generator.run_window(CELL["traffic_file"], d.pool, control.bound(ENTRY, d),
                                       0.0, min_calls=len(d.pool))
    ok, compared = correct.compared(correct.check_calls(ENTRY, d, calls, seed))
    assert not ok and compared["calls_wrong"]["value"] >= 6


# -- the readers --------------------------------------------------------------


def _span(name, t0_ms, dur_ms, id_, parent=None, **attrs):
    return {"name": name, "id": id_, "parent": parent, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "tid": 1, "attrs": attrs}


def _obs(spans, calls=(), before=None, after=None):
    return Observation(cell={}, device={}, calls=list(calls), window_s=1.0,
                       before=before or {}, after=after or {}, compiles_in_window=0,
                       spans=spans, trace=None, slice=None)


def test_readers_on_hand_made_spans_and_counters():
    spans = [
        _span("light.fetch", 0, 9.0, 1, height=1),              # the root of trust: no call's
        _span("light.verify_to_height", 10, 100.0, 2, target=257),
        _span("light.fetch", 10, 4.0, 3, 2, height=257),
        _span("light.hop", 15, 1.0, 4, 2, outcome="refused"),
        _span("commit.select", 15, 0.5, 5, 4, mode="trusting"),
        _span("light.fetch", 16, 2.0, 6, 2, height=129),
        _span("light.hop", 20, 30.0, 7, 2, outcome="accepted"),
        _span("commit.select", 20, 1.5, 8, 7, mode="trusting"),
        _span("commit.select", 30, 7.0, 9, 7, mode="light"),
        _span("light.hop", 60, 3.0, 10, 2, outcome="refused"),
        _span("light.hop", 70, 20.0, 11, 2, outcome="accepted"),
        _span("light.store", 95, 5.0, 12, 2, blocks=2),
        _span("light.verify_to_height", 200, 50.0, 13, target=257),
        _span("light.fetch", 200, 8.0, 14, 13, height=257),
        _span("light.hop", 210, 10.0, 15, 13, outcome="failed"),
    ]
    obs = _obs(spans)
    assert manifest.reader("hop_ms")(obs) == pytest.approx(25.0)
    assert manifest.reader("refused_jump_ms")(obs) == pytest.approx(2.0)
    assert manifest.reader("trusting_select_ms")(obs) == pytest.approx(1.0)
    assert manifest.reader("fetch_ms")(obs) == pytest.approx((6.0 + 8.0) / 2)
    assert manifest.reader("store_ms")(obs) == pytest.approx(5.0 / 2)
    calls = [generator.Call(0, 0.0, 0.1, ("accept", None), 800)] * 4
    c = _obs([], calls, {"flushes": 10, "cache_hits": 100, "submitted": 1000},
             {"flushes": 42, "cache_hits": 340, "submitted": 1760})
    assert manifest.reader("flushes_per_call")(c) == 8.0
    assert manifest.reader("cache_hit_rows_pct")(c) == pytest.approx(24.0)
    # a program without the light client's spans (the parent) reads nothing
    old = _obs([_span("verify.submit", 0, 10.0, 1, n=10),
                _span("commit.select", 0, 1.0, 2, mode="light")])
    for name in ("hop_ms", "refused_jump_ms", "trusting_select_ms", "fetch_ms", "store_ms"):
        assert manifest.reader(name)(old) is None
    entries = {m["name"]: m for m in M["per_layer"]}
    for name in ("hop_ms", "refused_jump_ms", "trusting_select_ms", "fetch_ms", "store_ms",
                 "flushes_per_call", "cache_hit_rows_pct"):
        assert entries[name]["workloads"] == ["skip-1000.bisect"]
        assert entries[name]["moves"] == "verify_p50_ms"


def test_the_rule_file_imports_nothing_of_the_program():
    src = open(rules.__file__).read()
    assert "tendermint_tpu" not in src.replace("chipbench/", "")
    assert "import" in src and "from chipbench" not in src


# -- the rehearsal and the planted faults, in a process of its own ----------

SCRIPT = r'''
import json, os, sys
sys.path.insert(0, os.getcwd())
from chipbench import run as runner

b = runner.Bench("skip-1000.bisect", rehearse=True)
b.traffic = {**b.traffic, "pool": {"min_commits": 8, "cache_factor": 0.0}}
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
            "rows": obs.rows(), "outcomes": sorted(c.outcome[0] if c.outcome[0] == "error" else c.outcome[0][0]
                                for c in obs.calls),
            "fetched": sorted({c.outcome[1] for c in obs.calls if c.outcome[0] != "error"}),
            "flushes": obs.after["flushes"] - obs.before["flushes"],
            "hits": obs.after["cache_hits"] - obs.before["cache_hits"],
            "compared": {k: v["value"] for k, v in w["compared"].items()}}


from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.light import client as lc
from tendermint_tpu.light import verifier
from tendermint_tpu.light.errors import ErrNewValSetCantBeTrusted

sound = window(1)
non_adjacent, fetch, enqueue = (verifier.verify_non_adjacent, lc.Client._light_block_from,
                                av.VerifyService._enqueue_device)


def mapping_undone():
    # as before this entry's PR: EVERY failure of the trusting check pivots
    def every_failure_pivots(*a, **k):
        try:
            return non_adjacent(*a, **k)
        except ValueError as e:
            raise ErrNewValSetCantBeTrusted(str(e)) from e
    verifier.verify_non_adjacent = every_failure_pivots


def cache_off():
    os.environ["TM_TPU_VERIFY_CACHE"] = "0"


def trusting_flush_on_the_host():
    def refuse(self, batch, inflight):
        if len(batch) == 67:
            raise RuntimeError("device refused (planted)")
        return enqueue(self, batch, inflight)
    av.VerifyService._enqueue_device = refuse


def pivot_off_by_one():
    def off(self, source, height):
        return fetch(self, source, height if height == 257 else height + 1)
    lc.Client._light_block_from = off


def undo():
    verifier.verify_non_adjacent, lc.Client._light_block_from = non_adjacent, fetch
    av.VerifyService._enqueue_device = enqueue
    os.environ.pop("TM_TPU_VERIFY_CACHE", None)
    av.get_service().stats["device_errors"] = 0


print(json.dumps({"sound": sound,
                  "mapping_undone": window(2, mapping_undone, undo),
                  "cache_off": window(3, cache_off, undo),
                  "host_flush": window(4, trusting_flush_on_the_host, undo),
                  "pivot_off_by_one": window(5, pivot_off_by_one, undo),
                  "sound_again": window(6)}), flush=True)
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
        "device_errors", "cache_hits_off", "flushes_off", "compiles_in_window",
        "route_other"}
    # 8 chains walked once: 8 flushes a call but for the chain whose LAST
    # trusting check fails (7); every call fetched 257, 129, 65, 193
    assert w["calls"] == 8 and w["flushes"] == 8 * 8 - 1 and w["hits"] > 8 * 150
    assert w["route"] == ["device", "pipelined"]
    assert w["fetched"] == [[257, 129, 65, 193]]
    assert w["outcomes"] == ["accept"] * 6 + ["failed"] * 2


def test_fault_the_error_mapping_undone(rehearsed):
    w = rehearsed["mapping_undone"]
    assert not w["ok"] and w["compared"]["calls_wrong"] >= 1
    assert "error" in w["outcomes"]      # it pivoted to a height nobody serves


def test_fault_the_cache_switched_off(rehearsed):
    w = rehearsed["cache_off"]
    assert not w["ok"] and w["hits"] == 0 and w["compared"]["cache_hits_off"] < -8 * 150
    assert w["compared"]["calls_wrong"] == 0 == w["compared"]["flushes_off"]
    assert w["compared"]["rows_off_device"] == w["compared"]["cache_hits_off"]


def test_fault_a_hops_flush_forced_to_the_host(rehearsed):
    w = rehearsed["host_flush"]
    assert not w["ok"] and w["compared"]["host_flushes"] >= 1
    assert w["compared"]["calls_wrong"] == 0     # the host's verdicts are right
    assert w["compared"]["rows_off_device"] == 67 * w["compared"]["host_flushes"]


def test_fault_a_pivot_rule_off_by_one(rehearsed):
    w = rehearsed["pivot_off_by_one"]
    assert not w["ok"] and w["compared"]["calls_wrong"] == 8
