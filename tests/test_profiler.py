"""Continuous profiler (utils/profiler.py) + `tendermint-tpu prof`:
folding/attribution units on a deterministic clock, the NOP/env gate,
trigger rate-limiting, the diff classifier matrix, CLI exit codes, and
one live node serving `/debug/pprof/profile` under load."""

from __future__ import annotations

import asyncio
import json
import threading
import urllib.request

import pytest

from tendermint_tpu.utils import profiler as pf


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


class _FakeCode:
    def __init__(self, filename, name="fn"):
        self.co_filename = filename
        self.co_name = name


class _FakeFrame:
    def __init__(self, filename, name="fn"):
        self.f_code = _FakeCode(filename, name)
        self.f_back = None


def test_classify_thread_name_wins():
    fr = [_FakeFrame("/x/tendermint_tpu/consensus/state.py")]
    assert pf.classify("tm-verify-service-3", fr) == "verify-service"
    assert pf.classify("tm-threshold-measure", fr) == "verify-service"
    assert pf.classify("tm-gateway-coalescer", fr) == "gateway"
    assert pf.classify("tm-aot-warm", fr) == "device"
    assert pf.classify("health-node0", fr) == "health"
    assert pf.classify("prof-node0", fr) == "prof"


def test_classify_frame_fallback_innermost_first():
    inner = _FakeFrame("/x/tendermint_tpu/rpc/server.py")
    outer = _FakeFrame("/x/tendermint_tpu/consensus/state.py")
    assert pf.classify("MainThread", [inner, outer]) == "rpc"
    assert pf.classify("MainThread", [outer, inner]) == "consensus"
    assert pf.classify("MainThread",
                       [_FakeFrame("/x/tendermint_tpu/crypto/batch.py")]
                       ) == "verify-service"
    assert pf.classify("MainThread", [_FakeFrame("/usr/lib/random.py")]
                       ) == "other"
    assert pf.classify("MainThread", []) == "other"


def test_frame_labels_are_package_relative():
    assert pf._file_label("/opt/x/tendermint_tpu/mempool/clist.py") \
        == "tendermint_tpu/mempool/clist.py"
    assert pf._file_label("/usr/lib/python3.10/selectors.py") \
        == "selectors.py"


# ---------------------------------------------------------------------------
# folding round-trip / bounds
# ---------------------------------------------------------------------------


def test_folded_roundtrip_skips_header():
    stacks = {"rpc;MainThread;a.py:f;b.py:g": 7,
              "health;health-x;h.py:tick": 2}
    text = pf.render_folded(stacks, header="tendermint-tpu profile "
                                           "enabled=1 hz=19")
    assert text.startswith("# tendermint-tpu profile")
    assert pf.parse_folded(text) == stacks
    # idempotent through a second render
    assert pf.parse_folded(pf.render_folded(pf.parse_folded(text))) == stacks


def test_bounded_add_overflow_collapses_but_keeps_totals():
    stacks: dict = {}
    for i in range(40):
        pf._bounded_add(stacks, f"rpc;t;f{i}", 1, 16)
    assert len(stacks) == 17            # 16 distinct + the overflow bucket
    assert stacks["rpc;(overflow);(other)"] == 24
    assert sum(stacks.values()) == 40


def test_function_table_self_vs_cum_and_recursion():
    stacks = {"rpc;MainThread;a.py:f;b.py:g": 3,
              "rpc;MainThread;a.py:f;a.py:f;b.py:g": 2,   # recursion
              "rpc;MainThread;a.py:f": 5}
    blk = pf.function_table(stacks)["rpc"]
    assert blk["samples"] == 10
    # recursion counted once per stack for cum; leaf-only for self
    assert blk["functions"]["a.py:f"] == {"self": 5, "cum": 10}
    assert blk["functions"]["b.py:g"] == {"self": 5, "cum": 5}


# ---------------------------------------------------------------------------
# sampler on a deterministic clock
# ---------------------------------------------------------------------------


def _busy_thread(name: str):
    evt = threading.Event()
    t = threading.Thread(target=evt.wait, name=name, daemon=True)
    t.start()
    return evt, t


def test_sampler_windows_roll_on_injected_clock():
    box = {"t": 0.0}
    p = pf.Profiler(node="n0", window_s=10.0, ring=2,
                    clock=lambda: box["t"])
    evt, _ = _busy_thread("tm-verify-service-0")
    try:
        for _ in range(3):
            p.sample()                   # window [0, 10)
        box["t"] = 10.0
        p.sample()                       # rolls -> window 2
        box["t"] = 20.0
        p.sample()                       # rolls -> window 3
        box["t"] = 30.0
        p.sample()                       # rolls -> 4th; ring keeps 2
    finally:
        evt.set()
    st = p.status_block()
    assert st["sweeps"] == 6 and st["windows"] == 3   # ring(2) + open
    assert st["by_subsystem"].get("verify-service", 0) >= 6
    assert st["overhead_s"] > 0.0
    # folded_recent only spans the ring + open window (4 sweeps), the
    # cumulative fold spans all 6
    recent = pf.parse_folded(p.folded_recent())
    assert sum(recent.values()) < sum(p.cumulative_stacks().values())
    meta_line = p.folded_recent().splitlines()[0]
    assert "enabled=1" in meta_line and "node=n0" in meta_line


def test_sampler_excludes_calling_thread():
    p = pf.Profiler(node="n0")
    me = threading.current_thread().name
    for sub, name, key in p.sample():
        assert name != me, key


def test_metrics_rows_and_typed_empty_shape():
    p = pf.Profiler(node="n0")
    assert p.overhead_samples() == []            # no sweeps yet
    evt, _ = _busy_thread("health-n0")
    try:
        p.sample()
    finally:
        evt.set()
    rows = dict()
    for labels, value in p.subsystem_samples():
        rows[labels["subsystem"]] = value
    assert rows.get("health", 0) >= 1
    ov = p.overhead_samples()
    assert len(ov) == 1 and ov[0][0] == {} and ov[0][1] > 0.0
    # NOP: typed-empty (no rows), stable contract
    assert pf.NOP.subsystem_samples() == []
    assert pf.NOP.overhead_samples() == []


def test_capture_returns_delta_and_feeds_cumulative():
    p = pf.Profiler(node="n0", hz=200.0)
    evt, _ = _busy_thread("tm-verify-service-0")
    try:
        cap = p.capture(seconds=0.05)
    finally:
        evt.set()
    assert cap["enabled"] and cap["node"] == "n0"
    assert cap["sweeps"] >= 1
    assert cap["samples"] == sum(cap["by_subsystem"].values())
    assert cap["by_subsystem"].get("verify-service", 0) >= 1
    assert p.samples >= cap["samples"]           # capture samples are real
    doc = json.loads(pf.export_chrome(cap))
    assert doc["traceEvents"], "chrome export must carry events"
    ev = doc["traceEvents"][0]
    assert ev["ph"] == "X" and ev["cat"] in cap["by_subsystem"]


def test_report_names_top_subsystem_and_function():
    p = pf.Profiler(node="n0")
    evt, _ = _busy_thread("tm-verify-service-0")
    try:
        p.sample()
    finally:
        evt.set()
    rep = p.report()
    assert rep["top_subsystem"] == "verify-service"
    assert rep["top"] and rep["top"][0]["self"] >= 1


# ---------------------------------------------------------------------------
# trigger rate-limit
# ---------------------------------------------------------------------------


def test_trigger_rate_limited_on_injected_clock():
    box = {"t": 0.0}
    p = pf.Profiler(node="n0", trigger_min_s=30.0, clock=lambda: box["t"])
    assert p.trigger("health-critical:height_stall") is True
    box["t"] = 10.0
    assert p.trigger("slo_burn") is False        # inside the limit
    assert p.trigger("slo_burn") is False
    box["t"] = 31.0
    assert p.trigger("slo_burn") is True
    assert p.triggers == 2 and p.trigger_suppressed == 2
    assert p.report()["last_trigger"] == "slo_burn"


# ---------------------------------------------------------------------------
# NOP + env gate
# ---------------------------------------------------------------------------


def test_nop_contract():
    nop = pf.NOP
    assert nop.enabled is False
    assert nop.sample() == []
    assert nop.trigger("x") is False
    assert nop.capture(1.0)["enabled"] is False
    assert nop.status_block() == {"enabled": False}
    assert nop.report() == {"enabled": False}
    assert "enabled=0" in nop.folded_recent()
    nop.start()
    nop.stop()


def test_from_env_gate_and_knobs(monkeypatch):
    monkeypatch.setenv("TM_TPU_PROF", "0")
    assert pf.from_env(node="x") is pf.NOP
    monkeypatch.setenv("TM_TPU_PROF", "off")
    assert pf.from_env(node="x") is pf.NOP

    monkeypatch.setenv("TM_TPU_PROF", "1")
    monkeypatch.setenv("TM_TPU_PROF_HZ", "97")
    monkeypatch.setenv("TM_TPU_PROF_TRIGGER_MIN_S", "5")
    p = pf.from_env(node="x")
    assert p.enabled and p.hz == 97.0 and p.trigger_min_s == 5.0

    # malformed knob falls back to the default instead of crashing
    monkeypatch.setenv("TM_TPU_PROF_HZ", "fast")
    p = pf.from_env(node="x")
    assert p.hz == pf.DEFAULT_HZ


# ---------------------------------------------------------------------------
# diff classifier matrix
# ---------------------------------------------------------------------------


def _prof(**shares):
    """Folded stacks with one leaf per function and the given counts."""
    return {f"other;t;{func}": n for func, n in shares.items()}


def test_diff_matrix_regression_improvement_ok():
    base = _prof(**{"a.py:hot": 10, "b.py:warm": 10, "c.py:cold": 80})
    new = _prof(**{"a.py:hot": 40, "b.py:warm": 9, "c.py:cold": 51})
    res = pf.diff_folded(base, new)
    by = {r["func"]: r["verdict"] for r in res["rows"]}
    assert by["a.py:hot"] == "regression"        # 10% -> 40%
    assert by["c.py:cold"] == "improvement"      # 80% -> 51%
    assert by["b.py:warm"] == "ok"               # 10% -> 9%: both gates quiet
    assert res["regressions"] == ["a.py:hot"] and not res["ok"]


def test_diff_both_gates_required():
    # +6 points absolute but only +15% relative: quiet (big function
    # drifting), and +60% relative but +3 points absolute: quiet (blip)
    base = _prof(**{"a.py:big": 40, "b.py:small": 5, "c.py:rest": 55})
    new = _prof(**{"a.py:big": 46, "b.py:small": 8, "c.py:rest": 46})
    assert pf.diff_folded(base, new)["ok"]


def test_diff_new_function_from_zero_regresses_on_abs_alone():
    base = _prof(**{"a.py:f": 100})
    new = _prof(**{"a.py:f": 80, "b.py:born": 20})
    res = pf.diff_folded(base, new)
    assert "b.py:born" in res["regressions"]


def test_diff_self_is_clean():
    base = _prof(**{"a.py:f": 30, "b.py:g": 70})
    res = pf.diff_folded(base, base)
    assert res["ok"] and all(r["verdict"] == "ok" for r in res["rows"])


# ---------------------------------------------------------------------------
# CLI: prof / prof --diff exit codes
# ---------------------------------------------------------------------------


def _write_folded(path, stacks):
    path.write_text(pf.render_folded(
        stacks, header="tendermint-tpu profile enabled=1 hz=19"))


def test_cli_diff_exit_codes(tmp_path, capsys):
    from tendermint_tpu.cli.main import main

    base, new = tmp_path / "base.folded", tmp_path / "new.folded"
    _write_folded(base, _prof(**{"a.py:hot": 10, "c.py:cold": 90}))
    _write_folded(new, _prof(**{"a.py:hot": 45, "c.py:cold": 55}))
    assert main(["prof", "--diff", str(base), str(new)]) == 1
    assert "REGRESSED" in capsys.readouterr().out

    assert main(["prof", "--diff", str(base), str(base)]) == 0
    assert "no function regressed" in capsys.readouterr().out

    assert main(["prof", "--diff", str(base), str(tmp_path / "nope")]) == 2
    empty = tmp_path / "empty.folded"
    empty.write_text("# tendermint-tpu profile enabled=1\n")
    assert main(["prof", "--diff", str(base), str(empty)]) == 2

    doc_rc = main(["prof", "--diff", str(base), str(new), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert doc_rc == 1 and doc["regressions"] == ["a.py:hot"]


def test_cli_unreachable_exits_3(capsys):
    from tendermint_tpu.cli.main import main

    rc = main(["prof", "--pprof-laddr", "http://127.0.0.1:9", "--once",
               "--timeout", "0.5"])
    assert rc == 3
    assert "unreachable" in capsys.readouterr().out


def test_cli_render_once_and_header_meta():
    from tendermint_tpu.cli.prof import header_meta, render_once

    text = pf.render_folded(
        {"rpc;MainThread;a.py:f;b.py:g": 7},
        header="tendermint-tpu profile node=n0 enabled=1 hz=19")
    meta = header_meta(text)
    assert meta["node"] == "n0" and meta["enabled"] == "1"
    out = render_once(text)
    assert "n0" in out and "rpc" in out and "b.py:g" in out


def test_top_folds_and_renders_prof_line():
    from tendermint_tpu.cli import top
    from tendermint_tpu.utils import promparse

    snap = promparse.empty_snapshot()
    snap["ts"] = 0.0
    top.fold_status(snap, {
        "node_info": {"moniker": "n0"},
        "sync_info": {"latest_block_height": 3},
        "prof": {"enabled": True, "hz": 19.0, "samples": 100,
                 "by_subsystem": {"consensus": 60, "other": 40},
                 "overhead_s": 0.012345, "triggers": 1},
    })
    assert snap["prof"]["samples"] == 100
    text = top.render(snap)
    line = next(ln for ln in text.splitlines() if ln.startswith("prof"))
    assert "samples 100" in line and "hz 19" in line
    assert "consensus:60" in line.replace(".0%", "%")


# ---------------------------------------------------------------------------
# verdict profile block (simnet)
# ---------------------------------------------------------------------------


def test_verdict_profile_block_names_hotspots():
    from tendermint_tpu.simnet.verdict import _profile_block

    run_info = {"profile": {
        "node0": {"enabled": True, "samples": 50,
                  "top_subsystem": "consensus",
                  "by_subsystem": {"consensus": 40, "other": 10},
                  "overhead_s": 0.01, "triggers": 0,
                  "top": [{"func": "a.py:f", "subsystem": "consensus",
                           "self": 30, "cum": 40}]},
        "node1": {"enabled": False},
    }}
    blk = _profile_block(run_info)
    assert blk["per_node"]["node0"]["top_subsystem"] == "consensus"
    assert blk["per_node"]["node0"]["top_function"] == "a.py:f"
    assert blk["per_node"]["node1"] == {"enabled": False}
    assert blk["hottest_function"]["node"] == "node0"
    assert _profile_block({}) == {"per_node": {}, "hottest_function": None}


# ---------------------------------------------------------------------------
# live node: /debug/pprof/profile, metrics, status, CLI
# ---------------------------------------------------------------------------


def test_live_node_prof_surfaces(tmp_path, monkeypatch):
    from tendermint_tpu.cli.prof import run_prof
    from tendermint_tpu.config import test_config as make_test_config
    from tendermint_tpu.crypto.batch import set_default_backend
    from tendermint_tpu.crypto.keys import priv_key_from_seed
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    set_default_backend("cpu")
    monkeypatch.delenv("TM_TPU_PROF", raising=False)
    monkeypatch.setenv("TM_TPU_PROF_HZ", "50")   # dense sweeps, short test

    async def run():
        key = priv_key_from_seed(b"\x79" * 32)
        gen = GenesisDoc(
            chain_id="prof-chain",
            genesis_time_ns=1_700_000_000 * 10**9,
            validators=[GenesisValidator(pub_key=key.pub_key(), power=10)],
        )
        cfg = make_test_config(str(tmp_path))
        cfg.base.fast_sync = False
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "tcp://127.0.0.1:0"
        cfg.rpc.pprof_laddr = "tcp://127.0.0.1:0"
        node = Node(cfg, genesis=gen)
        node.priv_validator.priv_key = key
        node.consensus.priv_validator = node.priv_validator
        await node.start()
        try:
            assert node.prof.enabled and node.prof.hz == 50.0
            assert node.health.prof is node.prof
            await node.wait_for_height(2, timeout=30)
            mh, mp = node.metrics.addr
            rpc = f"http://{node.rpc_addr[0]}:{node.rpc_addr[1]}"
            ph, pp = node.pprof_addr
            pprof = f"http://{ph}:{pp}"

            def get(url):
                with urllib.request.urlopen(url, timeout=10) as r:
                    return r.read().decode()

            # -- a fresh 2s capture under consensus load: >0 samples in
            # >= 2 subsystem buckets (the acceptance bar)
            text = await asyncio.to_thread(
                get, f"{pprof}/debug/pprof/profile?seconds=2")
            stacks = pf.parse_folded(text)
            assert sum(stacks.values()) > 0
            buckets = {k.split(";", 1)[0] for k in stacks}
            assert len(buckets) >= 2, buckets

            # -- the continuous ring (no capture) also serves
            text = await asyncio.to_thread(
                get, f"{pprof}/debug/pprof/profile")
            assert "enabled=1" in text

            # -- chrome export parses and carries events
            doc = json.loads(await asyncio.to_thread(
                get, f"{pprof}/debug/pprof/profile?seconds=1&fmt=chrome"))
            assert doc["traceEvents"]

            # -- pprof index advertises the route
            idx = await asyncio.to_thread(get, f"{pprof}/debug/pprof")
            assert "/debug/pprof/profile" in idx

            # -- metrics: both families typed, samples flowing
            mtext = await asyncio.to_thread(get, f"http://{mh}:{mp}/metrics")
            assert "# TYPE tendermint_prof_samples_total counter" in mtext
            assert ("# TYPE tendermint_prof_overhead_seconds_total counter"
                    in mtext)
            assert 'tendermint_prof_samples_total{subsystem="' in mtext

            # -- RPC status prof block
            st = json.loads(await asyncio.to_thread(get, f"{rpc}/status"))
            blk = st["result"]["prof"]
            assert blk["enabled"] and blk["running"]
            assert blk["samples"] > 0 and blk["by_subsystem"]

            # -- CLI against the live node: read ok (0), flame output
            rc = await asyncio.to_thread(
                lambda: run_prof(pprof, as_json=True))
            assert rc == 0
            flame = str(tmp_path / "live.folded")
            rc = await asyncio.to_thread(
                lambda: run_prof(pprof, flame=flame))
            assert rc == 0
            assert pf.parse_folded(open(flame).read())
        finally:
            await node.stop()
        assert node.prof.status_block()["running"] is False

    asyncio.run(run())


def test_live_node_prof_disabled_is_nop(tmp_path, monkeypatch):
    """TM_TPU_PROF=0: the node carries the NOP singleton, the route
    answers `enabled=0`, and the metric families are typed-empty."""
    from tendermint_tpu.config import test_config as make_test_config
    from tendermint_tpu.crypto.batch import set_default_backend
    from tendermint_tpu.crypto.keys import priv_key_from_seed
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    set_default_backend("cpu")
    monkeypatch.setenv("TM_TPU_PROF", "0")

    async def run():
        key = priv_key_from_seed(b"\x7a" * 32)
        gen = GenesisDoc(
            chain_id="prof-off-chain",
            genesis_time_ns=1_700_000_000 * 10**9,
            validators=[GenesisValidator(pub_key=key.pub_key(), power=10)],
        )
        cfg = make_test_config(str(tmp_path))
        cfg.base.fast_sync = False
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "tcp://127.0.0.1:0"
        cfg.rpc.pprof_laddr = "tcp://127.0.0.1:0"
        node = Node(cfg, genesis=gen)
        node.priv_validator.priv_key = key
        node.consensus.priv_validator = node.priv_validator
        await node.start()
        try:
            assert node.prof is pf.NOP
            await node.wait_for_height(1, timeout=30)
            mh, mp = node.metrics.addr
            rpc = f"http://{node.rpc_addr[0]}:{node.rpc_addr[1]}"
            ph, pp = node.pprof_addr

            def get(url):
                with urllib.request.urlopen(url, timeout=10) as r:
                    return r.read().decode()

            body = await asyncio.to_thread(
                get, f"http://{ph}:{pp}/debug/pprof/profile")
            assert "enabled=0" in body
            mtext = await asyncio.to_thread(get, f"http://{mh}:{mp}/metrics")
            assert "# TYPE tendermint_prof_samples_total counter" in mtext
            assert "tendermint_prof_samples_total{" not in mtext
            st = json.loads(await asyncio.to_thread(get, f"{rpc}/status"))
            assert "prof" not in st["result"]
        finally:
            await node.stop()

    asyncio.run(run())
