"""`tendermint-tpu profile` CLI contract (ISSUE 8), compile-free: the
harvest and timed-window internals are stubbed so the tests exercise
selection flags, the --json schema, budget degradation, error
containment and exit codes without ever lowering or executing a real
program (a fresh trace costs ~10 s and a compile ~100 s on this image).
"""

import json

import pytest

from tendermint_tpu.cli import profile as profile_mod
from tendermint_tpu.cli.main import main as cli_main
from tendermint_tpu.utils import costmodel


@pytest.fixture(autouse=True)
def fresh_model():
    costmodel.reset(enabled=True)
    yield
    costmodel.reset()


@pytest.fixture
def stubbed(monkeypatch):
    """Stub the two expensive internals; record what was called."""
    calls = {"harvest": [], "timed": []}

    def fake_harvest(kind, rung, impl):
        calls["harvest"].append((kind, rung, impl))
        return {"kind": kind, "rung": rung, "impl": impl,
                "flops": 1000.0 * rung, "bytes_accessed": 4000.0 * rung,
                "source": "lowered"}

    def fake_timed(kind, rung, impl, *, runs, deadline):
        calls["timed"].append((kind, rung, impl, runs))
        return {"warm_s": 0.01, "runs": runs, "wall_p50_ms": 2.0,
                "sigs_per_sec": rung / 0.002}

    monkeypatch.setattr(profile_mod, "harvest_entry", fake_harvest)
    monkeypatch.setattr(profile_mod, "timed_window", fake_timed)
    monkeypatch.setattr(profile_mod, "backend_info",
                        lambda: {"backend": "stub", "devices": 1})
    return calls


def _run_json(capsys, *argv):
    rc = cli_main(["profile", "--json", *argv])
    out = capsys.readouterr().out
    return rc, json.loads(out)


def test_profile_json_contract_every_rung_reports_costs(stubbed, capsys):
    rc, rep = _run_json(capsys, "--rungs", "8,64,192")
    assert rc == 0
    assert rep["backend"] == "stub"
    assert [e["rung"] for e in rep["entries"]] == [8, 64, 192]
    for e in rep["entries"]:
        # the acceptance bar: FLOPs and bytes for every rung, plus the
        # derived roofline columns and the timed window
        assert e["flops"] == 1000.0 * e["rung"]
        assert e["bytes_accessed"] == 4000.0 * e["rung"]
        assert e["wall_p50_ms"] == 2.0
        assert e["sigs_per_sec"] == pytest.approx(e["rung"] / 0.002)
        # flops/wall directly → achieved FLOPs/s even with no histogram
        assert e["achieved_flops_per_s"] == pytest.approx(
            e["flops"] / 0.002)
    assert stubbed["timed"] and stubbed["harvest"]


def test_profile_defaults_to_active_plan(stubbed, capsys, monkeypatch):
    from tendermint_tpu.ops import shape_plan

    monkeypatch.setenv("TM_TPU_RUNGS", "8,64")
    shape_plan.reload_plan()
    try:
        rc, rep = _run_json(capsys)
        assert rc == 0
        assert rep["plan"]["name"] == "env-rungs"
        assert [e["rung"] for e in rep["entries"]] == [8, 64]
    finally:
        monkeypatch.delenv("TM_TPU_RUNGS")
        shape_plan.reload_plan()


def test_profile_selection_mirrors_warm_flags(stubbed, capsys):
    rc, rep = _run_json(capsys, "--rungs", "8,64", "--kinds", "verify,rlc",
                        "--impls", "int64")
    assert rc == 0
    assert [(e["kind"], e["rung"]) for e in rep["entries"]] == [
        ("verify", 8), ("verify", 64), ("rlc", 8), ("rlc", 64)]


def test_profile_cost_only_skips_execution(stubbed, capsys):
    rc, rep = _run_json(capsys, "--rungs", "8", "--cost-only")
    assert rc == 0
    assert rep["cost_only"] is True
    assert stubbed["timed"] == []
    assert "wall_p50_ms" not in rep["entries"][0]
    # --budget 0 is the same degradation
    rc, rep = _run_json(capsys, "--rungs", "8", "--budget", "0")
    assert rep["cost_only"] is True and stubbed["timed"] == []


def test_profile_budget_exhaustion_keeps_cost_rows(stubbed, capsys,
                                                   monkeypatch):
    ticks = iter([0.0, 0.0])  # deadline anchor + first rung's check pass
    monkeypatch.setattr(profile_mod, "_now",
                        lambda: next(ticks, 1000.0))
    rc, rep = _run_json(capsys, "--rungs", "8,64", "--budget", "5")
    assert rc == 0
    skipped = [e for e in rep["entries"] if e.get("timed") == "skipped: budget"]
    assert skipped, "budget exhaustion must mark skipped timed windows"
    for e in rep["entries"]:
        assert e["flops"] is not None  # cost rows survive the budget


def test_profile_harvest_error_contained_and_exit_1(stubbed, capsys,
                                                    monkeypatch):
    def boom(kind, rung, impl):
        if rung == 64:
            raise RuntimeError("lowering failed")
        return {"kind": kind, "rung": rung, "impl": impl, "flops": 1.0,
                "source": "lowered"}

    monkeypatch.setattr(profile_mod, "harvest_entry", boom)
    rc, rep = _run_json(capsys, "--rungs", "8,64")
    assert rc == 1
    errs = [e for e in rep["entries"] if e.get("error")]
    assert len(errs) == 1 and errs[0]["rung"] == 64
    assert "lowering failed" in errs[0]["error"]
    # the other rung still reported
    assert rep["entries"][0]["flops"] == 1.0


def test_profile_timed_error_does_not_fail_the_sweep(stubbed, capsys,
                                                     monkeypatch):
    def boom(kind, rung, impl, *, runs, deadline):
        raise RuntimeError("device wedged")

    monkeypatch.setattr(profile_mod, "timed_window", boom)
    rc, rep = _run_json(capsys, "--rungs", "8")
    assert rc == 0  # cost row landed; only execution degraded
    assert "device wedged" in rep["entries"][0]["timed_error"]


def test_profile_text_table_renders_na(stubbed, capsys):
    rc = cli_main(["profile", "--rungs", "8", "--cost-only"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "verify" in out and "n/a" in out  # no timed columns → n/a


def test_profile_usage_error_on_malformed_rungs(capsys):
    assert cli_main(["profile", "--rungs", "8,banana"]) == 2
    capsys.readouterr()


def test_profile_impl_comparison_json(stubbed, capsys, monkeypatch):
    """--impls with 2+ backends produces the side-by-side block (ISSUE
    12 satellite): per (kind, rung) one cell per impl, ratio columns vs
    the first impl in selection order."""
    def fake_harvest(kind, rung, impl):
        scale = {"int64": 1.0, "packed": 0.5}[impl]
        return {"kind": kind, "rung": rung, "impl": impl,
                "flops": 1000.0 * rung * scale,
                "bytes_accessed": 4000.0 * rung * scale,
                "source": "lowered"}

    def fake_timed(kind, rung, impl, *, runs, deadline):
        wall = 0.002 if impl == "int64" else 0.001
        return {"warm_s": 0.01, "runs": runs, "wall_p50_ms": wall * 1e3,
                "sigs_per_sec": rung / wall}

    monkeypatch.setattr(profile_mod, "harvest_entry", fake_harvest)
    monkeypatch.setattr(profile_mod, "timed_window", fake_timed)
    rc, rep = _run_json(capsys, "--rungs", "8,64",
                        "--impls", "int64,packed")
    assert rc == 0
    comp = rep["impl_comparison"]
    assert [c["rung"] for c in comp] == [8, 64]
    for c in comp:
        assert c["baseline"] == "int64"
        cell = c["impls"]["packed"]
        assert cell["flops_ratio"] == pytest.approx(0.5)
        assert cell["speedup"] == pytest.approx(2.0)
        assert "flops_ratio" not in c["impls"]["int64"]  # baseline: none
    # a single impl produces no comparison block
    rc, rep = _run_json(capsys, "--rungs", "8", "--impls", "int64")
    assert rep["impl_comparison"] == []


def test_profile_impl_comparison_text_table(stubbed, capsys, monkeypatch):
    monkeypatch.setattr(
        profile_mod, "timed_window",
        lambda kind, rung, impl, *, runs, deadline: {
            "warm_s": 0.0, "runs": runs, "wall_p50_ms": 1.0,
            "sigs_per_sec": rung / 0.001})
    rc = cli_main(["profile", "--rungs", "8", "--impls", "int64,packed"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "impl comparison (baseline int64):" in out
    assert "packed" in out and "1.00x" in out


def test_render_impl_comparison_unit():
    comp = profile_mod.impl_comparison([
        {"kind": "verify", "rung": 8, "impl": "int64",
         "hlo_bytes_per_row": 1200.0, "flops": 100.0,
         "sigs_per_sec": 10.0, "wall_p50_ms": 1.0},
        {"kind": "verify", "rung": 8, "impl": "packed",
         "hlo_bytes_per_row": 800.0, "flops": 50.0,
         "sigs_per_sec": 20.0, "wall_p50_ms": 0.5},
    ])
    assert len(comp) == 1
    cell = comp[0]["impls"]["packed"]
    assert cell["bytes_ratio"] == pytest.approx(800.0 / 1200.0, abs=1e-3)
    assert cell["speedup"] == pytest.approx(2.0)
    lines = profile_mod.render_impl_comparison(comp)
    assert lines[0].startswith("impl comparison")
    assert any("packed" in ln and "0.67x" in ln for ln in lines)
    # errored rows are excluded; single-impl groups render nothing
    assert profile_mod.impl_comparison(
        [{"kind": "verify", "rung": 8, "impl": "int64"}]) == []


def test_synth_rows_match_abstract_shapes():
    from tendermint_tpu.ops import shape_plan

    rows = profile_mod._synth_rows("verify", 8)
    specs = shape_plan.abstract_rows("verify", 8)
    assert [tuple(r.shape) for r in rows] == [tuple(s.shape) for s in specs]
    assert [str(r.dtype) for r in rows] == [str(s.dtype) for s in specs]
    assert rows[-1].all()  # every valid bit set → full per-row work
