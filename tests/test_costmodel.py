"""Kernel cost model (ISSUE 8): sparse-tolerant cost/memory-analysis
parsing, compiled vs lowered harvests, the pending-program queue, the
roofline derivations, the devmon `costs` snapshot block, and the
warm-path / lazy-cache hooks — all compile-free (stubbed executables and
lowerings; the one real-jax test only BUILDS a jit, never calls it).
"""

import math
from types import SimpleNamespace

import pytest

from tendermint_tpu.utils import costmodel
from tendermint_tpu.utils.costmodel import (
    CostModel,
    CostRecord,
    parse_cost_analysis,
    parse_memory_analysis,
)
from tendermint_tpu.utils.metrics import Histogram


@pytest.fixture(autouse=True)
def fresh_model():
    costmodel.reset(enabled=True)
    yield
    costmodel.reset()


class StubCompiled:
    """A fake jax Compiled: configurable cost/memory analyses, each
    independently able to raise (the XLA-CPU / deserialized-executable
    degradation paths)."""

    def __init__(self, cost=None, mem=None, cost_raises=False,
                 mem_raises=False):
        self._cost = cost
        self._mem = mem
        self._cost_raises = cost_raises
        self._mem_raises = mem_raises

    def cost_analysis(self):
        if self._cost_raises:
            raise NotImplementedError("no cost analysis on this backend")
        return self._cost

    def memory_analysis(self):
        if self._mem_raises:
            raise NotImplementedError("no memory analysis on this backend")
        return self._mem


class StubLowered:
    def __init__(self, cost=None, raises=False):
        self._cost = cost
        self._raises = raises

    def cost_analysis(self):
        if self._raises:
            raise RuntimeError("sparse backend")
        return self._cost


MEM = SimpleNamespace(argument_size_in_bytes=1000, output_size_in_bytes=8,
                      temp_size_in_bytes=500, alias_size_in_bytes=0,
                      generated_code_size_in_bytes=100)


# ---------------------------------------------------------------------------
# parsers
# ---------------------------------------------------------------------------

def test_parse_cost_analysis_dict_and_aliases():
    out = parse_cost_analysis({"flops": 10.0, "bytes accessed": 20.0,
                               "transcendentals": 2.0})
    assert out == {"flops": 10.0, "bytes_accessed": 20.0,
                   "transcendentals": 2.0}
    # underscore alias some backends use
    assert parse_cost_analysis({"bytes_accessed": 5})["bytes_accessed"] == 5.0


def test_parse_cost_analysis_sparse_missing_and_garbage():
    assert parse_cost_analysis({})["flops"] is None
    assert parse_cost_analysis(None)["flops"] is None
    assert parse_cost_analysis("nonsense")["bytes_accessed"] is None
    out = parse_cost_analysis({"flops": "not-a-number",
                               "bytes accessed": float("nan")})
    assert out["flops"] is None and out["bytes_accessed"] is None


def test_parse_memory_analysis_object_dict_and_none():
    out = parse_memory_analysis(MEM)
    # peak = args + outputs + temps + code (alias excluded)
    assert out["peak_memory_bytes"] == 1608
    assert out["temp_bytes"] == 500
    out = parse_memory_analysis({"argument_size_in_bytes": 4,
                                 "temp_size_in_bytes": 6})
    assert out["peak_memory_bytes"] == 10
    assert parse_memory_analysis(None)["peak_memory_bytes"] is None
    # object with none of the known fields → all None
    assert parse_memory_analysis(object())["peak_memory_bytes"] is None


# ---------------------------------------------------------------------------
# harvesting
# ---------------------------------------------------------------------------

def test_record_compiled_full_harvest():
    m = CostModel(enabled=True)
    rec = m.record_compiled("verify", 192, "int64", {"donate": False},
                            StubCompiled(cost={"flops": 4.5e7,
                                               "bytes accessed": 1.6e9},
                                         mem=MEM))
    assert rec.flops == 4.5e7
    assert rec.peak_memory_bytes == 1608
    assert rec.source == "compiled"
    assert rec.error is None
    assert m.lookup("verify", 192, "int64") is rec


def test_record_compiled_never_raises_on_broken_backend():
    m = CostModel(enabled=True)
    rec = m.record_compiled("verify", 64, "int64", {},
                            StubCompiled(cost_raises=True, mem_raises=True))
    assert rec.flops is None and rec.peak_memory_bytes is None
    assert "cost_analysis" in rec.error and "memory_analysis" in rec.error
    # the errored record still exists (the program is known, costs n/a)
    assert m.lookup("verify", 64, "int64") is rec


def test_record_lowered_cost_only_and_no_downgrade():
    m = CostModel(enabled=True)
    m.record_compiled("verify", 8, "int64", {},
                      StubCompiled(cost={"flops": 1.0}, mem=MEM))
    # a later lowered harvest must not clobber the richer compiled one
    m.record_lowered("verify", 8, "int64", {}, StubLowered({"flops": 2.0}))
    rec = m.lookup("verify", 8, "int64")
    assert rec.source == "compiled" and rec.flops == 1.0
    # but compiled over lowered upgrades
    m.record_lowered("rlc", 8, "int64", {}, StubLowered({"flops": 3.0}))
    m.record_compiled("rlc", 8, "int64", {},
                      StubCompiled(cost={"flops": 4.0}, mem=MEM))
    assert m.lookup("rlc", 8, "int64").source == "compiled"
    # and an EMPTY compiled harvest (broken backend) does not block a
    # later lowered harvest that actually has data
    m.record_compiled("verify", 99, "int64", {},
                      StubCompiled(cost_raises=True, mem_raises=True))
    m.record_lowered("verify", 99, "int64", {}, StubLowered({"flops": 5.0}))
    rec = m.lookup("verify", 99, "int64")
    assert rec.source == "lowered" and rec.flops == 5.0


def test_pending_register_resolve_and_error_containment():
    m = CostModel(enabled=True)
    calls = []

    def thunk_ok():
        calls.append("ok")
        return StubLowered({"flops": 7.0, "bytes accessed": 14.0})

    def thunk_boom():
        raise RuntimeError("trace exploded")

    m.record_pending("verify", 64, "int64", {"donate": False}, thunk_ok)
    m.record_pending("verify", 8, "int64", {}, thunk_boom)
    # registration is free: nothing lowered yet
    assert calls == [] and m.pending_count() == 2
    assert m.resolve_pending() == 2
    assert calls == ["ok"]
    assert m.lookup("verify", 64, "int64").flops == 7.0
    boom = m.lookup("verify", 8, "int64")
    assert boom.flops is None and "trace exploded" in boom.error
    # already-recorded keys are not re-registered
    m.record_pending("verify", 64, "int64", {}, thunk_ok)
    assert m.pending_count() == 0


def test_resolve_pending_budget_stops_early():
    m = CostModel(enabled=True)
    for rung in (8, 64, 128):
        m.record_pending("verify", rung, "int64", {},
                         lambda: StubLowered({"flops": 1.0}))
    assert m.resolve_pending(budget_s=0.0) <= 1
    assert m.pending_count() >= 2


def test_samples_skip_unknown_fields():
    m = CostModel(enabled=True)
    m.record_compiled("verify", 8, "int64", {},
                      StubCompiled(cost={"flops": 5.0}))  # no bytes, no mem
    m.record_compiled("rlc", 64, "int64", {},
                      StubCompiled(cost={"flops": 2.0, "bytes accessed": 4.0},
                                   mem=MEM))
    flops = {(l["kind"], l["rung"]): v for l, v in m.flops_samples()}
    assert flops == {("verify", "8"): 5.0, ("rlc", "64"): 2.0}
    assert [l["rung"] for l, _v in m.bytes_samples()] == ["64"]
    assert [l["rung"] for l, _v in m.peak_memory_samples()] == ["64"]


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def test_roofline_derivations_full():
    rec = CostRecord("verify", 192, "int64", {}, "compiled")
    rec.flops = 4.8e7
    rec.bytes_accessed = 1.6e9
    roof = costmodel.roofline(
        rec, exec_by_rung={"192": {"count": 3, "mean_s": 0.012}},
        peak=1.0e12)
    assert roof["arithmetic_intensity"] == pytest.approx(0.03)
    assert roof["flops_per_row"] == pytest.approx(250_000)
    assert roof["hlo_bytes_per_row"] == pytest.approx(1.6e9 / 192)
    assert roof["transfer_bytes_per_row"] == 129  # devmon's measured 129 B/row
    assert roof["transfer_bytes"] == 129 * 192
    assert roof["achieved_flops_per_s"] == pytest.approx(4.8e7 / 0.012)
    assert roof["flops_utilization"] == pytest.approx(4e9 / 1e12)
    assert roof["measured_flushes"] == 3


def test_roofline_degrades_field_by_field():
    rec = CostRecord("verify", 64, "int64", {}, "lowered")
    roof = costmodel.roofline(rec, exec_by_rung={}, peak=None)
    # nothing known → only the static transfer constants survive
    assert "arithmetic_intensity" not in roof
    assert "achieved_flops_per_s" not in roof
    assert roof["transfer_bytes_per_row"] == 129  # 4 x 32 B + the valid bit
    rec.flops = 1.0e6
    roof = costmodel.roofline(rec,
                              exec_by_rung={"64": {"count": 1,
                                                   "mean_s": 0.001}},
                              peak=None)
    assert "achieved_flops_per_s" in roof
    assert "flops_utilization" not in roof  # peak unknown → never guessed


def test_measured_execute_seconds_reads_histogram():
    h = Histogram("x_exec_seconds", "", label_names=("rung",),
                  buckets=(0.01, 0.1))
    h.observe(0.02, rung=192)
    h.observe(0.04, rung=192)
    h.observe(0.5, rung="sync")
    out = costmodel.measured_execute_seconds(hist=h)
    assert out["192"]["count"] == 2
    assert out["192"]["mean_s"] == pytest.approx(0.03)
    assert out["sync"]["mean_s"] == pytest.approx(0.5)


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("TM_TPU_PEAK_FLOPS", "2.5e14")
    assert costmodel.peak_flops_per_s() == 2.5e14
    monkeypatch.setenv("TM_TPU_PEAK_FLOPS", "garbage")
    # malformed → falls through to the device table (cpu: unknown)
    assert costmodel.peak_flops_per_s() != "garbage"


# ---------------------------------------------------------------------------
# snapshot blocks + gates
# ---------------------------------------------------------------------------

def test_costs_block_and_devmon_snapshot(monkeypatch):
    monkeypatch.delenv("TM_TPU_PEAK_FLOPS", raising=False)
    costmodel.COSTS.record_compiled(
        "verify", 8, "int64", {},
        StubCompiled(cost={"flops": 3.0, "bytes accessed": 6.0}, mem=MEM))
    block = costmodel.costs_block()
    assert block["enabled"] is True
    assert block["pending"] == 0
    (rec,) = block["records"]
    assert rec["kind"] == "verify" and rec["flops"] == 3.0
    assert rec["arithmetic_intensity"] == pytest.approx(0.5)
    assert rec["peak_memory_bytes"] == 1608

    from tendermint_tpu.utils import devmon

    snap = devmon.device_stats()
    assert snap["costs"]["records"][0]["rung"] == 8
    # the pprof text dump renders the block without blowing up
    text = devmon.render_text()
    assert "program costs" in text and "flops=3" in text


def test_disabled_model_is_inert():
    m = CostModel(enabled=False)
    assert m.enabled is False
    # callers gate on .enabled; even direct calls stay consistent
    m.record_pending("verify", 8, "int64", {}, lambda: StubLowered({}))
    assert m.pending_count() == 1  # registration is allowed; harvest isn't hot
    costmodel.reset(enabled=False)
    assert costmodel.costs_block()["enabled"] is False


def test_env_gate_resolved_at_construction(monkeypatch):
    monkeypatch.setenv("TM_TPU_COSTMODEL", "0")
    assert CostModel().enabled is False
    monkeypatch.setenv("TM_TPU_COSTMODEL", "1")
    assert CostModel().enabled is True


# ---------------------------------------------------------------------------
# hooks (stubbed warm path; jit BUILD only for the lazy cache)
# ---------------------------------------------------------------------------

def test_warm_entry_harvests_compiled_costs(monkeypatch, tmp_path):
    from tendermint_tpu.ops import shape_plan

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    stub = StubCompiled(cost={"flops": 9.0, "bytes accessed": 18.0}, mem=MEM)
    monkeypatch.setattr(shape_plan, "_aot_compile",
                        lambda kind, rung, impl, flags: (stub, 0.01))
    monkeypatch.setattr(shape_plan, "_dump_executable", lambda exe: None)
    shape_plan.clear_registry()
    try:
        rep = shape_plan.warm_entry("verify", 8, "int64",
                                    flags={"donate": False},
                                    serialize=False)
        assert rep["source"] == "aot"
        rec = costmodel.COSTS.lookup("verify", 8, "int64")
        assert rec is not None and rec.source == "compiled"
        assert rec.flops == 9.0 and rec.peak_memory_bytes == 1608
    finally:
        shape_plan.clear_registry()


def test_lazy_compiled_registers_pending():
    """_compiled() (the lazy jit cache) registers a pending harvest for
    its (kind, rung, impl) — building the jit only, never calling it.
    Uses a rung no other suite touches instead of cache_clear(): the
    lazy cache is process-global, and clearing it would force later
    suites to re-trace their programs (seconds each)."""
    from tendermint_tpu.ops import ed25519_jax as dev

    rung = 31416  # not a plan rung; never flushed by any test
    dev._compiled(rung, "int64")
    assert costmodel.COSTS.pending_count() == 1
    assert costmodel.COSTS.lookup("verify", rung, "int64") is None
    # same functools.cache entry → no second registration attempt, and
    # a direct re-register of a pending key is a no-op dedupe anyway
    dev._compiled(rung, "int64")
    costmodel.COSTS.record_pending("verify", rung, "int64", {},
                                   lambda: StubLowered({}))
    assert costmodel.COSTS.pending_count() == 1


def test_record_to_dict_roundtrip_is_json_safe():
    import json

    rec = CostRecord("verify", 8, "int64", {"donate": True}, "lowered")
    rec.flops = 1.5
    rec.error = "cost_analysis: nope"
    doc = json.loads(json.dumps(rec.to_dict()))
    assert doc["flags"] == {"donate": True}
    assert doc["error"].startswith("cost_analysis")
    assert "bytes_accessed" not in doc  # unknown fields are absent, not null


def test_roofline_infinite_and_zero_guards():
    rec = CostRecord("verify", 0, "int64", {}, "lowered")
    rec.flops = 1.0
    rec.bytes_accessed = 0.0
    # rung 0 / bytes 0 must not divide by zero
    roof = costmodel.roofline(rec, exec_by_rung={}, peak=None)
    assert "arithmetic_intensity" not in roof
    assert "flops_per_row" not in roof
    assert math.isfinite(roof.get("transfer_bytes", 0))


def test_peak_table_is_keyed_by_exact_device_kind(monkeypatch):
    """The chip's own device_kind string ("TPU v5 lite" — no "v5e" in
    it) keys the table exactly; any other device is unknown (None),
    never a substring match and never a default."""
    from tendermint_tpu.utils import devmon

    monkeypatch.delenv("TM_TPU_PEAK_FLOPS", raising=False)

    def on(kind):
        monkeypatch.setattr(devmon, "device_memory", lambda: [
            {"id": 0, "platform": "tpu", "device_kind": kind}])
        return costmodel.peak_flops_per_s()

    assert on("TPU v5 lite") == 197e12
    assert on("TPU v5e") is None          # not what the chip calls itself
    assert on("TPU v5 lite pod") is None  # exact, not substring
    assert on("cpu") is None
    monkeypatch.setattr(devmon, "device_memory", lambda: [])
    assert costmodel.peak_flops_per_s() is None  # no backend yet
