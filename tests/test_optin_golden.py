"""Golden-batch self-check of a field backend on the device it runs on.

A program that compiles is not a program that is right (the f32 backend
the kernel once had computed wrong verdicts on the TPU, CHANGES PR 21):
before "auto" takes the packed backend on an accelerator its floor-rung
program runs once against a known mixed-validity batch and is refused —
loudly, falling back to int64 — when a verdict mismatches or the program
raises.  These tests pin the check's three outcomes (compile-free, with
`_compiled` stubbed), the golden batch on both real programs, and the
resolution of TM_TPU_FIELD_IMPL=auto.
"""

import warnings

import numpy as np
import pytest

from tendermint_tpu.ops import ed25519_jax as dev


@pytest.fixture
def clean_optin(monkeypatch):
    """Isolate the per-process memo and report of the golden check."""
    monkeypatch.setattr(dev, "_OPTIN_STATE", {})
    monkeypatch.setattr(dev, "_OPTIN_REPORT", {})


def _all_true(*rows):
    return np.ones(8, dtype=bool)  # rows 3 and 6 of the golden batch are bad


def _raises(*rows):
    raise RuntimeError("Mosaic refused the program")


@pytest.mark.parametrize("program,outcome,words", [
    (_all_true, "wrong_verdicts", "WRONG verdicts"),
    (_raises, "error", "RAISED"),
])
def test_golden_check_refuses_a_program(monkeypatch, clean_optin, program,
                                        outcome, words):
    """A packed program that answers wrong, or raises, is refused: the
    check returns False, warns, records why, and auto resolves to
    int64."""
    import jax as _jax

    monkeypatch.setattr(dev, "_compiled", lambda n, impl: program)
    monkeypatch.setattr(dev, "_AUTO_IMPL", None)
    monkeypatch.delenv("TM_TPU_FIELD_IMPL", raising=False)
    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert dev._optin_safe("impl", "packed") is False
        assert dev.default_impl() == "int64"  # the memo: asked once
    assert [words in str(x.message) for x in w] == [True]
    rec = dev.optin_report()["impl/packed"]
    assert rec["outcome"] == outcome
    if outcome == "wrong_verdicts":
        assert rec["got"] == [True] * 8
        assert rec["want"] == [i not in (3, 6) for i in range(8)]
    else:
        assert rec["type"] == "RuntimeError" and "Mosaic" in rec["message"]


def test_golden_standard_program_tier1():
    """Fast tier-1 golden check (ISSUE 7): the STANDARD per-row program
    reproduces the known mixed-validity verdicts.  It clears no caches
    and traces no fresh HLOs — it runs the n=8 floor rung the
    warmup/threshold paths compile anyway (in-process functools cache +
    the persistent compile cache make it effectively free), so the
    golden batch is exercised on every tier-1 run."""
    inputs, want = dev._golden_batch()
    got = [bool(v) for v in np.asarray(dev._compiled(8, "int64")(*inputs))]
    assert got == want


def test_golden_packed_program_tier1():
    """Round-9 twin of the check above for the PACKED limb layout
    (ISSUE 12): golden parity on the warm n=8 floor rung — the program
    the auto-promotion golden gate runs, persistent-cached, so tier-1
    pays no novel-HLO cold compile."""
    inputs, want = dev._golden_batch()
    got = [bool(v) for v in np.asarray(dev._compiled(8, "packed")(*inputs))]
    assert got == want


def test_golden_check_passes_the_real_packed_program(clean_optin):
    """The check itself on the warm floor rung: True, and the report in
    the shape chipbench and chip_smoke.py read."""
    assert dev._optin_safe("impl", "packed") is True
    assert dev.optin_report() == {"impl/packed": {"outcome": "pass"}}


# ---------------------------------------------------------------------------
# TM_TPU_FIELD_IMPL=auto resolution
# ---------------------------------------------------------------------------

@pytest.fixture
def clean_auto(monkeypatch):
    monkeypatch.setattr(dev, "_AUTO_IMPL", None)
    monkeypatch.setattr(dev, "_OPTIN_STATE", {})
    monkeypatch.delenv("TM_TPU_FIELD_IMPL", raising=False)
    yield


def test_auto_impl_is_int64_on_cpu_without_golden_run(clean_auto):
    """The tier-1 contract: on XLA-CPU the auto default short-circuits
    to int64 with NO golden run (no compiles, no _OPTIN_STATE entries),
    so warm cache keys are bit-identical to the pre-auto default."""
    assert dev.default_impl() == "int64"
    assert dev._OPTIN_STATE == {}


def test_explicit_impl_bypasses_auto(clean_auto, monkeypatch):
    monkeypatch.setenv("TM_TPU_FIELD_IMPL", "packed")
    assert dev.default_impl() == "packed"
    # unknown values (a removed backend's name among them) fall into
    # the auto path, not a crash
    for name in ("f32", "bogus"):
        monkeypatch.setenv("TM_TPU_FIELD_IMPL", name)
        assert dev.default_impl() == "int64"


def test_auto_impl_candidates_on_device(clean_auto, monkeypatch):
    """On an accelerator auto takes packed where the golden check
    validates it, else int64 — with the golden gate stubbed so no device
    program compiles here; packed is the one candidate asked about."""
    import jax as _jax

    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    asked = []

    def golden(verdict):
        def _safe(flag, impl):
            asked.append((flag, impl))
            return verdict(impl)
        return _safe

    monkeypatch.setattr(dev, "_optin_safe", golden(lambda impl: True))
    assert dev.default_impl() == "packed"

    monkeypatch.setattr(dev, "_AUTO_IMPL", None)
    monkeypatch.setattr(dev, "_optin_safe", golden(lambda impl: False))
    assert dev.default_impl() == "int64"
    assert asked == [("impl", "packed"), ("impl", "packed")]


def test_auto_impl_memoized_and_reload_env_clears(clean_auto, monkeypatch):
    import jax as _jax

    calls = []

    def fake_backend():
        calls.append(1)
        return "cpu"

    monkeypatch.setattr(_jax, "default_backend", fake_backend)
    assert dev.default_impl() == "int64"
    assert dev.default_impl() == "int64"
    assert len(calls) == 1  # memoized after the first resolution
    dev.reload_env()
    assert dev.default_impl() == "int64"
    assert len(calls) == 2  # reload_env dropped the memo


def test_plan_for_warm_folds_auto_impl(monkeypatch, tmp_path):
    """The warm story carries the promotion: plan_for_warm's implicit
    consolidated plan includes the resolved default impl (int64 on cpu —
    unchanged; a promoted impl is prepended off-cpu)."""
    from tendermint_tpu.ops import shape_plan

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))  # no saved plan
    monkeypatch.delenv("TM_TPU_RUNGS", raising=False)
    monkeypatch.delenv("TM_TPU_SHAPE_PLAN", raising=False)
    assert plan_impls_with(monkeypatch, shape_plan, "int64") == ("int64",)
    assert plan_impls_with(monkeypatch, shape_plan, "packed") == (
        "packed", "int64")


def plan_impls_with(monkeypatch, shape_plan, impl: str):
    monkeypatch.setattr(dev, "default_impl", lambda: impl)
    return plan_impls(shape_plan)


def plan_impls(shape_plan):
    return shape_plan.plan_for_warm().impls
