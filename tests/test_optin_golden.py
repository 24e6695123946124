"""Golden-batch self-check for opt-in kernel flags (VERDICT r4 item 6).

TM_TPU_FE_MXU was measured computing WRONG verdicts on real TPU
(benchmarks/tpu_kernel_r04.jsonl verify_ok=false), and TM_TPU_BASE_MXU
relies on the same Precision.HIGHEST-f32-matmul exactness assumption.
Production paths must therefore run any opt-in kernel once against a
known mixed-validity batch and refuse it — loudly, falling back to the
standard program — when verdicts mismatch.  These tests pin both arms:
the flag is honored where the kernel is exact (XLA-CPU), and a wrong
kernel is disabled without a single wrong verdict escaping.
"""

import warnings

import numpy as np
import pytest

from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.ops import ed25519_jax as dev

# The broken-kernel tests trace fresh XLA programs (the clean_optin
# fixture clears the compiled-program caches on purpose, and the
# monkeypatched kernels produce NOVEL HLOs the persistent cache has
# never seen), and a cold XLA-CPU compile of a verify program costs
# about a minute: those tests regularly blow the tier-1 870 s budget, so
# they carry a per-test `slow` mark (run with `-m slow` on a warm cache).  The tier-1 golden coverage lives in
# test_golden_standard_program_tier1 below: it clears no caches and
# reuses the already-warm floor rung, so it fits the budget — the
# "fast golden check" ISSUE 7 calls for.
slow = pytest.mark.slow


def _small_batch(n=8, bad=(2,)):
    pubs, msgs, sigs, want = [], [], [], []
    for i in range(n):
        k = priv_key_from_seed(bytes([i + 91]) * 32)
        m = b"optin-test-%d" % i
        s = k.sign(m)
        ok = True
        if i in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
            ok = False
        pubs.append(k.pub_key().bytes_())
        msgs.append(m)
        sigs.append(s)
        want.append(ok)
    return pubs, msgs, sigs, want


@pytest.fixture
def clean_optin(monkeypatch):
    """Isolate the per-process opt-in memo + compiled-program caches."""
    monkeypatch.setattr(dev, "_OPTIN_STATE", {})
    dev._compiled.cache_clear()
    yield
    dev._compiled.cache_clear()
    dev._OPTIN_STATE.clear()


@slow
def test_base_mxu_honored_where_exact(monkeypatch, clean_optin):
    """On XLA-CPU (true f32 dots) the comb passes its self-check and the
    flag stays enabled."""
    monkeypatch.setenv("TM_TPU_BASE_MXU", "1")
    pubs, msgs, sigs, want = _small_batch()
    got = [bool(v) for v in dev.verify_batch(pubs, msgs, sigs, impl="int64")]
    assert got == want
    assert dev._OPTIN_STATE[("base_mxu", "int64")] is True


@slow
def test_base_mxu_refused_when_wrong(monkeypatch, clean_optin):
    """A comb that computes garbage is caught by the golden batch: the
    flag is disabled with a warning and verdicts stay correct via the
    standard program."""
    monkeypatch.setenv("TM_TPU_BASE_MXU", "1")

    def broken_comb(self, s_rows):
        # structurally valid points (the identity), wrong results
        return self.fe.pt_identity(s_rows.shape[:-1])

    monkeypatch.setattr(dev._Core, "_scalarmul_base_mxu", broken_comb)
    pubs, msgs, sigs, want = _small_batch()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = [bool(v) for v in
               dev.verify_batch(pubs, msgs, sigs, impl="int64")]
    assert got == want, "wrong verdicts escaped the golden gate"
    assert dev._OPTIN_STATE[("base_mxu", "int64")] is False
    assert any("WRONG verdicts" in str(x.message) for x in w)


@slow
def test_fe_mxu_refused_when_wrong(monkeypatch, clean_optin):
    """The f32 field backend's MXU fe_mul (hardware-refuted in r4) is
    disabled by the gate: module flag flipped, caches dropped, verdicts
    correct."""
    fe32 = dev._field("f32")
    dev._compiled_rlc.cache_clear()

    def broken_mul(a, b):
        return a * b * 0.0  # right shape/dtype, garbage value

    monkeypatch.setattr(fe32, "_fe_mul_mxu", broken_mul)
    monkeypatch.setattr(fe32, "_USE_MXU", True)
    pubs, msgs, sigs, want = _small_batch()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = [bool(v) for v in
               dev.verify_batch(pubs, msgs, sigs, impl="f32")]
    assert got == want
    assert dev._OPTIN_STATE[("fe_mxu", "f32")] is False
    assert fe32._USE_MXU is False  # flipped so later traces are clean
    assert any("WRONG verdicts" in str(x.message) for x in w)


@slow
def test_bench_path_bypasses_gate(monkeypatch, clean_optin):
    """kernel_bench measures the RAW opt-in path (its verify_ok reports
    wrongness); the gate must not be consulted by a direct
    _Core.verify_core call."""
    import functools

    import jax

    monkeypatch.setenv("TM_TPU_BASE_MXU", "1")
    pubs, msgs, sigs, want = _small_batch()
    inputs = dev.prepare_batch(pubs, msgs, sigs)
    core = jax.jit(functools.partial(dev._core("int64").verify_core,
                                     base_mxu=True))
    got = [bool(v) for v in np.asarray(core(*inputs))]
    assert got == want  # exact on XLA-CPU
    assert ("base_mxu", "int64") not in dev._OPTIN_STATE


def test_golden_standard_program_tier1():
    """Fast tier-1 golden check (ISSUE 7): the STANDARD per-row program
    reproduces the known mixed-validity verdicts.  Unlike the opt-in
    tests above this clears no caches and traces no fresh HLOs — it
    runs the n=8 floor rung the warmup/threshold paths compile anyway
    (in-process functools cache + the persistent compile cache make it
    effectively free), so the golden batch is exercised on every tier-1
    run even while the adversarial broken-kernel tests stay `slow`."""
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


# ---------------------------------------------------------------------------
# TM_TPU_FIELD_IMPL=auto resolution (round 9: MXU/packed promotion)
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
    monkeypatch.setenv("TM_TPU_FIELD_IMPL", "f32")
    assert dev.default_impl() == "f32"
    # unknown values fall into the auto path, not a crash
    monkeypatch.setenv("TM_TPU_FIELD_IMPL", "bogus")
    assert dev.default_impl() == "int64"


def test_auto_impl_candidates_on_device(clean_auto, monkeypatch):
    """On an accelerator auto takes packed where the golden check
    validates it, else int64 — with the golden gate stubbed so no device
    program compiles here.  f32+MXU is never a candidate (it computed
    wrong verdicts on every TPU that ran it): its golden check is not
    even consulted, so no start pays its compile to refuse it."""
    import jax as _jax

    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dev._field("f32"), "_USE_MXU", True)
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


def test_fe_mxu_auto_resolves_off_on_cpu(monkeypatch):
    """TM_TPU_FE_MXU's new default 'auto' must resolve False on XLA-CPU
    (bit-identical tier-1 traces) and re-resolve after reload_env."""
    fe32 = dev._field("f32")
    monkeypatch.delenv("TM_TPU_FE_MXU", raising=False)
    monkeypatch.setattr(fe32, "_USE_MXU", None)
    assert fe32._use_mxu() is False
    monkeypatch.setenv("TM_TPU_FE_MXU", "1")
    assert fe32._use_mxu() is False  # cached until reload_env
    fe32.reload_env()
    assert fe32._use_mxu() is True
    monkeypatch.setenv("TM_TPU_FE_MXU", "auto")
    fe32.reload_env()
    import jax as _jax

    monkeypatch.setattr(_jax, "default_backend", lambda: "tpu")
    assert fe32._use_mxu() is True  # auto turns on off-cpu (golden-gated
    fe32.reload_env()              # downstream by _resolve_optin)


def test_base_mxu_never_consulted_for_packed(clean_auto, monkeypatch):
    """The one-hot comb's f32 table cannot hold 26-bit packed limbs
    exactly: _resolve_optin must skip the base_mxu gate entirely for the
    packed impl (structurally wrong, not merely unvalidated)."""
    monkeypatch.setenv("TM_TPU_BASE_MXU", "1")
    assert dev._resolve_optin("packed") is False
    assert ("base_mxu", "packed") not in dev._OPTIN_STATE


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
