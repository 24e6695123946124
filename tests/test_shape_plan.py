"""Shape plan + AOT warming (ISSUE 7): the default plan is bit-identical
to the legacy `_bucket` ladder, every flush site's bucket lands on a
plan rung, the padding bound holds over the exhaustive device-eligible
sweep, plan JSON / `warm --json` round-trips, the AOT registry feeds
`_compiled`, and a post-warm standard run records zero
`source="cold"` compile events.

Every test here is compile-free: the AOT compile/serialize hooks are
stubbed (a real fresh compile costs tens of seconds to minutes per
program — the very tax AOT warming exists to kill), and all plan state
is isolated from the repo's shared cache dir via
JAX_COMPILATION_CACHE_DIR.
"""

import json
import os
import threading

import numpy as np
import pytest

from tendermint_tpu.ops import ed25519_jax as dev
from tendermint_tpu.ops import shape_plan
from tendermint_tpu.utils import devmon, jaxcache


@pytest.fixture(autouse=True)
def plan_isolation(monkeypatch, tmp_path):
    """Private cache dir + clean plan/env state; AOT registry and the
    _compiled caches are only dropped when a test actually dirtied them
    (clearing them forces later suites to re-trace)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    for var in ("TM_TPU_RUNGS", "TM_TPU_SHAPE_PLAN", "TM_TPU_AOT",
                "TM_TPU_DONATE"):
        monkeypatch.delenv(var, raising=False)
    shape_plan.reload_plan()
    yield
    shape_plan.reload_plan()
    if shape_plan.registry_snapshot():
        shape_plan.clear_registry()
        dev._compiled.cache_clear()


@pytest.fixture
def stub_compile(monkeypatch):
    """Replace the jit().lower().compile() step with an instant stub so
    warm paths run without touching XLA."""
    compiled = []

    def _stub(kind, rung, impl, flags):
        def exe(*rows):
            return np.ones(rung, dtype=bool)

        compiled.append((kind, rung, impl, dict(flags)))
        return exe, 0.01

    monkeypatch.setattr(shape_plan, "_aot_compile", _stub)
    return compiled


# ---------------------------------------------------------------------------
# plan math
# ---------------------------------------------------------------------------

def test_default_plan_is_the_legacy_ladder():
    """With no env override and no saved plan, _bucket behaves exactly
    as the historical formula — nothing changes until an operator opts
    in (the repo's persistent cache is warm for THESE shapes)."""
    plan = shape_plan.active_plan()
    assert plan.name == "legacy"
    for n in (1, 8, 9, 16, 33, 64, 65, 96, 97, 129, 192, 300, 320, 321,
              500, 600, 10_000, 10_241, 12_289, 16_384, 20_000, 25_000):
        assert dev._bucket(n) == dev._ladder_bucket(n), n
    # the pins test_chunked.py has always asserted
    assert dev._bucket(10_000) == 10_240
    assert dev._bucket(129) == 192


def test_padding_bound_exhaustive_sweep():
    """bucket(n)/n <= 1.5 for every n in the device-eligible [65, 20000]
    sweep (the `_bucket` docstring's historical measurement), for BOTH
    shipped plans; the consolidated plan is genuinely smaller."""
    legacy = shape_plan.legacy_plan()
    cons = shape_plan.consolidated_plan()
    assert len(cons.rungs) < len(legacy.rungs)
    assert 10_240 in cons.rungs  # the 10k-commit north star stays exact-fit
    for plan in (legacy, cons):
        worst, worst_n = 1.0, None
        for n in range(65, 20_001):
            b = plan.bucket(n)
            assert b >= n, (plan.name, n, b)
            if b / n > worst:
                worst, worst_n = b / n, n
        assert worst <= shape_plan.MAX_PADDING, (plan.name, worst_n, worst)
        assert plan.max_padding() == pytest.approx(worst)


def test_every_flush_site_bucket_maps_to_a_plan_rung():
    """The five device flush sites all derive their padded shape from
    _bucket (plus, for the sharded sites, a pad-to-mesh-multiple): over
    the full device-eligible sweep the resulting shape is a plan rung
    for every mesh size the harness runs (1/2/4/8 — every plan rung is
    a multiple of 8)."""
    from tendermint_tpu.parallel.sharding import pad_to_multiple

    for plan_obj in (shape_plan.legacy_plan(), shape_plan.consolidated_plan()):
        rungs = set(plan_obj.rungs)
        for r in plan_obj.rungs:
            assert r % 8 == 0 or r < 8 or r in (8,), r
        for n in range(1, 20_001, 7):
            b = plan_obj.bucket(n)
            assert b in rungs or n > plan_obj.top, (plan_obj.name, n, b)
            # verify_batch and the async enqueue use _bucket directly;
            # the sharded sites pad to the mesh:
            for n_dev in (1, 2, 4, 8):
                bs = max(b, pad_to_multiple(n, n_dev))
                bs = pad_to_multiple(bs, n_dev)
                assert bs == b, (plan_obj.name, n, n_dev, bs, b)


def test_plan_json_and_env_overrides(monkeypatch, tmp_path):
    plan = shape_plan.ShapePlan([8, 64, 4096], impls=("int64",),
                                kinds=("verify", "rlc"), name="mini")
    doc = plan.to_dict()
    back = shape_plan.ShapePlan.from_dict(json.loads(json.dumps(doc)))
    assert back.rungs == plan.rungs and back.kinds == plan.kinds
    assert back.bucket(65) == 4096
    # above the plan's top rung the formula ladder takes over
    assert back.bucket(5000) == dev._ladder_bucket(5000)

    monkeypatch.setenv("TM_TPU_RUNGS", "8,64,1024")
    shape_plan.reload_plan()
    assert shape_plan.active_plan().rungs == (8, 64, 1024)
    assert dev._bucket(100) == 1024

    monkeypatch.delenv("TM_TPU_RUNGS")
    monkeypatch.setenv("TM_TPU_SHAPE_PLAN", "consolidated")
    shape_plan.reload_plan()
    assert shape_plan.active_plan().name == "consolidated"

    p = tmp_path / "plan.json"
    p.write_text(plan.to_json())
    monkeypatch.setenv("TM_TPU_SHAPE_PLAN", str(p))
    shape_plan.reload_plan()
    assert shape_plan.active_plan().rungs == (8, 64, 4096)

    # a malformed file degrades to legacy instead of crashing dispatch
    p.write_text("{not json")
    shape_plan.reload_plan()
    assert shape_plan.active_plan().name == "legacy"


def test_saved_plan_auto_loads_and_version_gates(tmp_path):
    plan = shape_plan.ShapePlan([8, 256], name="saved-test")
    path = shape_plan.save_plan(plan)
    assert path == shape_plan.plan_path()
    shape_plan.reload_plan()
    active = shape_plan.active_plan()
    assert active.name == "saved-test" and active.rungs == (8, 256)
    with pytest.raises(ValueError):
        shape_plan.ShapePlan.from_dict({"version": 99, "rungs": [8]})


def test_consolidated_plan_keeps_hot_exact_fit_rungs():
    """devmon occupancy data feeds the plan: a rung the workload fills
    well survives consolidation even if the base ladder dropped it."""
    stats = {"rungs": [
        {"kind": "verify", "rung": 320, "flushes": 50,
         "mean_occupancy": 1.0},            # hot exact fit: kept
        {"kind": "verify", "rung": 640, "flushes": 1,
         "mean_occupancy": 1.0},            # one-off: not kept
        {"kind": "verify", "rung": 1280, "flushes": 9,
         "mean_occupancy": 0.5},            # badly filled: not kept
    ]}
    plan = shape_plan.consolidated_plan(stats)
    assert 320 in plan.rungs
    assert 640 not in plan.rungs and 1280 not in plan.rungs
    base = shape_plan.consolidated_plan()
    assert set(base.rungs) | {320} == set(plan.rungs)


# ---------------------------------------------------------------------------
# AOT warm + registry + devmon source label
# ---------------------------------------------------------------------------

def test_warm_registers_and_compiled_dispatches_aot(monkeypatch, stub_compile):
    tr = devmon.CompileTracker()
    monkeypatch.setattr(devmon, "TRACKER", tr)
    rep = shape_plan.warm_rungs(kinds=("verify",), rungs=(8, 64),
                                impls=("int64",), serialize=False)
    assert [e["source"] for e in rep] == ["aot", "aot"]
    assert [(c[0], c[1]) for c in stub_compile] == [("verify", 8),
                                                   ("verify", 64)]
    # a second warm of the same grid is a no-op (registry hit)
    rep2 = shape_plan.warm_rungs(kinds=("verify",), rungs=(8,),
                                 impls=("int64",), serialize=False)
    assert rep2[0]["source"] == "registered" and len(stub_compile) == 2

    # the standard dispatch path hands the AOT executable out
    dev._compiled.cache_clear()
    fn = dev._compiled(8, "int64")
    out = fn(np.zeros((8, 32), np.uint8), np.zeros((8, 32), np.uint8),
             np.zeros((8, 32), np.uint8), np.zeros((8, 32), np.uint8),
             np.ones(8, bool))
    assert out.shape == (8,) and out.all()


def test_post_warm_run_records_zero_cold_events(monkeypatch, stub_compile):
    """The acceptance criterion, in miniature: after a warm, a standard
    run's compile accounting shows only aot/deserialized sources —
    jit_compile_total{source="cold"} == 0 — and the metrics samples
    carry the source label."""
    tr = devmon.CompileTracker()
    monkeypatch.setattr(devmon, "TRACKER", tr)
    shape_plan.warm_rungs(kinds=("verify",), rungs=(8,), impls=("int64",),
                          serialize=False)
    dev._compiled.cache_clear()
    fn = dev._compiled(8, "int64")
    for _ in range(3):  # steady state records nothing new
        fn(np.zeros((8, 32), np.uint8), np.zeros((8, 32), np.uint8),
           np.zeros((8, 32), np.uint8), np.zeros((8, 32), np.uint8),
           np.ones(8, bool))
    snap = tr.snapshot()
    assert snap["sources"] == {"aot": 1}
    assert tr.cold_compiles() == 0
    assert snap["events"][0]["source"] == "aot"
    assert ({"rung": "8", "impl": "int64", "source": "aot"}, 1.0) \
        in tr.compile_count_samples()
    # an unwarmed lazy first call classifies by the duration heuristic
    tr.record("verify", 192, "int64", (), 0.01)
    tr.record("verify", 320, "int64", (), 99.0)
    snap = tr.snapshot()
    assert snap["sources"]["persistent-cache"] == 1
    assert snap["sources"]["cold"] == 1
    assert tr.cold_compiles() == 1
    text = devmon.render_text()
    assert "aot" in text and "COLD" in text


def test_serialized_artifact_round_trip(monkeypatch, stub_compile):
    """Artifact lifecycle with the serializer stubbed (XLA-CPU cannot
    relocate real executables — measured: 'Symbols not found' — so the
    disk logic is what this pins): fresh compile writes the .aotx,
    a later warm deserializes it as source="deserialized"."""
    tr = devmon.CompileTracker()
    monkeypatch.setattr(devmon, "TRACKER", tr)
    monkeypatch.setattr(shape_plan, "_dump_executable",
                        lambda exe: b"FAKE-EXECUTABLE")

    def load(blob):
        assert blob == b"FAKE-EXECUTABLE"
        return lambda *a: np.ones(8, dtype=bool)

    monkeypatch.setattr(shape_plan, "_load_executable", load)

    e1 = shape_plan.warm_entry("verify", 8, "int64", serialize=True)
    assert e1["source"] == "aot" and e1["serialized"] is True
    assert os.path.exists(e1["path"])
    assert e1["path"].startswith(jaxcache.aot_dir())

    shape_plan.clear_registry()
    e2 = shape_plan.warm_entry("verify", 8, "int64", serialize=True)
    assert e2["source"] == "deserialized"
    assert tr.snapshot()["sources"] == {"aot": 1, "deserialized": 1}

    # corrupt artifact: recompiles instead of crashing
    with open(e1["path"], "wb") as fh:
        fh.write(b"garbage")
    monkeypatch.setattr(shape_plan, "_load_executable",
                        lambda blob: (_ for _ in ()).throw(ValueError("bad")))
    shape_plan.clear_registry()
    e3 = shape_plan.warm_entry("verify", 8, "int64", serialize=True)
    assert e3["source"] == "aot"


def test_warm_entry_errors_are_contained(monkeypatch):
    def boom(kind, rung, impl, flags):
        raise RuntimeError("compile exploded")

    monkeypatch.setattr(shape_plan, "_aot_compile", boom)
    rep = shape_plan.warm_rungs(kinds=("verify",), rungs=(8, 64),
                                impls=("int64",), serialize=False)
    assert all(e["source"] == "error" for e in rep)
    assert "compile exploded" in rep[0]["error"]
    assert shape_plan.aot_lookup(
        "verify", 8, "int64", **shape_plan._entry_flags("verify", "int64")
    ) is None


@pytest.mark.parametrize("kind,impl,words", [
    ("rlc", "int64", "unknown jit kind 'rlc'"),
    ("verify", "f32", "unknown field impl 'f32'"),
])
def test_saved_plan_naming_a_removed_program_fails_that_entry(kind, impl,
                                                              words):
    """A plan file is input from outside: an entry whose kind or impl
    no longer exists is reported failed before anything is traced, and
    the rest of the grid is still warmed."""
    (rep,) = shape_plan.warm_rungs(kinds=(kind,), rungs=(8,), impls=(impl,),
                                   serialize=False)
    assert rep["source"] == "error" and words in rep["error"]


def test_bucket_ladder():
    assert [dev._bucket(n) for n in (1, 8, 9, 16, 33, 64, 65, 96, 97,
                                     128, 129, 200)] == \
        [8, 8, 16, 16, 64, 64, 96, 96, 128, 128, 192, 256]
    # 5*2^(k-2) rungs from 320 up
    assert [dev._bucket(n) for n in (300, 321, 500, 600)] == \
        [320, 384, 512, 640]
    # the north-star shape: 10k pads 1.024x, not 1.64x
    assert dev._bucket(10_000) == 10_240
    assert dev._bucket(10_241) == 12_288
    assert dev._bucket(12_289) == 16_384
    assert dev._bucket(16_384) == 16_384


def test_warm_plan_saves_and_activates(stub_compile):
    plan = shape_plan.ShapePlan([8, 64], name="wp")
    report = shape_plan.warm_plan(plan, serialize=False)
    assert report["errors"] == 0 and report["sources"] == {"aot": 2}
    assert os.path.exists(report["plan_path"])
    # saving made it the active plan (reload_plan inside warm_plan)
    assert shape_plan.active_plan().name == "wp"
    assert dev._bucket(33) == 64


# ---------------------------------------------------------------------------
# warm-on-start gating
# ---------------------------------------------------------------------------

def test_background_warm_is_strict_opt_in(monkeypatch, stub_compile):
    monkeypatch.setattr(shape_plan, "_BG_STARTED", False)
    # no saved plan: no thread, ever
    assert shape_plan.start_background_warm("test") is False
    # kill switch beats a saved plan
    shape_plan.save_plan(shape_plan.ShapePlan([8], name="bg"))
    monkeypatch.setenv("TM_TPU_AOT", "0")
    assert shape_plan.start_background_warm("test") is False

    monkeypatch.delenv("TM_TPU_AOT")
    done = threading.Event()
    real_warm_plan = shape_plan.warm_plan

    def traced(plan, **kw):
        out = real_warm_plan(plan, **kw)
        done.set()
        return out

    monkeypatch.setattr(shape_plan, "warm_plan", traced)
    assert shape_plan.start_background_warm("test") is True
    assert done.wait(10), "background warm thread never ran"
    # idempotent per process
    assert shape_plan.start_background_warm("test") is False
    assert shape_plan.aot_lookup(
        "verify", 8, "int64", **shape_plan._entry_flags("verify", "int64")
    ) is not None


# ---------------------------------------------------------------------------
# warm CLI
# ---------------------------------------------------------------------------

def _cli(capsys, argv):
    from tendermint_tpu.cli.main import main as cli_main

    rc = cli_main(argv)
    return rc, capsys.readouterr().out


def test_warm_cli_dry_run_json_round_trips_the_plan(capsys, tmp_path):
    rc, out = _cli(capsys, ["warm", "--dry-run", "--json",
                            "--rungs", "8,64,1024"])
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1])
    assert doc["dry_run"] is True
    assert doc["plan"]["rungs"] == [8, 64, 1024]
    # a sparse custom ladder honestly reports its (terrible) bound —
    # the <=1.5x guarantee is a property of the SHIPPED plans
    assert doc["max_padding"] == pytest.approx(1024 / 65, abs=1e-3)
    assert [e["rung"] for e in doc["entries"]] == [8, 64, 1024]

    # round trip: the emitted plan feeds straight back through --plan
    p = tmp_path / "rt.json"
    p.write_text(json.dumps(doc["plan"]))
    rc, out = _cli(capsys, ["warm", "--dry-run", "--json",
                            "--plan", str(p)])
    assert rc == 0
    doc2 = json.loads(out.strip().splitlines()[-1])
    assert doc2["plan"] == doc["plan"]


def test_warm_cli_default_plan_is_consolidated(capsys):
    rc, out = _cli(capsys, ["warm", "--dry-run", "--json"])
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1])
    assert doc["plan"]["name"] == "consolidated"
    assert doc["plan"]["rungs"] == list(shape_plan.CONSOLIDATED_RUNGS)


def test_warm_cli_compiles_saves_and_reports(capsys, monkeypatch,
                                             stub_compile):
    # jaxcache.enable must not repoint the suite's live jax config at
    # the test's private dir
    monkeypatch.setattr(jaxcache, "enable", lambda jax_module: None)
    rc, out = _cli(capsys, ["warm", "--rungs", "8,64", "--impls", "int64",
                            "--kinds", "verify", "--json"])
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1])
    assert doc["sources"] == {"aot": 2} and doc["errors"] == 0
    assert os.path.exists(doc["plan_path"])
    saved = shape_plan.load_plan(doc["plan_path"])
    assert saved.rungs == (8, 64)
    # usage errors exit 2
    rc, _ = _cli(capsys, ["warm", "--rungs", "8", "--plan", "x.json"])
    assert rc == 2
    rc, _ = _cli(capsys, ["warm", "--rungs", "not-a-number"])
    assert rc == 2


def test_plan_mesh_dimension_round_trips():
    """Round 10: the mesh dimension serializes with the plan; JSON
    saved before the dimension existed loads as the single-chip plan."""
    plan = shape_plan.ShapePlan([8, 64], mesh=(8, 2, 1))
    assert plan.mesh == (1, 2, 8)  # sorted, deduped
    doc = plan.to_dict()
    assert doc["mesh"] == [1, 2, 8]
    assert shape_plan.ShapePlan.from_dict(doc).mesh == (1, 2, 8)
    legacy = {k: v for k, v in doc.items() if k != "mesh"}
    assert shape_plan.ShapePlan.from_dict(legacy).mesh == (1,)
    with pytest.raises(ValueError):
        shape_plan.ShapePlan([8], mesh=(0,))


def test_plan_mesh_entries_skip_indivisible_rungs():
    plan = shape_plan.ShapePlan([8, 64], mesh=(1, 2, 8))
    assert plan.mesh_entries() == [(8, 2), (64, 2), (8, 8), (64, 8)]
    # mesh=(1,) — the default — adds no sharded work at all
    assert shape_plan.ShapePlan([8, 64]).mesh_entries() == []
    # a rung the mesh size does not divide is skipped (sharding pads it
    # up to a different rung; warming it here would be a novel program)
    assert shape_plan.ShapePlan([8], mesh=(1, 16)).mesh_entries() == []


def test_plan_for_warm_folds_visible_mesh():
    """On the conftest's 8-device slice the default warm plan grows a
    mesh dimension; a plan that already names mesh sizes is kept as-is
    (the operator chose)."""
    plan = shape_plan.plan_for_warm(None)
    assert plan.mesh == (1, 8)
    explicit = shape_plan.ShapePlan([8, 64], mesh=(1, 2))
    assert shape_plan._fold_mesh(explicit).mesh == (1, 2)


def test_aot_path_keys_on_host_signature(monkeypatch):
    """Satellite 1 (the MULTICHIP_r05 SIGILL tail): AOT artifact paths
    fold in the host-machine signature, so an artifact compiled on a
    different machine is simply absent here — clean recompile, never a
    deserialize of foreign machine code."""
    sig = shape_plan.host_signature()
    assert sig and sig == shape_plan.host_signature()
    p1 = shape_plan._aot_path("verify", 64, "int64", {})
    monkeypatch.setattr(shape_plan, "host_signature", lambda: "otherhost")
    p2 = shape_plan._aot_path("verify", 64, "int64", {})
    assert p1 != p2
