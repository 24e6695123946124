"""`tendermint-tpu benchdiff` (ISSUE 8): artifact-shape normalization
(driver wrapper vs flat vs results-list, including the parsed:null crash
shape), direction-aware classification, the threshold/exit-code matrix,
thresholds-file overrides, and the regression test over a synthetic
artifact pair shaped like two driver rounds (tests/data/benchdiff/) — a
-5% sigs/s drop with lost tail stages must exit 1.
"""

import json
import os

import pytest

from tendermint_tpu.cli.benchdiff import (
    classify,
    diff,
    latest_artifact,
    load_thresholds,
    normalize,
    run_cli,
)
from tendermint_tpu.cli.main import main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data", "benchdiff")
# synthetic fixtures with the shapes bench.py and its driver wrapper
# emit: a run that crashed before emitting (parsed: null), a CPU-fallback
# round, and a device pair whose B side regressed and lost its tail
CRASHED = os.path.join(DATA, "crashed.json")
CPU_FALLBACK = os.path.join(DATA, "cpu_fallback.json")
DEVICE_A = os.path.join(DATA, "device_a.json")
DEVICE_B = os.path.join(DATA, "device_b_regressed.json")


def _artifact(path):
    with open(path) as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def test_normalize_wrapper_flat_and_null_parsed():
    wrapped = {"cmd": "python bench.py", "rc": 0, "n": 1,
               "parsed": {"value": 10.0, "metric": "x"}}
    metrics, meta = normalize(wrapped)
    assert metrics == {"value": 10.0, "metric": "x"}
    assert meta["rc"] == 0

    flat = {"value": 5.0, "metric": "x", "vs_baseline": 1.2}
    metrics, meta = normalize(flat)
    assert metrics["vs_baseline"] == 1.2 and meta == {}

    # r01 shape: the bench crashed before emitting → parsed is null
    crashed = {"cmd": "...", "rc": 1, "tail": "Traceback", "parsed": None}
    metrics, meta = normalize(crashed)
    assert metrics == {} and meta["parse_failed"] is True


def test_normalize_results_list_shape():
    doc = {"results": [
        {"metric": "verify_commit", "value": 17.5, "unit": "ms"},
        {"metric": "fastsync", "value": 35.1},
        "garbage-entry",
    ]}
    metrics, meta = normalize(doc)
    assert metrics == {"verify_commit": 17.5, "fastsync": 35.1}
    assert meta["shape"] == "results-list"


def test_normalize_artifact_files_all_shapes():
    # every fixture shape (and the checked-in baseline's results list)
    # normalizes without error
    for path in (os.path.join(REPO, "BENCH_BASELINE.json"), CRASHED,
                 CPU_FALLBACK, DEVICE_A, DEVICE_B):
        metrics, _meta = normalize(_artifact(path))
        assert isinstance(metrics, dict), path
    # a pre-emit crash carries nothing; the others a headline value
    assert normalize(_artifact(CRASHED))[0] == {}
    assert normalize(_artifact(DEVICE_B))[0]["value"] == 38000.0


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key,cls,direction", [
    ("value", "throughput", "higher"),
    ("vs_baseline", "throughput", "higher"),
    ("field_impl_int64_sigs_per_sec", "throughput", "higher"),
    ("rlc_sigs_per_sec", "throughput", "higher"),
    ("simnet_accepted_tx_per_s", "throughput", "higher"),
    ("simnet_heights_per_min", "throughput", "higher"),
    ("async_coalesce_speedup", "throughput", "higher"),
    ("commit10k_p50_ms", "latency", "lower"),
    ("commit10k_device_only_p50_ms", "latency", "lower"),
    ("journal_enabled_us_per_event", "latency", "lower"),
    # tx-latency stage (ISSUE 9): finality percentiles are tracked at
    # the latency class's 10% default threshold
    ("tx_finality_p50_ms", "latency", "lower"),
    ("tx_finality_p95_ms", "latency", "lower"),
    ("tx_finality_p99_ms", "latency", "lower"),
    ("txlife_enabled_us_per_stamp", "latency", "lower"),
    ("tx_latency_accepted_tx_per_s", "throughput", "higher"),
    ("tx_latency_ok", "boolean", "higher"),
    ("lint_seconds", "timing", "lower"),
    ("jit_recompiles", "count", "lower"),
    ("lint_findings", "count", "lower"),
    ("simnet_ok", "boolean", "higher"),
    ("devstats_within_budget", "boolean", "higher"),
    ("simnet_max_round", None, None),          # informational
    ("commit10k_chunk_plan", None, None),
    # impl-shootout stage (ISSUE 12): per-impl sigs/s land in the 3%
    # throughput gate; per-row HLO resource costs are the 5% resource
    # class — a representation regression in ANY impl is flagged
    ("shootout_packed_sigs_per_sec", "throughput", "higher"),
    ("shootout_int64_sigs_per_sec", "throughput", "higher"),
    ("shootout_f32_sigs_per_sec", "throughput", "higher"),
    ("shootout_packed_hlo_bytes_per_row", "resource", "lower"),
    ("shootout_int64_flops_per_row", "resource", "lower"),
    ("shootout_packed_wall_p50_ms", "latency", "lower"),
])
def test_classify_matrix(key, cls, direction):
    assert classify(key) == (cls, direction)


def test_resource_class_threshold_is_tight():
    """A 6% bytes/row rise is a regression (5% resource gate); 4% is ok;
    a drop is an improvement."""
    a = {"shootout_packed_hlo_bytes_per_row": 1000.0}
    rep = diff(a, {"shootout_packed_hlo_bytes_per_row": 1060.0})
    assert rep["regressions"] == ["shootout_packed_hlo_bytes_per_row"]
    rep = diff(a, {"shootout_packed_hlo_bytes_per_row": 1040.0})
    assert rep["ok"] and rep["rows"][0]["status"] == "ok"
    rep = diff(a, {"shootout_packed_hlo_bytes_per_row": 660.0})
    assert rep["rows"][0]["status"] == "improvement"


def test_shootout_meta_keys_not_tracked():
    rep = diff({"shootout_rung": 1024, "shootout_n": 1024,
                "shootout_runs": 3},
               {"shootout_rung": 2048, "shootout_n": 2048,
                "shootout_runs": 2})
    assert rep["rows"] == [] and rep["ok"]


# ---------------------------------------------------------------------------
# diff semantics
# ---------------------------------------------------------------------------

def test_diff_threshold_matrix():
    a = {"value": 100.0, "x_p50_ms": 10.0, "lint_findings": 0,
         "simnet_ok": True, "simnet_max_round": 2, "n": 16384}
    b = {"value": 98.0, "x_p50_ms": 10.5, "lint_findings": 0,
         "simnet_ok": True, "simnet_max_round": 7, "n": 16384}
    rep = diff(a, b)
    by_key = {r["key"]: r for r in rep["rows"]}
    assert by_key["value"]["status"] == "ok"            # -2% < 3%
    assert by_key["x_p50_ms"]["status"] == "ok"         # +5% < 10%
    assert by_key["simnet_max_round"]["status"] == "info"
    assert "n" not in by_key                            # meta key skipped
    assert rep["ok"] is True

    b2 = dict(b, value=90.0, x_p50_ms=12.0, lint_findings=3,
              simnet_ok=False)
    rep2 = diff(a, b2)
    by_key = {r["key"]: r for r in rep2["rows"]}
    assert by_key["value"]["status"] == "regression"      # -10%
    assert by_key["x_p50_ms"]["status"] == "regression"   # +20% latency
    assert by_key["lint_findings"]["status"] == "regression"  # 0 → 3 = inf
    assert by_key["simnet_ok"]["status"] == "regression"  # True → False
    assert set(rep2["regressions"]) == {"value", "x_p50_ms",
                                        "lint_findings", "simnet_ok"}
    assert rep2["ok"] is False


def test_diff_direction_awareness():
    # a latency DROP and a throughput RISE are improvements, never flagged
    a = {"value": 100.0, "x_p50_ms": 10.0}
    b = {"value": 150.0, "x_p50_ms": 5.0}
    rep = diff(a, b)
    assert rep["ok"] is True
    assert {r["status"] for r in rep["rows"]} == {"improvement"}


def test_diff_missing_and_new_keys():
    a = {"value": 100.0, "rlc_sigs_per_sec": 50.0, "note_str": "x",
         "simnet_max_round": 1}
    b = {"value": 100.0, "brand_new_sigs_per_sec": 1.0}
    rep = diff(a, b)
    # tracked (classified numeric) keys only — the info key and the
    # string never appear in missing_in_b
    assert rep["missing_in_b"] == ["rlc_sigs_per_sec"]
    assert rep["new_in_b"] == ["brand_new_sigs_per_sec"]
    assert rep["ok"] is True  # missing alone is not a failure by default


def test_diff_thresholds_overrides():
    a = {"value": 100.0, "x_p50_ms": 10.0}
    b = {"value": 96.0, "x_p50_ms": 11.5}
    # default: value -4% regression (3%), latency +15% regression (10%)
    assert set(diff(a, b)["regressions"]) == {"value", "x_p50_ms"}
    # per-metric + per-class overrides loosen both
    over = {"thresholds": {"value": 0.05}, "defaults": {"latency": 0.20}}
    assert diff(a, b, thresholds=over)["ok"] is True


def test_load_thresholds_json(tmp_path):
    j = tmp_path / "thr.json"
    j.write_text(json.dumps({"thresholds": {"value": 0.08},
                             "defaults": {"latency": 0.5}}))
    doc = load_thresholds(str(j))
    assert doc["thresholds"]["value"] == 0.08
    assert doc["defaults"]["latency"] == 0.5


def test_load_thresholds_toml(tmp_path):
    try:
        import tomllib  # noqa: F401
    except ImportError:
        pytest.importorskip("tomli",
                            reason="no tomllib/tomli in this container")
    t = tmp_path / "thr.toml"
    t.write_text('[thresholds]\nvalue = 0.08\n[defaults]\nlatency = 0.5\n')
    doc = load_thresholds(str(t))
    assert doc["thresholds"]["value"] == 0.08
    assert doc["defaults"]["latency"] == 0.5


def test_load_thresholds_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"thresholds": ["not", "a", "table"]}))
    with pytest.raises(ValueError):
        load_thresholds(str(bad))


# ---------------------------------------------------------------------------
# the regression pair + CLI exit codes
# ---------------------------------------------------------------------------

def test_regressed_pair_flags_the_sigs_regression(capsys):
    rc = run_cli(DEVICE_A, DEVICE_B, as_json=True)
    rep = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert "value" in rep["regressions"]                      # -5% sigs/s
    assert "field_impl_int64_sigs_per_sec" in rep["regressions"]
    assert "vs_baseline" in rep["regressions"]                # 4.5 → 0
    # the lost tail stages are named, not silently dropped
    assert "rlc_sigs_per_sec" in rep["missing_in_b"]
    assert "commit10k_p50_ms" in rep["missing_in_b"]


def test_cpu_fallback_to_device_is_clean(capsys):
    rc = run_cli(CPU_FALLBACK, DEVICE_A, as_json=True)
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0 and rep["ok"] is True


def test_crash_shape_diffs_without_error(capsys):
    rc = run_cli(CRASHED, CPU_FALLBACK)
    capsys.readouterr()
    assert rc == 0  # nothing shared → nothing regressed


def test_cli_subcommand_wiring_and_text_mode(capsys):
    rc = cli_main(["benchdiff", DEVICE_A, DEVICE_B])
    out = capsys.readouterr().out
    assert rc == 1
    assert "REGRESSION" in out and "value" in out
    assert "missing in B" in out


def test_cli_threshold_file_loosens_to_exit_zero(tmp_path, capsys):
    thr = tmp_path / "thr.json"
    thr.write_text(json.dumps({"defaults": {"throughput": 2.0}}))
    rc = cli_main(["benchdiff", DEVICE_A, DEVICE_B,
                   "--thresholds", str(thr), "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0 and rep["regressions"] == []


def test_cli_fail_on_missing(capsys):
    rc = cli_main(["benchdiff", CPU_FALLBACK, DEVICE_A,
                   "--fail-on-missing"])
    capsys.readouterr()
    assert rc == 1  # xla_cpu_device_sigs_per_sec vanished in the B side


def test_cli_usage_errors(tmp_path, capsys):
    assert run_cli("/nonexistent/a.json", "/nonexistent/b.json") == 2
    bad = tmp_path / "bad.json"
    bad.write_text("not json")
    assert run_cli(str(bad), str(bad)) == 2
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"value": 1.0}))
    assert run_cli(str(good), str(good),
                   thresholds_path="/nonexistent/t.toml") == 2
    capsys.readouterr()


def test_latest_artifact_picks_highest_round(tmp_path):
    for name in ("BENCH_r01.json", "BENCH_r09.json", "BENCH_r10.json",
                 "BENCH_BASELINE.json", "unrelated.json"):
        (tmp_path / name).write_text("{}")
    assert latest_artifact(str(tmp_path)).endswith("BENCH_r10.json")
    assert latest_artifact(str(tmp_path / "missing-dir")) is None
