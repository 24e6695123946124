"""tmlint: the tier-1 gate (zero findings over the package) plus
per-rule fixture coverage and the lazy-env regressions the
import-time-env rule demands.

Fixture convention (tests/data/lint/): every line a rule must report
carries a trailing `# LINT: <rule-id>` marker; suppressed and clean
variants carry none.  The tests diff the analyzer's (line, rule) set
against the markers, so a rule that over- or under-reports fails
loudly with the exact lines.
"""

import io
import json
import re
from pathlib import Path

import pytest

from tendermint_tpu.lint import (
    RULES,
    lint_package,
    lint_paths,
    package_root,
    run_cli,
)

FIXTURES = Path(__file__).parent / "data" / "lint"

_MARKER = re.compile(r"#\s*LINT:\s*([a-z\-]+)")


def expected_markers(path: Path) -> set[tuple[int, str]]:
    out = set()
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        m = _MARKER.search(line)
        if m:
            out.add((i, m.group(1)))
    return out


def findings_set(path: Path, rule: str) -> set[tuple[int, str]]:
    return {(f.line, f.rule) for f in lint_paths([path], rules={rule})}


# ---------------------------------------------------------------------------
# the gate: the package itself is clean
# ---------------------------------------------------------------------------

def test_package_has_zero_findings():
    findings = lint_package()
    assert findings == [], "tmlint found violations:\n" + "\n".join(
        f.format() for f in findings)


def test_package_root_is_the_real_tree():
    assert (package_root() / "consensus" / "state.py").exists()


# ---------------------------------------------------------------------------
# per-rule fixtures: planted violations are reported with file:line +
# rule id; suppressed/clean variants are not
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture,rule", [
    ("import_time_env.py", "import-time-env"),
    ("eager_optional.py", "eager-optional-import"),
    ("consensus/wallclock.py", "wallclock-in-consensus"),
    ("ungated_obs.py", "ungated-observability"),
    ("host_sync.py", "host-sync-in-jit"),
    ("metrics_bad.py", "metric-name-conformance"),
    ("simnet/harness.py", "unpluggable-clock"),
    ("shared_mutation.py", "unguarded-shared-mutation"),
    ("blocking_async.py", "blocking-call-in-async"),
    ("thread_lifecycle.py", "thread-lifecycle"),
    ("env_knobs.py", "env-knob-registry"),
])
def test_rule_fixture(fixture, rule):
    path = FIXTURES / fixture
    expected = expected_markers(path)
    assert expected, f"fixture {fixture} has no LINT markers"
    got = findings_set(path, rule)
    assert got == expected, (
        f"missing: {sorted(expected - got)}  spurious: {sorted(got - expected)}")


def test_findings_carry_path_line_and_rule_id():
    f = lint_paths([FIXTURES / "consensus" / "wallclock.py"],
                   rules={"wallclock-in-consensus"})[0]
    assert f.rule == "wallclock-in-consensus"
    assert f.path.endswith("consensus/wallclock.py")
    assert f.line > 0 and f.col > 0
    assert re.match(r".+:\d+:\d+: wallclock-in-consensus: ", f.format())


def test_jax_allowed_in_ops_directories():
    assert lint_paths([FIXTURES / "ops" / "jax_allowed.py"]) == []


def test_wallclock_rule_is_scoped_to_consensus_paths(tmp_path):
    src = (FIXTURES / "consensus" / "wallclock.py").read_text()
    out = tmp_path / "elsewhere.py"
    out.write_text(src)
    assert lint_paths([out], rules={"wallclock-in-consensus"},
                      base=tmp_path) == []


def test_unpluggable_clock_rule_is_scoped_to_seam_files(tmp_path):
    """The same source outside CLOCK_SEAM_FILES is clean — modules the
    virtual clock does not own may read time.* freely."""
    src = (FIXTURES / "simnet" / "harness.py").read_text()
    out = tmp_path / "elsewhere.py"
    out.write_text(src)
    assert lint_paths([out], rules={"unpluggable-clock"},
                      base=tmp_path) == []


def test_unknown_rule_raises():
    with pytest.raises(ValueError, match="no-such-rule"):
        lint_paths([FIXTURES], rules={"no-such-rule"})


# ---------------------------------------------------------------------------
# CLI contract: exit codes, --json, --list-rules
# ---------------------------------------------------------------------------

def test_cli_exit_zero_on_clean_tree():
    buf = io.StringIO()
    assert run_cli([str(FIXTURES / "ops")], out=buf) == 0
    assert "0 finding(s)" in buf.getvalue()


def test_cli_exit_one_with_findings_and_text_format():
    buf = io.StringIO()
    rc = run_cli([str(FIXTURES / "metrics_bad.py")], out=buf,
                 rules="metric-name-conformance")
    assert rc == 1
    text = buf.getvalue()
    assert "metrics_bad.py:" in text
    assert "metric-name-conformance" in text


def test_cli_json_output_is_machine_readable():
    buf = io.StringIO()
    rc = run_cli([str(FIXTURES / "import_time_env.py")], as_json=True,
                 rules="import-time-env", out=buf)
    assert rc == 1
    doc = json.loads(buf.getvalue())
    assert doc["files_scanned"] == 1
    assert doc["rules"] == ["import-time-env"]
    assert doc["elapsed_s"] >= 0
    assert all(set(f) == {"path", "line", "col", "rule", "message"}
               for f in doc["findings"])
    assert len(doc["findings"]) == len(
        expected_markers(FIXTURES / "import_time_env.py"))


def test_cli_exit_two_on_usage_errors(tmp_path, capsys):
    assert run_cli([str(tmp_path / "missing.py")], out=io.StringIO()) == 2
    assert run_cli([str(FIXTURES)], rules="bogus", out=io.StringIO()) == 2
    bad = tmp_path / "unparseable.py"
    bad.write_text("def broken(:\n")
    assert run_cli([str(bad)], out=io.StringIO()) == 2
    capsys.readouterr()


def test_cli_list_rules():
    buf = io.StringIO()
    assert run_cli(list_rules=True, out=buf) == 0
    text = buf.getvalue()
    for rid in RULES:
        assert rid in text


def test_cli_subcommand_wired():
    from tendermint_tpu.cli.main import build_parser

    args = build_parser().parse_args(["lint", "--list-rules"])
    assert args.fn(args) == 0


# ---------------------------------------------------------------------------
# lazy-env regressions: the fixes the import-time-env rule demanded.
# Setting the env var AFTER import must take effect (the PR 3 multinode
# flake was exactly a construction-time env capture).
# ---------------------------------------------------------------------------

def test_trace_enabled_resolves_env_after_import(monkeypatch):
    from tendermint_tpu.utils import trace

    monkeypatch.setattr(trace, "_enabled", None)  # back to unresolved
    monkeypatch.setenv("TM_TPU_TRACE", "1")
    assert trace.enabled() is True
    with trace.span("lint.lazy-env-check", probe=1):
        pass
    assert any(s["name"] == "lint.lazy-env-check" for s in trace.spans())
    # and the off state resolves lazily too
    trace.clear()
    monkeypatch.setattr(trace, "_enabled", None)
    monkeypatch.setenv("TM_TPU_TRACE", "0")
    assert trace.enabled() is False
    with trace.span("lint.should-not-record"):
        pass
    assert not any(s["name"] == "lint.should-not-record"
                   for s in trace.spans())


def test_batch_backend_resolves_env_after_import(monkeypatch):
    from tendermint_tpu.crypto import batch

    monkeypatch.setattr(batch, "_DEFAULT_BACKEND", None)
    monkeypatch.setenv("TM_TPU_CRYPTO_BACKEND", "cpu")
    assert isinstance(batch.new_batch_verifier(), batch.CPUBatchVerifier)
    # reload_env() drops a pinned value back to the environment
    batch.set_default_backend("auto")
    batch.reload_env()
    assert batch._DEFAULT_BACKEND is None
    assert batch._default_backend() == "cpu"
    # invalid env values fall back to auto instead of raising
    monkeypatch.setattr(batch, "_DEFAULT_BACKEND", None)
    monkeypatch.setenv("TM_TPU_CRYPTO_BACKEND", "warp-drive")
    assert batch._default_backend() == "auto"
