"""The readers of the caller's `commit.*` / `verify.wait` spans and the
worker's `verify.account` / `verify.resolve` spans, on hand-made spans
whose answers are known by construction — and on none: a program without
those spans (the parent of the PR that added them) reads nothing."""

import pytest

from chipbench import manifest, tracing
from chipbench.observe import Observation

MS = 1_000_000
NEW = ("signbytes_ms", "assemble_ms", "account_ms", "resolve_ms", "collect_ms",
       "idle_unattributed_pct")


def _span(name, t0_ms, dur_ms, tid=1, **attrs):
    return {"name": name, "id": 0, "parent": None, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "tid": tid, "attrs": attrs}


def _call(t0, flush, select=2.0, add=8.0, resolve_path="device"):
    """One call of 100 ms starting at `t0` ms: caller thread 1, worker 2."""
    return [
        _span("commit.select", t0, select, mode="full"),
        _span("commit.sign_bytes", t0 + 10, 5.0, n=10),
        _span("commit.add", t0 + 15, add, n=10),
        _span("commit.verify", t0 + 25, 70.0, n=10),
        _span("verify.submit", t0 + 25, 10.0, n=10, fresh=10),
        _span("verify.wait", t0 + 35, 60.0, n=10),
        _span("verify.coalesce", t0 + 35, 1.0, tid=2, n=10, flush=flush),
        _span("verify.account", t0 + 36, 3.0, tid=2, n=10, flush=flush),
        _span("verify.device_execute", t0 + 40, 40.0, tid=2, n=10, flush=flush),
        _span("verify.resolve", t0 + 80, 9.0, tid=2, n=10, flush=flush,
              path=resolve_path),
        _span("commit.tally", t0 + 95, 4.0, n=10),
    ]


def _obs(spans, trace=None):
    return Observation(cell={}, device={}, calls=[], window_s=1.0,
                       before={}, after={}, compiles_in_window=0, spans=spans,
                       trace=trace, slice=None)


def _read(name, obs):
    return manifest.reader(name)(obs)


def test_every_new_metric_has_a_manifest_entry_and_a_reader():
    entries = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == "verify_p50_ms" and "workloads" not in entries[name]
        assert callable(manifest.reader(name))


@pytest.mark.parametrize("name", NEW)
def test_no_such_span_reads_nothing(name):
    """The parent's spans: none of the new names, and no trace."""
    old = [_span("verify.submit", 0, 10.0, n=10, fresh=10),
           _span("verify.device_execute", 15, 40.0, tid=2, n=10, rung=16)]
    assert _read(name, _obs([])) is None
    assert _read(name, _obs(old)) is None


def test_means_over_the_window():
    spans = _call(0, 1) + _call(200, 2, select=4.0, add=12.0)
    obs = _obs(spans)
    assert _read("signbytes_ms", obs) == pytest.approx(5.0)
    assert _read("assemble_ms", obs) == pytest.approx((10.0 + 16.0) / 2)
    assert _read("account_ms", obs) == pytest.approx(3.0)
    assert _read("resolve_ms", obs) == pytest.approx(9.0)
    # wait ends at +95, the resolve inside it at +89
    assert _read("collect_ms", obs) == pytest.approx(6.0)


def test_assemble_sums_the_jobs_of_a_call_and_keeps_threads_apart():
    two_jobs = [_span("commit.select", 0, 1.0), _span("commit.add", 2, 3.0),
                _span("commit.select", 6, 1.0), _span("commit.add", 8, 3.0),
                _span("commit.verify", 12, 20.0)]
    other = [_span("commit.select", 1, 10.0, tid=7), _span("commit.add", 12, 10.0, tid=7),
             _span("commit.verify", 23, 20.0, tid=7)]
    refused = [_span("commit.select", 50, 100.0, tid=9)]      # raised in the basics
    assert _read("assemble_ms", _obs(two_jobs + other + refused)) == pytest.approx(
        (8.0 + 20.0) / 2)


def test_resolve_counts_device_flushes_only():
    spans = _call(0, 1) + _call(200, 2, resolve_path="host")
    spans[-2]["dur_ns"] = 50 * MS
    assert _read("resolve_ms", _obs(spans)) == pytest.approx(9.0)
    assert _read("resolve_ms", _obs(_call(0, 1, resolve_path="host"))) is None


def test_collect_takes_the_last_resolve_inside_the_wait_and_never_reads_under_zero():
    chunked = [_span("verify.wait", 0, 100.0),
               _span("verify.resolve", 20, 10.0, tid=2, path="device"),
               _span("verify.resolve", 60, 10.0, tid=2, path="device"),
               _span("verify.resolve", 150, 10.0, tid=2, path="device")]  # another caller's
    assert _read("collect_ms", _obs(chunked)) == pytest.approx(30.0)
    # the worker stamps its span's end a moment after the caller left
    late = [_span("verify.wait", 0, 100.0),
            _span("verify.resolve", 90, 10.5, tid=2, path="device")]
    assert _read("collect_ms", _obs(late)) == 0.0
    # every request met the cache: a wait without a resolve is left out
    assert _read("collect_ms", _obs([_span("verify.wait", 0, 1.0)])) is None


def test_idle_unattributed_is_the_idle_time_no_program_span_covers():
    # slice of 1 s at perf 10.0: busy 0.4 s; gaps 10.0-10.3 and 10.7-11.0,
    # and one of 20 us that counts as idle but is nobody's
    gaps = [(10.0, 10.3), (10.5, 10.50002), (10.7, 11.0)]
    red = tracing.Reduced(window_s=1.0, busy_s=0.39998, program_events=[0.2, 0.2],
                          device_ops=[], gaps=gaps)
    spans = [_span("verify.submit", 10_000, 100.0),           # 10.0-10.1
             _span("commit.verify", 10_000, 250.0),           # 10.0-10.25, overlapping
             _span("verify.resolve", 10_650, 150.0, tid=2)]   # 10.65-10.8
    # uncovered: 10.25-10.3 and 10.8-11.0 = 0.25 s of 0.60002 s idle
    assert _read("idle_unattributed_pct", _obs(spans, red)) == pytest.approx(
        0.25 / 0.60002 * 100.0)
    # agrees with the labels of breakdown.idle_gaps, harness span included
    call = [{"name": "harness.call (outside the service's spans)",
             "t0_ns": 10.0e9, "dur_ns": 0.9e9}]
    labels = dict(tracing.label_gaps(gaps, spans + call))
    bare = (labels["harness.call (outside the service's spans)"]
            + labels["no span (between calls, or harness)"])
    assert bare == pytest.approx(0.25)
    # the parent's spans leave most of it bare; no span at all, all of it
    assert _read("idle_unattributed_pct", _obs(spans[:1], red)) == pytest.approx(
        0.5 / 0.60002 * 100.0)
    assert _read("idle_unattributed_pct", _obs([], red)) == pytest.approx(
        0.6 / 0.60002 * 100.0)
