"""The reduction from trace events to per-layer numbers: on a synthetic
trace whose answers are known by construction, and on a trace recorded on
the chip (tests/data/recorded_trace.json.gz, cut down from a traced run of
commit-10k.seq; see its "origin" key)."""

import json
import os

import pytest

from chipbench import tracing
from chipbench.observe import Observation

HERE = os.path.dirname(os.path.abspath(__file__))


def _synthetic():
    # trace clock in ns; sync at 1_000 ns == perf_counter 50.0 s
    mods, ops = [], []
    for k in range(4):                      # four programs of 100 ms, 150 ms apart
        start = 1_000 + 10_000_000 + k * 150_000_000
        mods.append(["jit_verify_core(123)", start, 100_000_000])
        ops.append(["fusion.1", start, 60_000_000])
        ops.append(["fusion.2", start + 60_000_000, 40_000_000])
    return {"planes": {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops},
                       "/device:TPU:0 (sparse)": {}},
            "sync_ns": 1_000.0}


def test_synthetic_busy_programs_gaps():
    ev = _synthetic()
    spans = [{"name": "verify.host_prep", "t0_ns": (50.0 + 0.110 + k * 0.150) * 1e9,
              "dur_ns": 0.050e9} for k in range(4)]
    red = tracing.reduce(ev, 50.0, 50.6, int(50.0 * 1e9) , spans=spans)
    assert red.window_s == pytest.approx(0.6)
    assert red.busy_s == pytest.approx(0.4)
    assert red.program_events == pytest.approx([0.1] * 4)
    assert red.device_ops[0][0] == "fusion.1" and red.device_ops[0][1] == pytest.approx(0.24)
    idle = dict(red.idle_gaps)
    # three 50 ms gaps between programs and the 40 ms after the last one
    assert idle["verify.host_prep"] == pytest.approx(0.190, abs=1e-6)
    assert idle["no span (between calls, or harness)"] == pytest.approx(0.010, abs=1e-6)
    # a gap is cut where a span ends inside it
    cut = dict(tracing.label_gaps([(1.0, 2.0)], [
        {"name": "a", "t0_ns": 0.5e9, "dur_ns": 0.8e9},
        {"name": "b", "t0_ns": 1.2e9, "dur_ns": 0.05e9}]))
    assert cut == pytest.approx({"a": 0.25, "b": 0.05,
                                 "no span (between calls, or harness)": 0.7})
    assert sum(idle.values()) == pytest.approx(0.2)


def test_partial_program_at_the_edge_is_not_counted():
    red = tracing.reduce(_synthetic(), 50.0, 50.40, int(50.0 * 1e9))
    assert len(red.program_events) == 2     # the third is cut by the slice's end
    assert red.busy_s == pytest.approx(0.1 + 0.1 + 0.09)


@pytest.mark.parametrize("breakage,words", [
    (lambda e: e.update(sync_ns=None), "no 'chipbench.sync' annotation"),
    (lambda e: e.update(planes={}), "no device plane"),
    (lambda e: e["planes"]["/device:TPU:0"].update({"XLA Modules": [["jit_other(1)", 2e7, 1e8]]}),
     "no whole 'verify_core' program event"),
])
def test_unreadable_trace_says_what_it_found(breakage, words):
    ev = _synthetic()
    breakage(ev)
    with pytest.raises(tracing.TraceUnreadable, match=words) as exc:
        tracing.reduce(ev, 50.0, 50.6, int(50.0 * 1e9))
    assert "found" in str(exc.value)


def test_readers_on_the_synthetic_trace():
    from chipbench import manifest

    red = tracing.reduce(_synthetic(), 50.0, 50.6, int(50.0 * 1e9))
    spans = [{"name": "verify.device_execute", "t0_ns": (50.005 + k * 0.150) * 1e9,
              "dur_ns": 0.120e9, "attrs": {"n": 10_000, "rung": 10_240}} for k in range(4)]
    zero = {"rows_padded": 0, "rows_requested": 0,
            "hist": {"queue_wait": (0, 0.0), "host_prep": (0, 0.0), "linger": (0, 0.0)}}
    after = {"rows_padded": 40_960, "rows_requested": 40_000,
             "hist": {"queue_wait": (40_000, 80.0), "host_prep": (4, 0.1), "linger": (4, 0.004)}}
    obs = Observation(cell={}, device={"kind": "TPU v5 lite"}, rows_per_call=10_000,
                      calls=[], window_s=0.6, before=zero, after=after,
                      compiles_in_window=0, spans=spans, trace=red, slice=(50.0, 50.6))
    r = lambda name: manifest.reader(name)(obs)  # noqa: E731
    assert r("kernel_us_per_sig") == pytest.approx(10.0)
    assert r("verify_core_roofline") == pytest.approx(7880 * 2048 / 393e12 / 10e-6 * 100)
    assert r("verify_core_roofline") < 1.0
    assert r("execute_minus_kernel_ms") == pytest.approx(20.0)
    assert r("device_idle_pct") == pytest.approx(100 / 3)
    assert r("rung_occupancy_pct") == pytest.approx(40_000 / 40_960 * 100)
    assert r("queue_wait_ms") == pytest.approx(2.0)
    assert r("host_prep_ms") == pytest.approx(25.0)
    assert r("compiles_in_window") == 0.0
    obs.trace = None
    assert r("kernel_us_per_sig") is None and r("verify_core_roofline") is None


def test_recorded_trace_from_the_chip():
    import gzip

    with gzip.open(os.path.join(HERE, "data", "recorded_trace.json.gz"), "rt") as fh:
        rec = json.load(fh)
    red = tracing.reduce(rec["events"], rec["t_on"], rec["t_off"], rec["sync_perf_ns"])
    assert len(red.program_events) == rec["expect"]["programs"]
    assert red.busy_s == pytest.approx(rec["expect"]["busy_s"], rel=1e-6)
    assert 0 < red.busy_s < red.window_s
    assert red.program_events == pytest.approx([rec["expect"]["program_s"]])
    assert red.device_ops and all(" = " not in name for name, _ in red.device_ops)
