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
    obs = Observation(cell={}, device={"kind": "TPU v5 lite", "count": 1},
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
    assert r("shard_skew_pct") is None      # one plane ran the program
    obs.trace = None
    assert r("kernel_us_per_sig") is None and r("verify_core_roofline") is None


def _four_planes(skew_ns=(0, 2_000_000, 4_000_000, 10_000_000)):
    """The synthetic trace on four chips: each flush runs the program on
    every plane at once; chip k takes 50 ms + skew_ns[k], and starts
    k x 0.1 ms after chip 0."""
    one = _synthetic()["planes"]["/device:TPU:0"]["XLA Modules"]
    planes = {}
    for k, extra in enumerate(skew_ns):
        mods = [[name, start + k * 100_000, 50_000_000 + extra] for name, start, _ in one]
        planes[f"/device:TPU:{k}"] = {"XLA Modules": mods,
                                      "XLA Ops": [["fusion.1", s, d] for _, s, d in mods]}
    return {"planes": planes, "sync_ns": 1_000.0}


def test_four_planes_flushes_not_events_and_the_skew_between_chips():
    from chipbench import manifest

    ev = _four_planes()
    red = tracing.reduce(ev, 50.0, 50.6, int(50.0 * 1e9), chips=4)
    # four flushes of four events, not sixteen programs: a flush's time is
    # its slowest chip's
    assert len(red.program_events) == 4
    assert red.program_events == pytest.approx([0.060] * 4)
    assert [sorted(f) for f in red.flush_programs] == [
        pytest.approx([0.050, 0.052, 0.054, 0.060])] * 4
    # busy: the mean of the chips', (50 + 52 + 54 + 60) / 4 ms a flush
    assert red.busy_s == pytest.approx(4 * 0.054)
    # the idle gaps are the first plane's (chip 0: 50 ms busy of every 150)
    assert sum(s for _, s in red.idle_gaps) == pytest.approx(0.6 - 4 * 0.050)
    spans = [{"name": "verify.device_execute", "t0_ns": (50.005 + k * 0.150) * 1e9,
              "dur_ns": 0.070e9, "attrs": {"n": 10_000, "rung": 10_240}} for k in range(4)]
    obs = Observation(cell={}, device={"kind": "TPU v5 lite", "count": 4}, calls=[],
                      window_s=0.6, before={}, after={}, compiles_in_window=0,
                      spans=spans, trace=red, slice=(50.0, 50.6))
    r = lambda name: manifest.reader(name)(obs)  # noqa: E731
    assert r("shard_skew_pct") == pytest.approx((60 - 50) / 60 * 100)
    assert r("kernel_us_per_sig") == pytest.approx(6.0)
    # against four chips' peak: a quarter of what one chip's floor would read
    assert r("verify_core_roofline") == pytest.approx(
        7880 * 2048 / 393e12 / 4 / 6e-6 * 100)
    assert r("execute_minus_kernel_ms") == pytest.approx(10.0)
    assert r("device_idle_pct") == pytest.approx((1 - 4 * 0.054 / 0.6) * 100)
    # a chip of the cell that ran nothing counts as idle throughout
    del ev["planes"]["/device:TPU:3"]
    assert tracing.reduce(ev, 50.0, 50.6, int(50.0 * 1e9), chips=4).busy_s == pytest.approx(
        4 * (0.050 + 0.052 + 0.054) / 4)


def test_a_flush_cut_on_any_chip_is_not_counted():
    # the slice ends inside the third flush's slowest chip only
    red = tracing.reduce(_four_planes(), 50.0, 50.0 + 0.010 + 0.300 + 0.057,
                         int(50.0 * 1e9), chips=4)
    assert len(red.program_events) == 2 and len(red.flush_programs) == 2


def test_the_traced_slice_is_capped_by_program_executions():
    one = tracing.Slice("unused", 1.0, 2.0, 3, 6)
    four = tracing.Slice("unused", 1.0, 2.0, 3, 6, chips=4)
    assert (one.min_flushes, one.max_flushes) == (3, 6)
    assert (four.min_flushes, four.max_flushes) == (1, 1)      # 4 executions: within 3 to 6


def _recorded():
    import gzip

    with gzip.open(os.path.join(HERE, "data", "recorded_trace.json.gz"), "rt") as fh:
        return json.load(fh)


@pytest.mark.parametrize("count", [1, 4])
def test_roofline_counts_the_chips_on_the_recorded_trace(count):
    """One chip: what the parent read, to the last digit (floor / time);
    four: the same program time against four chips' peak."""
    from chipbench import manifest, work

    rec = _recorded()
    red = tracing.reduce(rec["events"], rec["t_on"], rec["t_off"], rec["sync_perf_ns"])
    spans = [{"name": "verify.device_execute", "t0_ns": rec["t_on"] * 1e9 + 1, "dur_ns": 1.0,
              "attrs": {"n": 10_000}}]
    obs = Observation(cell={}, device={"kind": "TPU v5 lite", "count": count}, calls=[],
                      window_s=1.0, before={}, after={}, compiles_in_window=0,
                      spans=spans, trace=red, slice=(rec["t_on"], rec["t_off"]))
    floor_s, _ = work.floor_seconds_per_sig("TPU v5 lite")
    one_chip = floor_s / (rec["expect"]["program_s"] / 10_000) * 100.0
    got = manifest.reader("verify_core_roofline")(obs)
    assert got == (one_chip if count == 1 else pytest.approx(one_chip / 4))
    assert 0.28 / count < got < 0.30 / count


def test_recorded_trace_from_the_chip():
    rec = _recorded()
    red = tracing.reduce(rec["events"], rec["t_on"], rec["t_off"], rec["sync_perf_ns"])
    assert len(red.program_events) == rec["expect"]["programs"]
    assert red.busy_s == pytest.approx(rec["expect"]["busy_s"], rel=1e-6)
    assert 0 < red.busy_s < red.window_s
    assert red.program_events == pytest.approx([rec["expect"]["program_s"]])
    assert red.device_ops and all(" = " not in name for name, _ in red.device_ops)
