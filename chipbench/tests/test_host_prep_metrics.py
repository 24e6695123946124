"""The readers of host prep's steps, the launch and the light client's
start, on hand-made spans whose answers are known by construction: two
flushes in flight at once, their spans interleaved in time, so that
pairing by order would read wrong — and on none, the parent's spans,
where each reads nothing.  And the new spans beside the recorded trace,
which move none of the readers that were there."""

import gzip
import json
import os

import pytest

from chipbench import manifest, tracing
from chipbench.observe import Observation

MS = 1_000_000
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("host_prep_gather_ms", "host_prep_k_ms", "host_prep_rest_ms", "launch_ms",
       "client_start_ms")
STEPS = ("rows", "canon", "msgs", "k", "pad")
# the readers that were there and read the reduced trace
TRACE_READERS = ("kernel_us_per_sig", "verify_core_roofline", "execute_minus_kernel_ms",
                 "device_idle_pct", "idle_unattributed_pct", "shard_skew_pct")


def _span(name, t0_ms, dur_ms, sid=0, parent=None, **attrs):
    return {"name": name, "id": sid, "parent": parent, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "tid": 2, "attrs": attrs}


def _flush(t0, flush, steps, launch, sid):
    """One flush's host prep (its five steps, then `self` ms of its own
    between them), its launch and its device execute; ids from `sid`."""
    spans = [_span("verify.host_prep", t0, sum(steps) + 0.5, sid, n=10, rung=16, flush=flush)]
    at = t0 + 0.1
    for k, (step, dur) in enumerate(zip(STEPS, steps)):
        spans.append(_span(f"verify.host_prep.{step}", at, dur, sid + 1 + k, sid, flush=flush))
        at += dur + 0.1
    spans.append(_span("verify.launch", at + 1.0, launch, sid + 6, flush=flush, rung=16, chips=1))
    spans.append(_span("verify.device_execute", at + 1.0, 20.0, sid + 7, n=10, rung=16,
                       flush=flush))
    return spans


def _two_flushes_interleaved():
    """Flush 2 is host-prepped while flush 1 is on the device, and its
    spans are recorded first: by order, 1 would pair with 2's."""
    one = _flush(0.0, 1, (1.0, 0.2, 0.5, 3.0, 0.3), 0.4, 100)
    two = _flush(5.0, 2, (2.0, 0.4, 1.5, 5.0, 0.1), 0.8, 200)
    return two + one + [_span("verify.resolve", 30.0, 1.0, 300, flush=1, path="device")]


def _obs(spans, trace=None, slice_=None):
    return Observation(cell={}, device={}, calls=[], window_s=1.0, before={}, after={},
                       compiles_in_window=0, spans=spans, trace=trace, slice=slice_)


def _read(name, obs):
    return manifest.reader(name)(obs)


def test_manifest_entries():
    entries = {m["name"]: m for m in manifest.load()["per_layer"]}
    layers = {"host_prep_gather_ms": "host prep", "host_prep_k_ms": "host prep",
              "host_prep_rest_ms": "host prep", "launch_ms": "readback / resolve",
              "client_start_ms": "light client"}
    for name, layer in layers.items():
        assert entries[name]["layer"] == layer and entries[name]["moves"] == "verify_p50_ms"
    assert entries["client_start_ms"]["workloads"] == ["skip-1000.bisect"]
    assert all("workloads" not in entries[n] for n in layers if n != "client_start_ms")
    assert list(entries)[-len(NEW):] == list(NEW)


def test_host_prep_steps_pair_by_flush_and_partition_the_span():
    obs = _obs(_two_flushes_interleaved())
    gather = _read("host_prep_gather_ms", obs)
    k = _read("host_prep_k_ms", obs)
    rest = _read("host_prep_rest_ms", obs)
    assert gather == pytest.approx(((1.0 + 0.5) + (2.0 + 1.5)) / 2)
    assert k == pytest.approx((3.0 + 5.0) / 2)
    # canon + pad + the span's own 0.5 ms
    assert rest == pytest.approx(((0.2 + 0.3 + 0.5) + (0.4 + 0.1 + 0.5)) / 2)
    whole = [s["dur_ns"] / MS for s in obs.spans if s["name"] == "verify.host_prep"]
    assert gather + k + rest == pytest.approx(sum(whole) / len(whole))


def test_a_flush_whose_host_prep_has_no_steps_is_not_counted():
    spans = _two_flushes_interleaved()
    spans.append(_span("verify.host_prep", 40.0, 100.0, 400, flush=3))   # no steps
    assert _read("host_prep_k_ms", _obs(spans)) == pytest.approx(4.0)


def test_launch_pairs_by_flush():
    assert _read("launch_ms", _obs(_two_flushes_interleaved())) == pytest.approx(0.6)


def test_client_start_is_the_mean_a_call():
    spans = [_span("light.client_start", 0.0, 20.0, 1, height=1, decodes=2),
             _span("light.fetch", 1.0, 5.0, 2, 1, height=1),
             _span("light.client_start", 100.0, 30.0, 3, height=1, decodes=2)]
    assert _read("client_start_ms", _obs(spans)) == pytest.approx(25.0)


@pytest.mark.parametrize("name", NEW)
def test_no_such_span_reads_nothing(name):
    """The parent's spans: `verify.host_prep` without steps, no launch,
    no client's start — and its trace, with no launch annotation."""
    parent = [s for s in _two_flushes_interleaved()
              if s["name"] in ("verify.host_prep", "verify.device_execute", "verify.resolve")]
    red = tracing.reduce(_events(), 50.0, 50.6, int(50.0 * 1e9))
    assert _read(name, _obs(parent, red, (50.0, 50.6))) is None
    assert _read(name, _obs([])) is None


def _events():
    """A synthetic trace (clock ns; sync at 1,000 ns = perf_counter 50 s):
    two programs."""
    mods = [["jit_verify_core(1)", 1_000 + 10_000_000 + k * 150_000_000, 100_000_000]
            for k in range(2)]
    return {"planes": {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": mods}},
            "sync_ns": 1_000.0}


def _recorded():
    with gzip.open(os.path.join(HERE, "data", "recorded_trace.json.gz"), "rt") as fh:
        return json.load(fh)


def test_the_recorded_trace_reduces_as_it_did_and_the_trace_readers_read_the_same():
    """The recorded trace reduces to what it did, and none of the readers
    that were there and read the trace moves when a flush's host-prep
    steps and launch are recorded beside its device execute."""
    rec = _recorded()
    red = tracing.reduce(rec["events"], rec["t_on"], rec["t_off"], rec["sync_perf_ns"])
    assert len(red.program_events) == rec["expect"]["programs"]
    assert red.busy_s == pytest.approx(rec["expect"]["busy_s"], rel=1e-6)
    assert red.program_events == pytest.approx([rec["expect"]["program_s"]])
    t0 = rec["t_on"] * 1e3 + 1e-3
    parent = [_span("verify.device_execute", t0, 1.0, 1, n=10_000, rung=10_240, flush=7)]
    steps = [_span(f"verify.host_prep.{step}", t0 - 1.0 + 0.1 * k, 0.1, 3 + k, 2, flush=7)
             for k, step in enumerate(STEPS)]
    change = parent + steps + [
        _span("verify.host_prep", t0 - 1.0, 0.6, 2, n=10_000, rung=10_240, flush=7),
        _span("verify.launch", t0, 0.2, 8, flush=7, rung=10_240, chips=1)]
    for name in TRACE_READERS:
        got = [_read(name, Observation(
            cell={}, device={"kind": "TPU v5 lite", "count": 1}, calls=[], window_s=1.0,
            before={}, after={}, compiles_in_window=0, spans=spans, trace=red,
            slice=(rec["t_on"], rec["t_off"]))) for spans in (parent, change)]
        assert got[0] == got[1], name
