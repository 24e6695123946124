"""utils.trace: span nesting/parent links, ring-buffer bounding, the
zero-cost disabled path, and export validity (JSONL + Chrome
trace-event JSON round trips through json.loads)."""

import json

import pytest

from tendermint_tpu.utils import trace


@pytest.fixture(autouse=True)
def fresh_tracer():
    was = trace.enabled()
    trace.set_enabled(False)
    trace.set_ring_size(trace.DEFAULT_RING_SIZE)
    trace.clear()
    yield
    trace.set_enabled(was)
    trace.set_ring_size(trace.DEFAULT_RING_SIZE)
    trace.clear()


def _bare_sites():
    # one branch per site: the disabled span() returns a shared no-op
    # singleton, no allocation, and nothing reaches the ring
    s1 = trace.span("a", k=1)
    s2 = trace.span("b")
    assert s1 is s2
    with s1 as sp:
        sp.set(late=2)
    trace.record("x", 0.0, 1.0)
    trace.instant("y")


def _commit_call(entry):
    """The verify surfaces' and the service's span sites (commit.*,
    verify.*), driven through a whole call on the host path."""
    from helpers import small_commit
    from tendermint_tpu.crypto import async_verify as av

    av.reset_service(linger_ms=1.0)
    try:
        chain, val_set, bid, commit = small_commit(24)
        getattr(val_set, entry)(chain, bid, 3, commit)
    finally:
        av.reset_service()


@pytest.mark.parametrize("drive", [
    _bare_sites,
    lambda: _commit_call("verify_commit"),
    lambda: _commit_call("verify_commit_light"),
], ids=["sites", "verify_commit", "verify_commit_light"])
def test_disabled_path_is_zero_cost_and_records_nothing(drive):
    trace.set_enabled(False)
    drive()
    assert trace.spans() == []
    assert trace.summary() == {}


def test_record_parents_under_the_callers_open_span():
    trace.set_enabled(True)
    with trace.span("outer") as sp:
        sp.set(found=3)
        trace.record("timed.elsewhere", 1.0, 0.5)
    trace.record("root", 2.0, 0.5)
    by = {s["name"]: s for s in trace.spans()}
    assert by["timed.elsewhere"]["parent"] == by["outer"]["id"]
    assert by["root"]["parent"] is None
    assert by["outer"]["attrs"] == {"found": 3}


def test_record_takes_an_explicit_parent_and_returns_its_id():
    """The parts of a span that is itself recorded at its end: the
    parent first (its id returned), the parts under it — whatever span
    the calling thread holds open."""
    assert trace.record("off", 1.0, 0.5) is None      # tracing off: no id
    trace.set_enabled(True)
    with trace.span("outer"):
        whole = trace.record("whole", 1.0, 0.5)
        trace.record("part", 1.1, 0.1, parent=whole, n=3)
    by = {s["name"]: s for s in trace.spans()}
    assert by["whole"]["id"] == whole
    assert by["whole"]["parent"] == by["outer"]["id"]
    assert by["part"]["parent"] == whole
    assert by["part"]["attrs"] == {"n": 3}            # `parent` is no attr


def test_span_nesting_and_parent_links():
    trace.set_enabled(True)
    with trace.span("outer", height=5):
        with trace.span("inner"):
            pass
    sp = trace.spans()
    assert [s["name"] for s in sp] == ["inner", "outer"]  # inner ends first
    by = {s["name"]: s for s in sp}
    assert by["outer"]["parent"] is None
    assert by["inner"]["parent"] == by["outer"]["id"]
    assert by["outer"]["attrs"] == {"height": 5}
    assert by["outer"]["dur_ns"] >= by["inner"]["dur_ns"] >= 0
    # inner is contained in outer on the shared monotonic timeline
    assert by["inner"]["t0_ns"] >= by["outer"]["t0_ns"]


def test_span_records_even_when_body_raises():
    trace.set_enabled(True)
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("x")
    assert [s["name"] for s in trace.spans()] == ["boom"]


def test_ring_buffer_is_bounded_dropping_oldest():
    trace.set_enabled(True)
    trace.set_ring_size(8)
    for i in range(32):
        trace.instant("tick", i=i)
    sp = trace.spans()
    assert len(sp) == 8
    assert [s["attrs"]["i"] for s in sp] == list(range(24, 32))
    # resizing keeps the most recent spans that still fit
    trace.set_ring_size(4)
    assert [s["attrs"]["i"] for s in trace.spans()] == list(range(28, 32))


def test_ring_counts_the_spans_it_drops():
    """A full ring's pushes are counted; a shrink counts what no longer
    fits; clear() starts the count again; the pprof dump prints it."""
    from tendermint_tpu.node import pprof

    trace.set_enabled(True)
    trace.set_ring_size(4)
    for i in range(4):
        trace.instant("tick", i=i)
    assert trace.dropped() == 0
    for i in range(4, 10):
        trace.record("tock", float(i), 0.5, i=i)
    assert trace.dropped() == 6 and len(trace.spans()) == 4
    trace.set_ring_size(3)
    assert trace.dropped() == 7
    trace.set_ring_size(8)
    assert trace.dropped() == 7 and len(trace.spans()) == 3
    assert "dropped=7 " in pprof._trace_dump("")[1].splitlines()[0]
    trace.clear()
    assert trace.dropped() == 0 and trace.spans() == []


def test_a_part_that_ends_with_its_parent_ends_on_the_same_ns():
    trace.set_enabled(True)
    t0, t_part, t_end = 12345.000000123, 12345.000400987, 12345.001234567
    whole = trace.record("whole", t0, t_end - t0)
    trace.record("part", t_part, t_end - t_part, parent=whole)
    by = {s["name"]: s for s in trace.spans()}
    assert (by["part"]["t0_ns"] + by["part"]["dur_ns"]
            == by["whole"]["t0_ns"] + by["whole"]["dur_ns"])


def test_exports_round_trip_and_summary():
    trace.set_enabled(True)
    with trace.span("verify.flush", path="host", n=64):
        pass
    trace.record("verify.device_execute", 1.0, 0.002, rung=256)

    rows = [json.loads(line) for line in trace.export_jsonl().splitlines()]
    assert {r["name"] for r in rows} == {"verify.flush",
                                         "verify.device_execute"}

    doc = json.loads(trace.export_chrome())
    events = doc["traceEvents"]
    assert len(events) == 2
    for ev in events:
        assert ev["ph"] == "X"
        assert {"name", "cat", "ts", "dur", "pid", "tid", "args"} <= set(ev)
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
    dev = next(e for e in events if e["name"] == "verify.device_execute")
    assert dev["dur"] == pytest.approx(2000.0)  # trace-event us
    assert dev["args"]["rung"] == 256

    summ = trace.summary()
    assert summ["verify.flush"]["count"] == 1
    assert summ["verify.device_execute"]["p50_ms"] == pytest.approx(2.0)
    assert summ["verify.device_execute"]["p99_ms"] == pytest.approx(2.0)


def test_record_clamps_negative_duration():
    trace.set_enabled(True)
    trace.record("clock.skew", 5.0, -0.001)
    assert trace.spans()[0]["dur_ns"] == 0


def test_cross_thread_spans_land_in_one_ring():
    import threading

    trace.set_enabled(True)

    def worker():
        with trace.span("thread.child"):
            pass

    t = threading.Thread(target=worker)
    with trace.span("main.parent"):
        t.start()
        t.join()
    names = {s["name"] for s in trace.spans()}
    assert names == {"thread.child", "main.parent"}
    by = {s["name"]: s for s in trace.spans()}
    # separate threads: no false parent link, distinct tids
    assert by["thread.child"]["parent"] is None
    assert by["thread.child"]["tid"] != by["main.parent"]["tid"]
