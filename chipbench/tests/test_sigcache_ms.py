"""The `sigcache_ms` reader (PR 35) on hand-made spans whose answer is
known by construction — and on the parent's spans, which have no
`verify.cache_put`: it reads nothing there, not a partial sum."""

import pytest

from chipbench import manifest
from chipbench.observe import Observation

MS = 1_000_000


def _span(name, t0_ms, dur_ms, tid=1, **attrs):
    return {"name": name, "id": 0, "parent": None, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "tid": tid, "attrs": attrs}


def _flush(t0, flush, keys, probe, put, path="device"):
    return [
        _span("verify.submit", t0, keys + probe + 1.0, n=10, fresh=10, hits=0),
        _span("verify.submit.keys", t0, keys, n=10, native=1),
        _span("verify.submit.probe", t0 + keys, probe, n=10, hits=0),
        _span("verify.resolve", t0 + 50, put + 2.0, tid=2, n=10, groups=1,
              path=path, flush=flush),
        _span("verify.cache_put", t0 + 51, put, tid=2, n=10, stored=10,
              flush=flush),
    ]


def _read(spans):
    obs = Observation(cell={}, device={}, calls=[], window_s=1.0, before={},
                      after={}, compiles_in_window=0, spans=spans, trace=None,
                      slice=None)
    return manifest.reader("sigcache_ms")(obs)


def test_manifest_entry():
    entry = {m["name"]: m for m in manifest.load()["per_layer"]}["sigcache_ms"]
    assert (entry["source"], entry["layer"], entry["moves"]) == (
        "program_span", "service / router", "verify_p50_ms")
    assert "workloads" not in entry


def test_three_passes_summed_over_the_device_flushes():
    spans = _flush(0, 1, 4.0, 1.0, 2.0) + _flush(100, 2, 6.0, 1.0, 2.0)
    assert _read(spans) == pytest.approx((7.0 + 9.0) / 2)


def test_a_host_flush_adds_its_passes_but_is_not_a_flush_of_the_divisor():
    spans = _flush(0, 1, 4.0, 1.0, 2.0) + _flush(100, 2, 3.0, 0.5, 0.5, path="host")
    assert _read(spans) == pytest.approx(7.0 + 4.0)
    assert _read(_flush(0, 1, 4.0, 1.0, 2.0, path="host")) is None


def test_no_cache_put_reads_nothing():
    """The parent's spans: keys and probe but no put — and no spans."""
    parent = [s for s in _flush(0, 1, 4.0, 1.0, 2.0)
              if s["name"] != "verify.cache_put"]
    assert _read(parent) is None
    assert _read([]) is None
