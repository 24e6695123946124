"""Where a verify call's host time goes, as spans: the caller's
`commit.*` / `verify.wait` (types/validator.py, crypto/async_verify.py),
the worker's `verify.account` / `verify.resolve` with the `flush` number
that ties one flush's spans, and the device program's phase names
(ops/ed25519_jax.py).  Host path only: the validator sets here are under
the device threshold, so no verify program is traced or compiled."""

import time
import types
from fractions import Fraction

import pytest

from helpers import small_commit
from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto import sigcache_native
from tendermint_tpu.utils import trace

SUBMIT_PARTS = {"verify.submit.keys", "verify.submit.probe"}
CALLER = {"commit.select", "commit.sign_bytes", "commit.add", "commit.verify",
          "verify.submit", "verify.wait", "commit.tally"} | SUBMIT_PARTS
RESOLVE_PARTS = {"verify.cache_put"}
WORKER = {"verify.coalesce", "verify.account", "verify.flush",
          "verify.host_verify", "verify.resolve"} | RESOLVE_PARTS


@pytest.fixture(autouse=True)
def traced_service():
    was = trace.enabled()
    av.reset_service(linger_ms=1.0)
    trace.set_ring_size(trace.DEFAULT_RING_SIZE)
    trace.clear()
    yield
    trace.set_enabled(was)
    trace.clear()
    av.reset_service()


def _entry(val_set, mode):
    if mode == "trusting":
        return lambda chain, bid, h, c: val_set.verify_commit_light_trusting(
            chain, c, Fraction(1, 3))
    return {"full": val_set.verify_commit, "light": val_set.verify_commit_light}[mode]


def _traced_call(mode, corrupt=(), height=3):
    """One call with tracing on, after an untraced one of another height
    has started the worker; returns (spans by name, wall ns, raised)."""
    chain, val_set, bid, warm = small_commit(48, height=2)
    _entry(val_set, mode)(chain, bid, 2, warm)
    chain, val_set, bid, commit = small_commit(48, height=height, corrupt=corrupt)
    trace.clear()
    trace.set_enabled(True)
    raised = None
    t0 = time.perf_counter_ns()
    try:
        _entry(val_set, mode)(chain, bid, height, commit)
    except ValueError as e:
        raised = e
    wall = time.perf_counter_ns() - t0
    deadline = time.monotonic() + 5.0     # the worker records its flush span last
    while (not any(s["name"] == "verify.flush" for s in trace.spans())
           and time.monotonic() < deadline):
        time.sleep(0.001)
    trace.set_enabled(False)
    by = {}
    for s in trace.spans():
        assert s["name"] not in by, f"{s['name']} emitted twice by one call"
        by[s["name"]] = s
    return by, wall, raised


@pytest.mark.parametrize("mode,selected", [("full", 48), ("light", 33),
                                           ("trusting", 17)])
def test_one_call_emits_every_span_with_its_attrs_and_ties(mode, selected):
    by, wall, raised = _traced_call(mode)
    assert raised is None
    assert set(by) == CALLER | WORKER

    n = selected
    assert by["commit.select"]["attrs"] == {"mode": mode, "n_sigs": 48, "selected": n}
    for name in ("commit.verify", "verify.wait", "commit.tally"):
        assert by[name]["attrs"] == {"n": n}, name
    # under 64 rows the sign-bytes are the Python row form; the job's
    # three columns go in by one add_many
    assert by["commit.sign_bytes"]["attrs"] == {"n": n, "path": "template"}
    assert by["commit.add"]["attrs"] == {"n": n, "bulk": 1}
    assert by["verify.submit"]["attrs"] == {"n": n, "fresh": n, "hits": 0}

    # the caller's side: commit.* are roots on one thread, and what the
    # service records on that thread hangs under commit.verify
    tid = by["commit.verify"]["tid"]
    for name in CALLER:
        assert by[name]["tid"] == tid, name
    for name in CALLER - {"verify.submit", "verify.wait"} - SUBMIT_PARTS:
        assert by[name]["parent"] is None, name
    assert by["verify.submit"]["parent"] == by["commit.verify"]["id"]
    assert by["verify.wait"]["parent"] == by["commit.verify"]["id"]
    # the submit's bulk passes: one child each, inside it, never per row
    sub = by["verify.submit"]
    assert by["verify.submit.keys"]["attrs"] == {
        "n": n, "native": int(sigcache_native.available())}
    assert by["verify.submit.probe"]["attrs"] == {"n": n, "hits": 0}
    for name in SUBMIT_PARTS:
        assert by[name]["parent"] == sub["id"], name
        assert sub["t0_ns"] <= by[name]["t0_ns"], name
        assert (by[name]["t0_ns"] + by[name]["dur_ns"]
                <= sub["t0_ns"] + sub["dur_ns"]), name
    # ... and cover the call's wall time.  The best of three calls: on a
    # busy machine the caller can be descheduled in the few microseconds
    # between two spans, which says nothing about what the spans cover
    shares = []
    for height in (3, 4, 5):
        if shares:
            by2, wall, _ = _traced_call(mode, height=height)
        else:
            by2 = by
        shares.append(sum(s["dur_ns"] for s in by2.values()
                          if s["tid"] == tid and s["parent"] is None) / wall)
        if shares[-1] >= 0.95:
            break
    assert max(shares) >= 0.95, shares

    # the worker's side: another thread, one flush number on every span,
    # and the coalesce span names the submit that fed it
    flush = by["verify.coalesce"]["attrs"]["flush"]
    assert isinstance(flush, int) and flush >= 1
    for name in WORKER:
        assert by[name]["tid"] != tid, name
        assert by[name]["parent"] is None or name in RESOLVE_PARTS, name
        assert by[name]["attrs"]["flush"] == flush, name
    assert by["verify.coalesce"]["attrs"]["oldest_submit_ns"] == by["verify.submit"]["t0_ns"]
    assert by["verify.coalesce"]["attrs"]["groups"] == 1
    assert by["verify.account"]["attrs"] == {"n": n, "groups": 1, "flush": flush}
    assert by["verify.resolve"]["attrs"] == {"n": n, "groups": 1, "path": "host",
                                             "flush": flush}
    assert by["verify.flush"]["attrs"]["path"] == "host"

    # in the order of a flush, each inside the caller's wait
    def end(s):
        return s["t0_ns"] + s["dur_ns"]

    # the cache put: one span inside the resolve, every row proven valid
    put, res = by["verify.cache_put"], by["verify.resolve"]
    assert put["parent"] == res["id"]
    assert put["attrs"] == {"n": n, "stored": n, "flush": flush}
    assert res["t0_ns"] <= put["t0_ns"] and end(put) <= end(res)

    assert end(by["verify.coalesce"]) <= by["verify.account"]["t0_ns"]
    assert end(by["verify.account"]) <= by["verify.flush"]["t0_ns"]
    assert end(by["verify.host_verify"]) <= by["verify.resolve"]["t0_ns"]
    assert by["verify.wait"]["t0_ns"] <= by["verify.resolve"]["t0_ns"]
    assert end(by["verify.submit"]) <= by["verify.wait"]["t0_ns"]


def test_a_refused_commit_still_records_its_tally():
    by, _wall, raised = _traced_call("full", corrupt=(5,))
    assert raised is not None and "wrong signature (#5)" in str(raised)
    assert set(by) == CALLER | WORKER
    assert by["commit.tally"]["attrs"] == {"n": 48}
    assert by["commit.tally"]["t0_ns"] >= by["commit.verify"]["t0_ns"] + by["commit.verify"]["dur_ns"]


def test_flush_numbers_count_up_and_pair_the_spans_of_each_flush():
    trace.set_enabled(True)
    for height in (5, 6, 7):
        chain, val_set, bid, commit = small_commit(24, height=height)
        val_set.verify_commit(chain, bid, height, commit)
    deadline = time.monotonic() + 5.0
    while (sum(s["name"] == "verify.flush" for s in trace.spans()) < 3
           and time.monotonic() < deadline):
        time.sleep(0.001)
    trace.set_enabled(False)
    per_flush: dict = {}
    for s in trace.spans():
        if s["name"] in WORKER:
            per_flush.setdefault(s["attrs"]["flush"], set()).add(s["name"])
    assert sorted(per_flush) == [1, 2, 3]
    assert all(names == WORKER for names in per_flush.values())


def test_verify_core_names_its_six_phases():
    """The `jax.named_scope`s of the device program, on a trace of
    verify_core's own structure over a field whose arithmetic is
    replaced by cheap stand-ins (the real one takes seconds to trace)."""
    import jax

    from tendermint_tpu.ops import ed25519_jax as dev
    from tendermint_tpu.ops import shape_plan as plan

    real = dev._field("packed")
    cheap = types.SimpleNamespace(**{k: getattr(real, k) for k in dir(real)
                                     if not k.startswith("__")})
    cheap.fe_mul = lambda a, b: a + b
    cheap.fe_sq = cheap.fe_pow_p58 = lambda a: a
    cheap.pt_dbl_n = lambda p, _n: p
    cheap.pt_add = lambda p, q: real.Pt(*(x + y for x, y in zip(p.astuple(), q.astuple())))
    jaxpr = jax.make_jaxpr(dev._Core(cheap).verify_core)(
        *plan.abstract_rows("verify", 8)).jaxpr

    scopes = [str(e.source_info.name_stack) for e in jaxpr.eqns]
    phases = ["ed25519.unpack", "ed25519.decompress_a", "ed25519.decompress_r",
              "ed25519.scalarmul_base", "ed25519.scalarmul_var", "ed25519.finish"]
    # every equation lies in one phase, and the phases come in program order
    assert list(dict.fromkeys(scopes)) == phases
    loops = [s for e, s in zip(jaxpr.eqns, scopes)
             if e.primitive.name in ("scan", "while")]   # the two fori_loops
    assert loops == ["ed25519.scalarmul_base", "ed25519.scalarmul_var"]
