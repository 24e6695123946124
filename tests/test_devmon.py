"""Device-layer observability (ISSUE 4): occupancy/padding accounting
exact against the `_bucket` ladder, compile-tracker first-call and
double-compile detection, the `device_stats()` snapshot, JSON log
format, jaxcache startup logging, and the `top --once --json` golden
over a live single node (plus exposition TYPE checks for every new
series and the /debug/pprof/device dump).
"""

import asyncio
import json
import logging
import os
import urllib.request

import pytest

from tendermint_tpu.config import test_config as make_test_config
from tendermint_tpu.crypto.batch import set_default_backend
from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.node import Node
from tendermint_tpu.types import GenesisDoc, GenesisValidator
from tendermint_tpu.utils import devmon
from tendermint_tpu.utils.metrics import Histogram


@pytest.fixture(autouse=True)
def cpu_backend():
    set_default_backend("cpu")
    yield
    set_default_backend("auto")


class _Capture(logging.Handler):
    """Handler attached DIRECTLY to a named logger: the package root
    sets propagate=False once node logging is configured, so pytest's
    root-logger caplog never sees these records."""

    def __init__(self):
        super().__init__()
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture
def capture_logger():
    handlers = []

    def attach(name: str, level=logging.INFO) -> _Capture:
        lg = logging.getLogger(name)
        h = _Capture()
        lg.addHandler(h)
        lg.setLevel(level)
        handlers.append((lg, h))
        return h

    yield attach
    for lg, h in handlers:
        lg.removeHandler(h)


# ---------------------------------------------------------------------------
# occupancy / padding math
# ---------------------------------------------------------------------------

def test_occupancy_padding_math_matches_bucket():
    """Exact expected waste at n=1, 64, 129, 320 against the real
    `_bucket` ladder (the 1.49x worst case at 129→192 included)."""
    from tendermint_tpu.ops.ed25519_jax import _bucket

    hist = Histogram("test_occupancy_ratio", "", label_names=("rung",),
                     buckets=devmon.OCCUPANCY_BUCKETS)
    st = devmon.DeviceStats(enabled=True, hist=hist)
    want_buckets = {1: 8, 64: 64, 129: 192, 320: 320}
    for n, want_b in want_buckets.items():
        b = _bucket(n)
        assert b == want_b, (n, b)
        # per-row program ships 4x 32B rows + 1 valid byte per padded row
        st.record_flush("verify", n, b, nbytes=129 * b)

    snap = st.snapshot()
    assert snap["flushes_total"] == 4
    assert snap["rows_requested_total"] == 1 + 64 + 129 + 320
    assert snap["rows_padded_total"] == 8 + 64 + 192 + 320
    assert snap["padding_rows_total"] == (8 - 1) + (192 - 129)
    assert snap["transfer_bytes_total"] == 129 * (8 + 64 + 192 + 320)

    per_rung = {(r["kind"], r["rung"]): r for r in snap["rungs"]}
    assert per_rung[("verify", 192)]["padding_rows"] == 63
    assert per_rung[("verify", 192)]["mean_occupancy"] == round(129 / 192, 4)
    assert per_rung[("verify", 64)]["padding_rows"] == 0
    assert per_rung[("verify", 64)]["mean_occupancy"] == 1.0

    # the histogram saw the exact ratios, one observation per rung
    for n, b in want_buckets.items():
        counts, total, cnt = hist._series[(str(b),)]
        assert cnt == 1
        assert total == n / b  # 1/8, 1.0, 129/192, 1.0 — all f64-exact


def test_disabled_stats_record_nothing():
    st = devmon.DeviceStats(enabled=False)
    # flush sites guard with `if STATS.enabled:` — one branch, no call
    if st.enabled:
        st.record_flush("verify", 10, 16)
    assert st.snapshot()["flushes_total"] == 0


# ---------------------------------------------------------------------------
# compile tracker
# ---------------------------------------------------------------------------

def test_compile_tracker_first_call_and_double_compile(capture_logger):
    cap = capture_logger("tendermint_tpu.devmon", logging.WARNING)
    tr = devmon.CompileTracker()
    calls = []

    def fake_jit(*args):
        calls.append(args)
        return "verdicts"

    p1 = devmon.track_jit(fake_jit, kind="verify", impl="int64", rung=192,
                          tracker=tr, devices=1)
    assert p1("a") == "verdicts"
    assert p1("b") == "verdicts"  # steady state: no second event
    snap = tr.snapshot()
    assert snap["total"] == 1 and snap["recompiles"] == 0
    assert snap["by_rung"] == {"192/int64": 1}
    ev = snap["events"][0]
    assert ev["rung"] == 192 and ev["impl"] == "int64"
    assert ev["cache_hit"] is True  # a stub "compile" is instant
    assert ev["recompile"] is False
    assert len(calls) == 2
    assert not cap.lines

    # the same cache key traced again (functools cache cleared): the
    # unexpected-recompile counter and a warn log
    p2 = devmon.track_jit(fake_jit, kind="verify", impl="int64", rung=192,
                          tracker=tr, devices=1)
    p2("c")
    snap = tr.snapshot()
    assert snap["total"] == 2 and snap["recompiles"] == 1
    assert snap["events"][-1]["recompile"] is True
    assert any("recompile" in ln for ln in cap.lines)

    # a DIFFERENT key (other rung) is a normal compile, not a recompile
    p3 = devmon.track_jit(fake_jit, kind="verify", impl="int64", rung=320,
                          tracker=tr, devices=1)
    p3("d")
    assert tr.snapshot()["recompiles"] == 1


def test_compile_tracker_dynamic_rung():
    """rung=None (the sharded jits): one program per input shape."""

    class Rows:
        def __init__(self, n):
            self.shape = (n, 32)

    tr = devmon.CompileTracker()
    proxy = devmon.track_jit(lambda a: a.shape[0], kind="sharded_verify",
                             impl="int64", tracker=tr, devices=8)
    assert proxy(Rows(128)) == 128
    proxy(Rows(128))
    proxy(Rows(256))
    snap = tr.snapshot()
    assert snap["total"] == 2
    assert set(snap["by_rung"]) == {"128/int64", "256/int64"}


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------

def test_device_stats_snapshot_shape():
    from tendermint_tpu.crypto import async_verify as _av

    st = _av.service_stats()
    assert "queue_depth" in st  # live queue depth rides service_stats now
    snap = _av.device_stats()
    for key in ("enabled", "flushes_total", "padding_rows_total",
                "transfer_bytes_total", "rungs", "compile", "device_memory",
                "queue_depth", "cache_hit_ratio"):
        assert key in snap, key
    assert isinstance(snap["device_memory"], list)
    assert {"total", "seconds_total", "recompiles",
            "by_rung", "events"} <= set(snap["compile"])
    # the text dump renders without a backend ever being touched
    text = devmon.render_text()
    assert "jit compiles" in text and "device memory" in text


# ---------------------------------------------------------------------------
# satellites: JSON log format, jaxcache startup log
# ---------------------------------------------------------------------------

def test_json_log_format(monkeypatch, capture_logger):
    from tendermint_tpu.utils import log as tmlog

    cap = capture_logger("tm-json-test", logging.DEBUG)
    base = logging.getLogger("tm-json-test")
    base.propagate = False
    lg = tmlog.Logger(base).with_(module="consensus")

    monkeypatch.setenv("TM_TPU_LOG_FMT", "json")
    lg.info("hello", height=3, peer="ab12")
    doc = json.loads(cap.lines[-1])
    assert doc["msg"] == "hello" and doc["level"] == "info"
    assert doc["module"] == "consensus"
    assert doc["height"] == 3 and doc["peer"] == "ab12"
    assert isinstance(doc["ts"], float)
    lg.warn("slow", dur_ms=12.5)
    assert json.loads(cap.lines[-1])["level"] == "warn"

    # default text format unchanged
    monkeypatch.delenv("TM_TPU_LOG_FMT")
    lg.info("hello", height=3)
    assert cap.lines[-1] == "hello module=consensus height=3"


class _FakeJax:
    """Stand-in for the jax module: a config that records update()s and
    carries what JAX itself would have read from the environment."""

    def __init__(self, cache_dir=None):
        outer = self
        self.updates = []

        class _Config:
            jax_compilation_cache_dir = cache_dir

            def update(self, k, v):
                outer.updates.append((k, v))
                setattr(self, k, v)

        self.config = _Config()


def test_jaxcache_honours_standard_env_and_sets_nothing_else(
        monkeypatch, tmp_path, capture_logger):
    """JAX_COMPILATION_CACHE_DIR places the cache (and the plan/AOT
    artifacts riding on it); with it set before start-up jax.config
    already carries the directory and enable() updates nothing."""
    from tendermint_tpu.utils import jaxcache

    cap = capture_logger("tendermint_tpu.utils.jaxcache")
    cache = tmp_path / "jcache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    assert jaxcache.cache_dir() == str(cache)
    assert jaxcache.plan_path() == str(cache / "shape_plan.json")
    assert jaxcache.aot_dir() == str(cache / "aot")

    fake = _FakeJax(cache_dir=str(cache))  # as jax reads it at import
    info = jaxcache.enable(fake)
    assert fake.updates == []
    assert info == {"dir": str(cache), "pre_existed": False, "entries": 0,
                    "from_env": True}
    assert "pre_existed=False" in cap.lines[-1]

    cache.mkdir()
    (cache / "prog_abc").write_bytes(b"x")
    info = jaxcache.enable(_FakeJax(cache_dir=str(cache)))
    assert info["pre_existed"] is True and info["entries"] == 1
    assert "entries=1" in cap.lines[-1]

    # the two private names this repo used to read are gone
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("TM_BENCH_CACHE", str(tmp_path / "a"))
    monkeypatch.setenv("TENDERMINT_TPU_JAX_CACHE", str(tmp_path / "b"))
    assert jaxcache.cache_dir() == os.path.join(jaxcache._REPO_ROOT,
                                                ".jax_cache")


def test_jaxcache_default_is_checkout_dir_without_git(
        monkeypatch, tmp_path):
    """A copied tree has no .git: the cache still resolves to
    <checkout>/.jax_cache from the package's own location — never a
    home directory or a temporary name."""
    import shutil
    import subprocess
    import sys

    from tendermint_tpu.utils import jaxcache

    pkg = tmp_path / "copy" / "tendermint_tpu" / "utils"
    pkg.mkdir(parents=True)
    (pkg.parent / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    shutil.copy(jaxcache.__file__, pkg / "jaxcache.py")
    assert not (tmp_path / "copy" / ".git").exists()
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c",
         "from tendermint_tpu.utils import jaxcache; "
         "print(jaxcache.cache_dir())"],
        cwd=tmp_path / "copy", env=env, capture_output=True, text=True,
        timeout=60, check=True)
    assert out.stdout.strip() == str(tmp_path / "copy" / ".jax_cache")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fake = _FakeJax()
    jaxcache.enable(fake)
    assert fake.updates == [("jax_compilation_cache_dir",
                             os.path.join(jaxcache._REPO_ROOT, ".jax_cache"))]


def test_top_roofline_fold_and_render():
    """ISSUE 8 satellite: the per-rung verify panel folds the cost
    gauges into a roofline column (FLOPs-util %, bytes/row) and blanks
    every piece that is absent."""
    from tendermint_tpu.cli import top as top_mod

    exposition = "\n".join([
        'tendermint_crypto_verify_batch_occupancy_ratio_count{rung="192"} 4',
        'tendermint_crypto_verify_batch_occupancy_ratio_sum{rung="192"} 2.7',
        'tendermint_crypto_verify_batch_occupancy_ratio_count{rung="64"} 2',
        'tendermint_crypto_verify_batch_occupancy_ratio_sum{rung="64"} 2.0',
        'tendermint_crypto_verify_rung_flops'
        '{impl="int64",kind="verify",rung="192"} 45400000',
        'tendermint_crypto_verify_rung_bytes_accessed'
        '{impl="int64",kind="verify",rung="192"} 1660000000',
        # an rlc row at the same rung must NOT shadow the verify panel
        'tendermint_crypto_verify_rung_flops'
        '{impl="int64",kind="rlc",rung="192"} 1',
        'tendermint_crypto_verify_device_peak_flops_per_s 1e12',
        'tendermint_crypto_verify_device_execute_seconds_count{rung="192"} 4',
        'tendermint_crypto_verify_device_execute_seconds_sum{rung="192"} 0.2',
    ])
    snap = {"ts": 0.0, "node": {}, "height": 1, "round": 0, "step": "NEW",
            "peers": {"count": 0, "send_queue_depths": {}},
            "verify": {"queue_depth": 0, "submitted": 0, "flushes": 0,
                       "device_batches": 0, "cache_hit_ratio": 0.0,
                       "backend": None, "device_ready": None,
                       "occupancy": {}, "padding_rows_total": 0,
                       "transfer_bytes_total": 0},
            "compile": {"total": 0, "seconds_total": 0.0, "recompiles": 0,
                        "by_rung": {}, "sources": {}},
            "costs": {}, "device_memory": [], "errors": []}
    by_name = top_mod._index(top_mod.parse_exposition(exposition))
    top_mod._fold_metrics(snap, by_name)

    cell = snap["costs"]["192"]
    assert cell["flops"] == 45400000  # the verify row, not the rlc one
    assert cell["hlo_bytes_per_row"] == pytest.approx(1660000000 / 192)
    # achieved = flops / (0.2/4) = 9.08e8; util = achieved / 1e12
    assert cell["flops_util"] == pytest.approx(9.08e8 / 1e12)
    assert "64" not in snap["costs"]  # no cost gauge for rung 64

    text = top_mod.render(snap)
    # rung 192 carries the roofline column; rung 64 degrades to blanks
    assert "u:0.1%" in text and "/row]" in text
    line = next(l for l in text.splitlines() if l.startswith("occupancy"))
    assert "64:2x@1.0 " in line and "[" not in line.split("192:")[0]


def test_top_roofline_line_when_idle():
    """Harvested costs but zero flushes (post-warm idle node): the
    roofline shows on its own line instead of vanishing."""
    from tendermint_tpu.cli import top as top_mod

    snap = {"ts": 0.0, "node": {}, "height": 1, "round": 0, "step": "NEW",
            "peers": {"count": 0, "send_queue_depths": {}},
            "verify": {"queue_depth": 0, "submitted": 0, "flushes": 0,
                       "device_batches": 0, "cache_hit_ratio": 0.0,
                       "backend": None, "device_ready": None,
                       "occupancy": {}, "padding_rows_total": 0,
                       "transfer_bytes_total": 0},
            "compile": {"total": 0, "seconds_total": 0.0, "recompiles": 0,
                        "by_rung": {}, "sources": {}},
            "costs": {"8": {"flops": 1.0, "hlo_bytes_per_row": 1024.0}},
            "device_memory": [], "errors": []}
    text = top_mod.render(snap)
    assert "roofline" in text and "1.0KiB/row" in text


# ---------------------------------------------------------------------------
# live single node: top --once --json golden, status verify_service,
# metrics TYPE conformance for every new series, pprof device dump
# ---------------------------------------------------------------------------

NEW_SERIES_TYPES = [
    ("tendermint_crypto_jit_compile_total", "counter"),
    ("tendermint_crypto_jit_compile_seconds_total", "counter"),
    ("tendermint_crypto_jit_recompile_total", "counter"),
    ("tendermint_crypto_verify_batch_occupancy_ratio", "histogram"),
    ("tendermint_crypto_verify_padding_rows_total", "counter"),
    ("tendermint_crypto_verify_transfer_bytes_total", "counter"),
    ("tendermint_crypto_verify_rung_flushes_total", "counter"),
    ("tendermint_crypto_verify_queue_depth", "gauge"),
    ("tendermint_crypto_device_memory_bytes", "gauge"),
    # ISSUE 8: per-program HLO cost gauges (utils/costmodel)
    ("tendermint_crypto_verify_rung_flops", "gauge"),
    ("tendermint_crypto_verify_rung_bytes_accessed", "gauge"),
    ("tendermint_crypto_verify_rung_peak_memory_bytes", "gauge"),
    ("tendermint_crypto_verify_device_peak_flops_per_s", "gauge"),
]


def test_top_once_json_over_live_node(tmp_path, capsys):
    from tendermint_tpu.cli.main import main as cli_main
    from tendermint_tpu.rpc import core as rpc_core

    async def run():
        key = priv_key_from_seed(b"\x77" * 32)
        gen = GenesisDoc(
            chain_id="devmon-chain",
            genesis_time_ns=1_700_000_000 * 10**9,
            validators=[GenesisValidator(pub_key=key.pub_key(), power=10)],
        )
        cfg = make_test_config(str(tmp_path))
        cfg.base.fast_sync = False
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "tcp://127.0.0.1:0"
        cfg.rpc.pprof_laddr = "tcp://127.0.0.1:0"
        node = Node(cfg, genesis=gen)
        node.priv_validator.priv_key = key
        node.consensus.priv_validator = node.priv_validator
        await node.start()
        try:
            await node.wait_for_height(2, timeout=60)
            rh, rp = node.rpc_addr
            mh, mp = node.metrics.addr
            ph, pp = node.pprof_addr

            rc = await asyncio.to_thread(
                cli_main,
                ["top", "--once", "--json",
                 "--rpc-laddr", f"http://{rh}:{rp}",
                 "--metrics-laddr", f"http://{mh}:{mp}"])
            assert rc == 0

            # RPC status carries the compact verify_service block
            st = rpc_core.status(node.rpc_env)
            vs = st["verify_service"]
            assert vs["enabled"] is True
            assert vs["backend"] in ("jax", "host", "unstarted")
            assert isinstance(vs["device_ready"], bool)
            assert int(vs["queue_depth"]) >= 0
            assert 0.0 <= vs["cache_hit_ratio"] <= 1.0

            def fetch(url):
                with urllib.request.urlopen(url, timeout=5) as r:
                    return r.read().decode()

            # every new series advertises the right exposition TYPE
            text = await asyncio.to_thread(
                fetch, f"http://{mh}:{mp}/metrics")
            for series, kind in NEW_SERIES_TYPES:
                assert f"# TYPE {series} {kind}" in text, series

            # pprof device dump renders the accounting
            dump = await asyncio.to_thread(
                fetch, f"http://{ph}:{pp}/debug/pprof/device")
            assert "jit compiles" in dump
            assert "device flushes" in dump
        finally:
            await node.stop()

    asyncio.run(run())

    out = capsys.readouterr().out
    snap = json.loads(out.strip().splitlines()[-1])
    assert snap["height"] >= 2
    assert snap["peers"]["count"] == 0
    verify = snap["verify"]
    assert verify["queue_depth"] == 0
    assert isinstance(verify["occupancy"], dict)
    assert verify["padding_rows_total"] >= 0
    assert verify["transfer_bytes_total"] >= 0
    assert verify["backend"] in ("jax", "host", "unstarted")
    comp = snap["compile"]
    assert comp["total"] >= 0 and comp["recompiles"] >= 0
    assert isinstance(snap["device_memory"], list)
    assert snap["errors"] == []
