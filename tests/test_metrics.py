"""Metrics: exposition format units + a live node serving Prometheus
text with consensus/mempool/p2p/state series.

Scenario parity: reference consensus/metrics.go + node Prometheus server
(node/node.go:925-928).
"""

import asyncio
import urllib.request

import pytest

from tendermint_tpu.config import test_config as make_test_config
from tendermint_tpu.crypto.batch import set_default_backend
from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.node import Node
from tendermint_tpu.types import GenesisDoc, GenesisValidator
from tendermint_tpu.utils.metrics import (
    CallbackCounter,
    Counter,
    Gauge,
    Histogram,
    LabeledCallbackGauge,
    Registry,
)


@pytest.fixture(autouse=True)
def cpu_backend():
    set_default_backend("cpu")
    yield
    set_default_backend("auto")


def test_exposition_format():
    reg = Registry()
    c = reg.register(Counter("txs_total", "Total txs", namespace="tm",
                             subsystem="consensus"))
    g = reg.register(Gauge("height", "Chain height", namespace="tm",
                           subsystem="consensus"))
    gl = reg.register(Gauge("bytes", "Bytes by channel", namespace="tm",
                            subsystem="p2p", label_names=("chan",)))
    h = reg.register(Histogram("lat", "Latency", namespace="tm",
                               buckets=(0.1, 1.0)))
    c.inc(3)
    g.set(42)
    gl.add(10, chan="0x20")
    gl.add(5, chan="0x30")
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.expose()
    assert "# TYPE tm_consensus_txs_total counter" in text
    assert "tm_consensus_txs_total 3" in text
    assert "tm_consensus_height 42" in text
    assert 'tm_p2p_bytes{chan="0x20"} 10' in text
    assert 'tm_p2p_bytes{chan="0x30"} 5' in text
    assert 'tm_lat_bucket{le="0.1"} 1' in text
    assert 'tm_lat_bucket{le="1"} 2' in text
    assert 'tm_lat_bucket{le="+Inf"} 3' in text
    assert "tm_lat_count 3" in text
    # callback gauge evaluated at scrape time
    src = {"v": 7}
    reg2 = Registry()
    reg2.register(Gauge("live", "cb", fn=lambda: src["v"]))
    assert "live 7" in reg2.expose()
    src["v"] = 9
    assert "live 9" in reg2.expose()


def _parse_exposition(text):
    """Parse exposition 0.0.4 text into ({name: type}, [(name, labels,
    value)]).  Minimal but strict enough for conformance checks."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        series, _, value = line.rpartition(" ")
        labels = {}
        if "{" in series:
            name, _, rest = series.partition("{")
            for pair in rest.rstrip("}").split(","):
                k, _, v = pair.partition("=")
                labels[k] = v.strip('"')
        else:
            name = series
        samples.append((name, labels, float(value)))
    return types, samples


def test_exposition_conformance():
    """Prometheus text-format conformance: _total series are typed
    counter, histogram buckets are cumulative and +Inf-terminated per
    labelset, and a raising callback gauge omits its sample without
    failing the scrape."""
    reg = Registry()
    c = reg.register(Counter("reqs_total", "plain counter", namespace="tm"))
    reg.register(CallbackCounter("flushes_total", "callback counter",
                                 namespace="tm", fn=lambda: 5))
    reg.register(LabeledCallbackGauge(
        "bytes_total", "labeled callback counter", namespace="tm",
        kind="counter", fn=lambda: [({"ch": "0x1"}, 7.0)]))
    h = reg.register(Histogram("lat_seconds", "labeled histogram",
                               namespace="tm", label_names=("path",),
                               buckets=(0.01, 0.1, 1.0)))
    reg.register(Gauge("fragile", "raising callback", namespace="tm",
                       fn=lambda: 1 / 0))
    reg.register(Gauge("ok", "working callback", namespace="tm",
                       fn=lambda: 3))
    c.inc(2)
    h.observe(0.05, path="host")
    h.observe(0.5, path="host")
    h.observe(2.0, path="device")

    text = reg.expose()
    types, samples = _parse_exposition(text)

    # every *_total family is advertised as a counter
    total_families = [n for n in types if n.endswith("_total")]
    assert sorted(total_families) == [
        "tm_bytes_total", "tm_flushes_total", "tm_reqs_total"]
    for name in total_families:
        assert types[name] == "counter", (name, types[name])
    assert types["tm_lat_seconds"] == "histogram"

    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert by_name["tm_flushes_total"] == [({}, 5.0)]
    # the raising callback omitted its sample; the scrape still carried
    # every other family
    assert "tm_fragile" not in by_name
    assert by_name["tm_ok"] == [({}, 3.0)]

    # histogram conformance per labelset: cumulative, +Inf-terminated,
    # +Inf bucket == _count
    for path, want_count in (("host", 2.0), ("device", 1.0)):
        buckets = [(labels["le"], v)
                   for labels, v in by_name["tm_lat_seconds_bucket"]
                   if labels.get("path") == path]
        assert buckets[-1][0] == "+Inf"
        values = [v for _le, v in buckets]
        assert values == sorted(values), values  # cumulative
        count = next(v for labels, v in by_name["tm_lat_seconds_count"]
                     if labels.get("path") == path)
        assert buckets[-1][1] == count == want_count
    host_sum = next(v for labels, v in by_name["tm_lat_seconds_sum"]
                    if labels.get("path") == "host")
    assert host_sum == pytest.approx(0.55)


def test_per_peer_series_in_metrics_and_net_info(tmp_path):
    """ISSUE 3 acceptance (p2p leg): with a live peer connected, the
    per-peer byte series appear in /metrics with correct peer_id/chID
    labels, message_receive_count_total carries concrete message types,
    net_info exposes the per-peer connection_status snapshot, and
    dump_consensus_state includes the reactor's peer round state."""
    from tendermint_tpu.node.node_key import load_or_gen_node_key
    from tendermint_tpu.p2p import MemoryNetwork
    from tendermint_tpu.rpc import core as rpc_core

    async def run():
        key = priv_key_from_seed(b"\x66" * 32)
        gen = GenesisDoc(
            chain_id="peer-metrics-chain",
            genesis_time_ns=1_700_000_000 * 10**9,
            validators=[GenesisValidator(pub_key=key.pub_key(), power=10)],
        )
        network = MemoryNetwork()

        v_cfg = make_test_config(str(tmp_path / "v"))
        v_cfg.base.fast_sync = False
        v_cfg.instrumentation.prometheus = True
        v_cfg.instrumentation.prometheus_listen_addr = "tcp://127.0.0.1:0"
        nk_v = load_or_gen_node_key(v_cfg.node_key_file)
        validator = Node(v_cfg, genesis=gen,
                         transport=network.create_transport(nk_v.node_id))
        validator.priv_validator.priv_key = key
        validator.consensus.priv_validator = validator.priv_validator

        f_cfg = make_test_config(str(tmp_path / "f"))
        f_cfg.base.fast_sync = False
        nk_f = load_or_gen_node_key(f_cfg.node_key_file)
        follower = Node(f_cfg, genesis=gen,
                        transport=network.create_transport(nk_f.node_id))

        await validator.start()
        await follower.start()
        await follower.router.dial(nk_v.node_id)
        try:
            await follower.wait_for_height(2, timeout=60)
            host, port = validator.metrics.addr

            def scrape():
                with urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=5
                ) as r:
                    return r.read().decode()

            text = await asyncio.to_thread(scrape)
            _types, samples = _parse_exposition(text)
            by_name = {}
            for name, labels, value in samples:
                by_name.setdefault(name, []).append((labels, value))

            # per-peer byte series labeled with the follower's real id +
            # a hex chID, nonzero in both directions
            for series in ("tendermint_p2p_peer_receive_bytes_total",
                           "tendermint_p2p_peer_send_bytes_total"):
                rows = by_name.get(series, [])
                assert rows, f"{series} missing from /metrics"
                assert all(lbl["peer_id"] == nk_f.node_id and
                           lbl["chID"].startswith("0x")
                           for lbl, _v in rows), rows
                assert sum(v for _l, v in rows) > 0
            # vote-channel (0x22) traffic flowed peer-wise: the validator
            # GOSSIPS votes to the (non-validator) follower, so it shows
            # on the send side; the follower's round-step broadcasts show
            # on the receive side (0x20)
            send_chs = {lbl["chID"] for lbl, _v in
                        by_name["tendermint_p2p_peer_send_bytes_total"]}
            assert "0x22" in send_chs, send_chs
            recv_chs = {lbl["chID"] for lbl, _v in
                        by_name["tendermint_p2p_peer_receive_bytes_total"]}
            assert "0x20" in recv_chs, recv_chs
            # message-type counters carry concrete types on both sides
            mr = {lbl["message_type"]: v for lbl, v in
                  by_name.get("tendermint_p2p_message_receive_count_total", [])}
            assert mr.get("NewRoundStepMessage", 0) > 0, mr
            ms = {lbl["message_type"]: v for lbl, v in
                  by_name.get("tendermint_p2p_message_send_count_total", [])}
            assert ms.get("VoteMessage", 0) > 0, ms
            assert _types["tendermint_p2p_peer_receive_bytes_total"] == "counter"
            assert by_name.get("tendermint_p2p_peers_connected_total") == [({}, 1.0)]

            # net_info: per-peer connection snapshot
            info = rpc_core.net_info(validator.rpc_env)
            assert len(info["peers"]) == 1
            peer = info["peers"][0]
            assert peer["node_info"]["id"] == nk_f.node_id
            st = peer["connection_status"]
            assert st["duration_s"] >= 0
            chans = {c["ch_id"]: c for c in st["channels"]}
            assert "0x22" in chans
            assert chans["0x22"]["recv_bytes"] > 0 or chans["0x22"]["send_bytes"] > 0

            # dump_consensus_state: the reactor's per-peer round state
            dump = rpc_core.dump_consensus_state(validator.rpc_env)
            peers = dump["round_state"]["peers"]
            assert len(peers) == 1 and peers[0]["node_address"] == nk_f.node_id
            ps = peers[0]["peer_state"]
            assert ps["height"] >= 1 and ps["step"]
        finally:
            await follower.stop()
            await validator.stop()

    asyncio.run(run())


def test_node_serves_prometheus(tmp_path):
    async def run():
        key = priv_key_from_seed(b"\x55" * 32)
        gen = GenesisDoc(
            chain_id="metrics-chain",
            genesis_time_ns=1_700_000_000 * 10**9,
            validators=[GenesisValidator(pub_key=key.pub_key(), power=10)],
        )
        cfg = make_test_config(str(tmp_path))
        cfg.base.fast_sync = False
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "tcp://127.0.0.1:0"
        node = Node(cfg, genesis=gen)
        node.priv_validator.priv_key = key
        node.consensus.priv_validator = node.priv_validator
        await node.start()
        try:
            node.mempool.check_tx(b"metric=1")
            await node.wait_for_height(3, timeout=30)
            host, port = node.metrics.addr

            def scrape():
                with urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=5
                ) as r:
                    assert "text/plain" in r.headers["Content-Type"]
                    return r.read().decode()

            text = await asyncio.to_thread(scrape)
            lines = dict(
                l.rsplit(" ", 1) for l in text.splitlines()
                if l and not l.startswith("#")
            )
            assert float(lines["tendermint_consensus_height"]) >= 3
            assert float(lines["tendermint_consensus_validators"]) == 1
            assert float(lines["tendermint_consensus_validators_power"]) == 10
            assert float(lines["tendermint_consensus_total_txs"]) >= 1
            assert float(lines["tendermint_consensus_fast_syncing"]) == 0
            assert float(lines["tendermint_p2p_peers"]) == 0
            assert float(lines["tendermint_state_block_processing_time_count"]) >= 3
            assert float(lines["tendermint_consensus_block_interval_seconds_count"]) >= 1
            # monotonic service counters are exposition-typed counter
            # (not gauge), and the per-step duration histogram populated
            # while the node committed its blocks
            assert "# TYPE tendermint_crypto_verify_submitted_total counter" in text
            assert "# TYPE tendermint_crypto_verify_flushes_total counter" in text
            assert "# TYPE tendermint_consensus_step_duration_seconds histogram" in text
            assert "# TYPE tendermint_crypto_verify_e2e_seconds histogram" in text
            assert "# TYPE tendermint_blocksync_request_duration_seconds histogram" in text
            for series in ("windows", "window_blocks", "window_rows", "window_cuts"):
                assert f"# TYPE tendermint_blocksync_{series}_total counter" in text
            assert "# TYPE tendermint_rpc_request_duration_seconds histogram" in text
            # per-program HLO cost gauges (ISSUE 8, utils/costmodel):
            # present and typed even before any program is harvested
            assert "# TYPE tendermint_crypto_verify_rung_flops gauge" in text
            assert ("# TYPE tendermint_crypto_verify_rung_bytes_accessed "
                    "gauge") in text
            assert ("# TYPE tendermint_crypto_verify_rung_peak_memory_bytes "
                    "gauge") in text
            assert ("# TYPE tendermint_crypto_verify_device_peak_flops_per_s "
                    "gauge") in text
            # tx lifecycle histograms (ISSUE 9, utils/txlife): typed on
            # every scrape; this node committed a tx it admitted itself,
            # so finality + mempool residency have observations, and the
            # single-validator quorum (its own vote) fed quorum-wait
            assert ("# TYPE tendermint_tx_time_to_finality_seconds "
                    "histogram") in text
            assert ("# TYPE tendermint_mempool_residency_seconds "
                    "histogram") in text
            assert ("# TYPE tendermint_consensus_quorum_wait_seconds "
                    "histogram") in text
            assert float(
                lines["tendermint_tx_time_to_finality_seconds_count"]) >= 1
            assert float(
                lines["tendermint_mempool_residency_seconds_count"]) >= 1
            qw_counts = [
                float(v) for k, v in lines.items()
                if k.startswith(
                    "tendermint_consensus_quorum_wait_seconds_count")
            ]
            assert qw_counts and sum(qw_counts) >= 1
            # health watchdog series (ISSUE 10, utils/health.py): typed
            # on every scrape, one status row per detector, all 0 on
            # this healthy single-validator node
            assert "# TYPE tendermint_health_status gauge" in text
            assert ("# TYPE tendermint_health_transitions_total counter"
                    in text)
            assert (lines['tendermint_health_status'
                          '{detector="height_stall"}'] == "0")
            step_counts = [
                float(v) for k, v in lines.items()
                if k.startswith("tendermint_consensus_step_duration_seconds_count")
            ]
            assert step_counts and sum(step_counts) >= 1
            # non-metrics path 404s
            def miss():
                try:
                    urllib.request.urlopen(f"http://{host}:{port}/nope", timeout=5)
                    return 200
                except urllib.error.HTTPError as e:
                    return e.code
            assert await asyncio.to_thread(miss) == 404
        finally:
            await node.stop()

    asyncio.run(run())
