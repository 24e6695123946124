"""Node metrics assembly (reference node/node.go:112-126
MetricsProvider + per-subsystem Metrics structs).

Point-in-time values (height, peers, mempool size, validator power) are
callback gauges read at scrape; flow values (block interval, tx counts,
block sizes, processing time) are fed by an EventBus NewBlock
subscription so the consensus hot path carries no metrics code.
"""

from __future__ import annotations

import asyncio

from tendermint_tpu.pubsub import SubscriptionCancelledError
from tendermint_tpu.types import events as tmevents
from tendermint_tpu.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsServer,
    Registry,
)


class StateMetrics:
    """reference state/metrics.go"""

    def __init__(self, reg: Registry, ns: str):
        self.block_processing_time = reg.register(Histogram(
            "block_processing_time",
            "Time spent executing a block against the app (s)",
            namespace=ns, subsystem="state",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0),
        ))


class NodeMetrics:
    def __init__(self, node, namespace: str = "tendermint"):
        self.node = node
        self.registry = Registry()
        reg, ns = self.registry, namespace

        # -- consensus (reference consensus/metrics.go:77-186) ----------
        self.height = reg.register(Gauge(
            "height", "Height of the chain", namespace=ns, subsystem="consensus",
            fn=lambda: node.block_store.height(),
        ))
        self.rounds = reg.register(Gauge(
            "rounds", "Round of the current height", namespace=ns,
            subsystem="consensus", fn=lambda: node.consensus.rs.round,
        ))
        self.validators = reg.register(Gauge(
            "validators", "Number of validators", namespace=ns,
            subsystem="consensus",
            fn=lambda: len(node.consensus.rs.validators.validators)
            if node.consensus.rs.validators else 0,
        ))
        self.validators_power = reg.register(Gauge(
            "validators_power", "Total voting power", namespace=ns,
            subsystem="consensus",
            fn=lambda: node.consensus.rs.validators.total_voting_power()
            if node.consensus.rs.validators else 0,
        ))
        self.fast_syncing = reg.register(Gauge(
            "fast_syncing", "Whether the node is fast-syncing", namespace=ns,
            subsystem="consensus",
            fn=lambda: 0 if node._consensus_running else 1,
        ))
        self.num_txs = reg.register(Gauge(
            "num_txs", "Txs in the latest block", namespace=ns,
            subsystem="consensus",
        ))
        self.block_size_bytes = reg.register(Gauge(
            "block_size_bytes", "Size of the latest block", namespace=ns,
            subsystem="consensus",
        ))
        # upstream parity: the reference exposes exactly
        # `tendermint_consensus_total_txs` (consensus/metrics.go), so the
        # non-conventional name is kept for dashboard compatibility
        # tmlint: disable=metric-name-conformance
        self.total_txs = reg.register(Counter(
            "total_txs", "Total committed txs since start", namespace=ns,
            subsystem="consensus",
        ))
        self.block_interval_seconds = reg.register(Histogram(
            "block_interval_seconds", "Time between this and the last block",
            namespace=ns, subsystem="consensus",
            buckets=(0.1, 0.25, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0, 60.0),
        ))

        # -- mempool (reference mempool/metrics.go) ---------------------
        self.mempool_size = reg.register(Gauge(
            "size", "Unconfirmed txs in the mempool", namespace=ns,
            subsystem="mempool", fn=lambda: node.mempool.size(),
        ))

        # -- p2p (reference p2p/metrics.go) -----------------------------
        self.peers = reg.register(Gauge(
            "peers", "Connected peers", namespace=ns, subsystem="p2p",
            fn=lambda: len(node.router.peers),
        ))
        from tendermint_tpu.utils.metrics import (
            CallbackCounter,
            LabeledCallbackGauge,
        )

        self.p2p_recv_bytes = reg.register(LabeledCallbackGauge(
            "message_receive_bytes_total", "Bytes received per channel",
            namespace=ns, subsystem="p2p", kind="counter",
            fn=lambda: [({"chID": f"{cid:#x}"}, v)
                        for cid, v in sorted(node.router.bytes_received.items())],
        ))
        self.p2p_send_bytes = reg.register(LabeledCallbackGauge(
            "message_send_bytes_total", "Bytes sent per channel",
            namespace=ns, subsystem="p2p", kind="counter",
            fn=lambda: [({"chID": f"{cid:#x}"}, v)
                        for cid, v in sorted(node.router.bytes_sent.items())],
        ))

        # per-peer series (reference p2p/metrics.go PeerReceiveBytesTotal /
        # PeerSendBytesTotal{peer_id, chID} + MessageReceiveBytesTotal
        # by message_type): the cross-node debugging surface — which
        # peer's votes arrived, over which channel, and how deep its
        # send queues sit right now
        def _per_peer(table):
            return [({"peer_id": pid, "chID": f"{cid:#x}"}, v)
                    for pid, chans in sorted(table.items())
                    for cid, v in sorted(chans.items())]

        self.p2p_peer_recv_bytes = reg.register(LabeledCallbackGauge(
            "peer_receive_bytes_total", "Bytes received per peer per channel",
            namespace=ns, subsystem="p2p", kind="counter",
            fn=lambda: _per_peer(node.router.peer_bytes_received),
        ))
        self.p2p_peer_send_bytes = reg.register(LabeledCallbackGauge(
            "peer_send_bytes_total", "Bytes sent per peer per channel",
            namespace=ns, subsystem="p2p", kind="counter",
            fn=lambda: _per_peer(node.router.peer_bytes_sent),
        ))
        self.p2p_msg_recv_count = reg.register(LabeledCallbackGauge(
            "message_receive_count_total", "Decoded inbound messages by type",
            namespace=ns, subsystem="p2p", kind="counter",
            fn=lambda: [({"message_type": t}, v)
                        for t, v in sorted(node.router.msg_recv_count.items())],
        ))

        def _msg_send_count():
            agg: dict[str, int] = {}
            for ch in node.router.channels.values():
                for t, v in ch.msg_send_count.items():
                    agg[t] = agg.get(t, 0) + v
            return [({"message_type": t}, v) for t, v in sorted(agg.items())]

        self.p2p_msg_send_count = reg.register(LabeledCallbackGauge(
            "message_send_count_total", "Outbound messages by type (all channels)",
            namespace=ns, subsystem="p2p", kind="counter",
            fn=_msg_send_count,
        ))
        self.p2p_send_queue_depth = reg.register(LabeledCallbackGauge(
            "peer_send_queue_depth",
            "Messages queued per peer per channel (live peers only)",
            namespace=ns, subsystem="p2p",
            fn=lambda: [({"peer_id": pid, "chID": f"{cid:#x}"}, depth)
                        for pid, cid, depth
                        in sorted(node.router.send_queue_depths())],
        ))
        self.p2p_peers_connected = reg.register(CallbackCounter(
            "peers_connected_total", "Peer connections established",
            namespace=ns, subsystem="p2p",
            fn=lambda: node.router.peers_connected,
        ))
        self.p2p_peers_disconnected = reg.register(CallbackCounter(
            "peers_disconnected_total", "Peer connections dropped",
            namespace=ns, subsystem="p2p",
            fn=lambda: node.router.peers_disconnected,
        ))

        # -- crypto: the async verification service ---------------------
        # counters scraped from crypto.async_verify.service_stats() —
        # all zeros until the first verify touches the service, and the
        # scrape itself never instantiates it.  Monotonic *_total series
        # are CallbackCounter so the exposition advertises `counter`.
        from tendermint_tpu.crypto import async_verify as _av

        def _svc(key: str):
            return lambda: _av.service_stats()[key]

        self.verify_submitted = reg.register(CallbackCounter(
            "verify_submitted_total",
            "Signatures submitted to the async verification service",
            namespace=ns, subsystem="crypto", fn=_svc("submitted"),
        ))
        self.verify_submits = reg.register(CallbackCounter(
            "verify_submits_total",
            "Submits (groups of signatures sharing one future) queued by "
            "the async verification service; submitted / submits = rows "
            "per submit",
            namespace=ns, subsystem="crypto", fn=_svc("submits"),
        ))
        self.verify_cache_hits = reg.register(CallbackCounter(
            "verify_cache_hits_total",
            "Verifications resolved from the verified-signature cache",
            namespace=ns, subsystem="crypto", fn=_svc("cache_hits"),
        ))
        self.verify_cache_misses = reg.register(CallbackCounter(
            "verify_cache_misses_total",
            "Verification cache lookups that missed",
            namespace=ns, subsystem="crypto", fn=_svc("cache_misses"),
        ))
        self.verify_cache_size = reg.register(Gauge(
            "verify_cache_size",
            "Entries in the verified-signature cache",
            namespace=ns, subsystem="crypto", fn=_svc("cache_size"),
        ))
        self.verify_flushes = reg.register(CallbackCounter(
            "verify_flushes_total",
            "Coalesced batches flushed by the verification service",
            namespace=ns, subsystem="crypto", fn=_svc("flushes"),
        ))
        self.verify_device_batches = reg.register(CallbackCounter(
            "verify_device_batches_total",
            "Service flushes dispatched to the device path",
            namespace=ns, subsystem="crypto", fn=_svc("device_batches"),
        ))
        self.verify_device_errors = reg.register(CallbackCounter(
            "verify_device_errors_total",
            "Device flushes that failed at enqueue or readback and were "
            "resolved with host verdicts instead",
            namespace=ns, subsystem="crypto", fn=_svc("device_errors"),
        ))
        self.verify_mesh_pinned = reg.register(CallbackCounter(
            "verify_mesh_pinned_batches_total",
            "Dispatcher flushes routed to the pinned single chip "
            "(small flushes — below TM_TPU_MESH_MIN_SHARD)",
            namespace=ns, subsystem="crypto", fn=_svc("mesh_pinned_batches"),
        ))
        self.verify_mesh_sharded = reg.register(CallbackCounter(
            "verify_mesh_sharded_batches_total",
            "Dispatcher flushes sharded across the full device mesh",
            namespace=ns, subsystem="crypto", fn=_svc("mesh_sharded_batches"),
        ))
        self.verify_queue_depth = reg.register(Gauge(
            "verify_queue_depth",
            "Requests waiting in the verification service's submission queue",
            namespace=ns, subsystem="crypto", fn=_svc("queue_depth"),
        ))

        # -- device layer (utils/devmon) --------------------------------
        # compile tracking + batch-efficiency accounting + device memory.
        # Module attributes are resolved at scrape time so a devmon.reset()
        # (tests/bench) is picked up by the next scrape.
        from tendermint_tpu.utils import devmon as _dm

        self.jit_compiles = reg.register(LabeledCallbackGauge(
            "jit_compile_total",
            "JIT programs made ready, by rung/impl/source (source: "
            "aot | deserialized | persistent-cache | cold — a warmed "
            "deployment keeps source=\"cold\" at zero)",
            namespace=ns, subsystem="crypto", kind="counter",
            fn=lambda: _dm.TRACKER.compile_count_samples(),
        ))
        self.jit_compile_seconds = reg.register(LabeledCallbackGauge(
            "jit_compile_seconds_total",
            "Wall seconds spent in first-call trace+compile, by rung/impl",
            namespace=ns, subsystem="crypto", kind="counter",
            fn=lambda: _dm.TRACKER.compile_seconds_samples(),
        ))
        self.jit_recompiles = reg.register(CallbackCounter(
            "jit_recompile_total",
            "Unexpected recompiles (same jit cache key compiled twice)",
            namespace=ns, subsystem="crypto",
            fn=lambda: _dm.TRACKER.recompiles,
        ))
        reg.register(_dm.VERIFY_BATCH_OCCUPANCY)
        self.verify_padding_rows = reg.register(CallbackCounter(
            "verify_padding_rows_total",
            "Wasted (padding) rows shipped to the device by bucket rounding",
            namespace=ns, subsystem="crypto",
            fn=lambda: _dm.STATS.padding_rows,
        ))
        self.verify_transfer_bytes = reg.register(CallbackCounter(
            "verify_transfer_bytes_total",
            "Estimated host-to-device bytes shipped (padded row widths)",
            namespace=ns, subsystem="crypto",
            fn=lambda: _dm.STATS.transfer_bytes,
        ))
        self.verify_rung_flushes = reg.register(LabeledCallbackGauge(
            "verify_rung_flushes_total",
            "Device flushes by program kind and bucket rung",
            namespace=ns, subsystem="crypto", kind="counter",
            fn=lambda: _dm.STATS.rung_flush_samples(),
        ))
        # per-device attribution (crypto/mesh_dispatch): which chips of
        # the mesh each flush actually landed on — a pinned flush is one
        # device's rows, a sharded flush is rung/n_dev rows per chip
        self.verify_device_flushes = reg.register(LabeledCallbackGauge(
            "verify_device_flushes_total",
            "Device flushes by mesh device (pinned: device 0; sharded: "
            "every mesh device)",
            namespace=ns, subsystem="crypto", kind="counter",
            fn=lambda: _dm.STATS.device_flush_samples(),
        ))
        self.verify_device_rows = reg.register(LabeledCallbackGauge(
            "verify_device_rows_total",
            "Padded rows placed per mesh device (each device's shard of "
            "every flush it participated in)",
            namespace=ns, subsystem="crypto", kind="counter",
            fn=lambda: _dm.STATS.device_rows_samples(),
        ))
        self.device_memory_bytes = reg.register(LabeledCallbackGauge(
            "device_memory_bytes",
            "Per-device memory from jax memory_stats()/live buffers "
            "(absent until a backend is initialized)",
            namespace=ns, subsystem="crypto",
            fn=_dm.memory_gauge_samples,
        ))

        # -- per-program HLO costs (utils/costmodel) --------------------
        # harvested from compiled executables (AOT warm) or lowered
        # programs (`tendermint-tpu profile`); absent until a harvest
        # happens — a scrape never triggers one.
        from tendermint_tpu.utils import costmodel as _cm

        self.verify_rung_flops = reg.register(LabeledCallbackGauge(
            "verify_rung_flops",
            "HLO cost-analysis FLOPs for one execution of the compiled "
            "program, by kind/rung/impl",
            namespace=ns, subsystem="crypto",
            fn=lambda: _cm.COSTS.flops_samples(),
        ))
        self.verify_rung_bytes_accessed = reg.register(LabeledCallbackGauge(
            "verify_rung_bytes_accessed",
            "HLO cost-analysis bytes accessed (working-set traffic, not "
            "host transfer) per execution, by kind/rung/impl",
            namespace=ns, subsystem="crypto",
            fn=lambda: _cm.COSTS.bytes_samples(),
        ))
        self.verify_rung_peak_memory = reg.register(LabeledCallbackGauge(
            "verify_rung_peak_memory_bytes",
            "Compiled-program device footprint (arguments + outputs + "
            "temps + code), by kind/rung/impl — compiled harvests only",
            namespace=ns, subsystem="crypto",
            fn=lambda: _cm.COSTS.peak_memory_samples(),
        ))
        self.verify_device_peak_flops = reg.register(Gauge(
            "verify_device_peak_flops_per_s",
            "Peak device FLOPs/s used as the roofline denominator "
            "(TM_TPU_PEAK_FLOPS or device-kind table; omitted when "
            "unknown)",
            namespace=ns, subsystem="crypto",
            fn=lambda: float(_cm.peak_flops_per_s()),
        ))

        # -- health watchdog (utils/health.py) --------------------------
        # per-detector level + transition counts, read from the node's
        # monitor at scrape time; empty (TYPE lines only) when the
        # monitor is disabled (TM_TPU_HEALTH=0 → the NOP singleton).
        self.health_status = reg.register(LabeledCallbackGauge(
            "health_status",
            "Per-detector watchdog level (0 ok / 1 warn / 2 critical)",
            namespace=ns,
            fn=lambda: node.health.status_samples(),
        ))
        self.health_transitions = reg.register(LabeledCallbackGauge(
            "health_transitions_total",
            "Watchdog detector level transitions since start",
            namespace=ns, kind="counter",
            fn=lambda: node.health.transition_samples(),
        ))
        self.health_slo_burn = reg.register(LabeledCallbackGauge(
            "health_slo_burn_total",
            "slo_burn records pushed into this node's monitor by the "
            "fleet layer (fleet/slo.py burn-rate verdicts) — fleet-scope "
            "pressure surfaced next to the local detectors",
            namespace=ns, kind="counter",
            fn=lambda: node.health.slo_burn_samples(),
        ))

        # -- continuous profiler (utils/profiler.py) --------------------
        # statistical sampler attribution + self-cost, read from the
        # node's sampler at scrape time; empty (TYPE lines only) when
        # disabled (TM_TPU_PROF=0 → the NOP singleton) — the scrape
        # never instantiates a profiler.
        self.prof_samples = reg.register(LabeledCallbackGauge(
            "prof_samples_total",
            "Statistical profiler thread-samples by subsystem bucket "
            "(consensus | verify-service | gateway | rpc | health | ...)",
            namespace=ns, kind="counter",
            fn=lambda: node.prof.subsystem_samples(),
        ))
        self.prof_overhead = reg.register(LabeledCallbackGauge(
            "prof_overhead_seconds_total",
            "Cumulative wall seconds the sampler spent folding stacks "
            "— the profiler's own cost, so its overhead budget is "
            "itself observable",
            namespace=ns, kind="counter",
            fn=lambda: node.prof.overhead_samples(),
        ))

        # -- metric history (utils/history.py) --------------------------
        # the flight-data recorder's self-accounting, read from the
        # node's recorder at scrape time; empty (TYPE lines only) when
        # disabled (TM_TPU_HISTORY=0 → the NOP singleton).
        self.history_samples = reg.register(LabeledCallbackGauge(
            "history_samples_total",
            "Metric-history samples recorded since start "
            "(one per TM_TPU_HISTORY_INTERVAL_S scrape of the registry)",
            namespace=ns, kind="counter",
            fn=lambda: node.history.sample_counts(),
        ))
        self.history_bytes = reg.register(LabeledCallbackGauge(
            "history_bytes_total",
            "Bytes appended to on-disk history segments — the "
            "recorder's own footprint, so retention math is observable",
            namespace=ns, kind="counter",
            fn=lambda: node.history.byte_counts(),
        ))

        # -- remediation controller (utils/remediate.py) ----------------
        # actions executed per (action, triggering detector), and the
        # currently-active state per action (shed = admission level,
        # evict = quarantined peers, rewarm = rate-limit window open);
        # empty (TYPE lines only) when TM_TPU_REMEDIATE=0 (NOP).
        self.remediation_actions = reg.register(LabeledCallbackGauge(
            "remediation_actions_total",
            "Remediation actions executed, by action and trigger "
            "(shed | rewarm | retune | evict | pardon)",
            namespace=ns, kind="counter",
            fn=lambda: node.remediate.action_samples(),
        ))
        self.remediation_active = reg.register(LabeledCallbackGauge(
            "remediation_active",
            "Currently-active remediation state per action (shed = "
            "admission level 0-2, evict = quarantined peers, rewarm = "
            "1 while the rewarm rate-limit window is open)",
            namespace=ns,
            fn=lambda: node.remediate.active_samples(),
        ))

        # -- light-client gateway (tendermint_tpu/gateway) --------------
        # read-path serving counters scraped from the module-level
        # gateway_stats() accessor: typed zeros until a gateway is
        # active (TM_TPU_GATEWAY=1 or the standalone front end), and the
        # scrape itself never builds one — the PR 2 NOP idiom.
        from tendermint_tpu.gateway import gateway_stats as _gw_stats

        def _gws(key: str):
            return lambda: _gw_stats()[key]

        self.gateway_clients = reg.register(Gauge(
            "clients", "Light clients currently syncing through the gateway",
            namespace=ns, subsystem="gateway", fn=_gws("clients"),
        ))
        self.gateway_verify_jobs = reg.register(CallbackCounter(
            "verify_jobs_total",
            "Commit-verify jobs submitted to the gateway coalescer",
            namespace=ns, subsystem="gateway", fn=_gws("verify_jobs"),
        ))
        self.gateway_verify_coalesced = reg.register(CallbackCounter(
            "verify_coalesced_total",
            "Verify jobs that joined another client's in-flight twin "
            "(cross-client sharing)",
            namespace=ns, subsystem="gateway", fn=_gws("verify_coalesced"),
        ))
        self.gateway_verify_flushes = reg.register(CallbackCounter(
            "verify_flushes_total",
            "Coalesced batch_verify_commits flushes issued by the gateway",
            namespace=ns, subsystem="gateway", fn=_gws("verify_flushes"),
        ))
        self.gateway_shed = reg.register(CallbackCounter(
            "shed_total",
            "Read-path verify jobs shed under verify-queue saturation",
            namespace=ns, subsystem="gateway", fn=_gws("shed"),
        ))
        self.gateway_cache_hits = reg.register(CallbackCounter(
            "cache_hits_total",
            "Height-keyed response cache hits",
            namespace=ns, subsystem="gateway", fn=_gws("cache_hits"),
        ))
        self.gateway_cache_misses = reg.register(CallbackCounter(
            "cache_misses_total",
            "Height-keyed response cache misses",
            namespace=ns, subsystem="gateway", fn=_gws("cache_misses"),
        ))
        self.gateway_cache_invalidations = reg.register(CallbackCounter(
            "cache_invalidations_total",
            "Latest-tagged cache entries dropped on height advance",
            namespace=ns, subsystem="gateway",
            fn=_gws("cache_invalidations"),
        ))
        self.gateway_cache_entries = reg.register(Gauge(
            "cache_entries", "Entries in the response cache",
            namespace=ns, subsystem="gateway", fn=_gws("cache_entries"),
        ))
        self.gateway_cache_bytes = reg.register(Gauge(
            "cache_bytes", "Bytes held by the response cache",
            namespace=ns, subsystem="gateway", fn=_gws("cache_bytes"),
        ))

        # -- latency histograms fed at their source ---------------------
        # Process-wide module singletons (the verify service, the FSM,
        # blocksync and RPC observe them where the timing happens); this
        # registry only EXPOSES them.  They carry the "tendermint"
        # namespace baked in at definition, matching the default ns here.
        from tendermint_tpu.blocksync.pool import (
            REQUEST_DURATION_SECONDS as _bsync_hist,
        )
        from tendermint_tpu.blocksync.reactor import (
            WINDOW_COUNTERS as _bsync_window_counters,
        )
        from tendermint_tpu.consensus.state import STEP_DURATION_SECONDS
        from tendermint_tpu.crypto.batch import ROWS_ADDED_TOTAL as _rows_added
        from tendermint_tpu.gateway.coalescer import GATEWAY_SERIES as _gw_series
        from tendermint_tpu.light.client import LIGHT_COUNTERS as _light_counters
        from tendermint_tpu.rpc.server import (
            REQUEST_DURATION_SECONDS as _rpc_hist,
        )

        self.step_duration = reg.register(STEP_DURATION_SECONDS)
        self.blocksync_request_duration = reg.register(_bsync_hist)
        for series in _bsync_window_counters + _light_counters + _gw_series:
            reg.register(series)
        self.rpc_request_duration = reg.register(_rpc_hist)
        for hist in _av.PIPELINE_HISTOGRAMS:
            reg.register(hist)
        reg.register(_rows_added)

        # -- transaction lifecycle (utils/txlife.py) --------------------
        # the user-facing latency signal: time-to-finality (rpc ingress →
        # applied), mempool residency (admission → commit) and quorum
        # wait (own vote → +2/3), observed at their source milestones —
        # per tx at commit and per quorum formation, never per signature
        from tendermint_tpu.utils import txlife as _txlife

        for hist in _txlife.LIFECYCLE_HISTOGRAMS:
            reg.register(hist)

        # -- state ------------------------------------------------------
        self.state = StateMetrics(reg, ns)

        self._server = MetricsServer(self.registry)
        self._pump_task: asyncio.Task | None = None
        self._last_block_time_ns: int | None = None
        self.addr: tuple[str, int] | None = None

    # -- lifecycle -------------------------------------------------------
    async def start(self, host: str, port: int) -> tuple[str, int]:
        self.addr = await self._server.start(host, port)
        sub = self.node.event_bus.subscribe(
            "metrics", tmevents.query_for_event(tmevents.EventNewBlock),
            capacity=64,
        )

        async def pump():
            try:
                while True:
                    msg = await sub.next()
                    block = msg.data.block
                    self.num_txs.set(len(block.data.txs))
                    self.total_txs.inc(len(block.data.txs))
                    self.block_size_bytes.set(len(block.encode()))
                    if self._last_block_time_ns is not None:
                        dt = (block.header.time_ns - self._last_block_time_ns) / 1e9
                        if dt >= 0:
                            self.block_interval_seconds.observe(dt)
                    self._last_block_time_ns = block.header.time_ns
            except (SubscriptionCancelledError, asyncio.CancelledError):
                return

        self._pump_task = asyncio.get_running_loop().create_task(pump())
        return self.addr

    async def stop(self) -> None:
        await self._server.stop()
        if self._pump_task is not None:
            self._pump_task.cancel()
            try:
                await self._pump_task
            except (asyncio.CancelledError, Exception):
                pass
            self._pump_task = None
        try:
            self.node.event_bus.unsubscribe_all("metrics")
        except KeyError:
            pass
