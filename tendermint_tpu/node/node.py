"""Node assembly: wire every subsystem and run the lifecycle.

Parity: reference node/node.go (NewNode :650, OnStart :904, OnStop,
LoadStateFromDBOrGenesisDocProvider with genesis-hash pinning,
createMempoolAndMempoolReactor / createEvidenceReactor /
createConsensusReactor / createBlockchainReactor wiring order,
fast-sync → consensus switch via SwitchToConsensus).

TPU-rebuild shape: one asyncio event loop hosts every reactor; the
crypto data plane (batched commit verification) rides the configured
BatchVerifier backend (device when available).
"""

from __future__ import annotations

import asyncio
import json
import os

from tendermint_tpu.abci import AppConns
from tendermint_tpu.abci.kvstore import CounterApplication, KVStoreApplication
from tendermint_tpu.blocksync.reactor import BlocksyncReactor
from tendermint_tpu.config import Config
from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.evidence import EvidencePool
from tendermint_tpu.evidence.reactor import EvidenceReactor
from tendermint_tpu.mempool import Mempool
from tendermint_tpu.mempool.reactor import MempoolReactor
from tendermint_tpu.p2p import MemoryNetwork, Router
from tendermint_tpu.p2p.tcp import TCPTransport
from tendermint_tpu.privval import load_or_gen_file_pv
from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
from tendermint_tpu.state.txindex import IndexerService, KVTxIndexer, NullTxIndexer
from tendermint_tpu.statesync.reactor import StateSyncReactor
from tendermint_tpu.store import BlockStore, open_db
from tendermint_tpu.types import GenesisDoc
from tendermint_tpu.types.events import EventBus
from tendermint_tpu.utils.log import Logger, nop_logger

from .node_key import load_or_gen_node_key


def load_state_from_db_or_genesis(state_store: StateStore, genesis: GenesisDoc):
    """Genesis-hash pinning (reference node.go
    LoadStateFromDBOrGenesisDocProvider): a node must never silently
    switch chains because someone swapped genesis.json."""
    stored_hash = state_store.genesis_doc_hash()
    doc_hash = genesis.doc_hash()
    if stored_hash is not None and stored_hash != doc_hash:
        raise RuntimeError(
            "genesis doc hash does not match the one this node was initialized "
            f"with (stored {stored_hash.hex()}, file {doc_hash.hex()})"
        )
    state = state_store.load()
    if state is None:
        genesis.validate_and_complete()
        state = make_genesis_state(genesis)
        state_store.save(state)
    if stored_hash is None:
        state_store.save_genesis_doc_hash(doc_hash)
    return state


def _parse_laddr(laddr: str, default_port: int = 26657) -> tuple[str, int]:
    """tcp://host:port → (host, port); port 0 picks an ephemeral port.
    Handles bracketed IPv6 ([::1]:26657) and a missing port (→ default:
    26657 for RPC, 26656 for p2p)."""
    body = laddr.split("://", 1)[-1]
    if body.startswith("["):  # [v6]:port
        host, _, rest = body[1:].partition("]")
        port = rest.lstrip(":")
    else:
        host, _, port = body.rpartition(":")
        if not _:  # no colon at all: bare host
            host, port = body, ""
    return host or "127.0.0.1", int(port) if port else default_port


def _builtin_app(name: str, snapshot_interval: int = 0):
    """reference proxy/client.go DefaultClientCreator local apps."""
    if name in ("kvstore", "persistent_kvstore"):
        return KVStoreApplication(snapshot_interval=snapshot_interval)
    if name == "counter":
        return CounterApplication()
    if name == "counter_serial":
        return CounterApplication(serial=True)
    if name == "noop":
        from tendermint_tpu.abci.types import BaseApplication

        return BaseApplication()
    raise ValueError(f"unknown builtin app {name!r}")


class Node:
    """A full node: stores, app conns, event bus, indexer, reactors,
    consensus — started/stopped as one unit."""

    def __init__(
        self,
        config: Config,
        genesis: GenesisDoc | None = None,
        app=None,
        transport=None,
        state_provider=None,
        logger: Logger | None = None,
    ):
        self.config = config
        self.logger = logger or nop_logger()
        config.ensure_dirs()

        # -- genesis + stores ------------------------------------------
        if genesis is None:
            with open(config.genesis_file) as fh:
                genesis = GenesisDoc.from_json(fh.read())
        self.genesis = genesis

        backend = config.base.db_backend
        self.block_db = self._open_db(backend, "blockstore")
        self.state_db = self._open_db(backend, "state")
        self.evidence_db = self._open_db(backend, "evidence")
        self.tx_index_db = self._open_db(backend, "tx_index")
        self.block_store = BlockStore(self.block_db)
        self.state_store = StateStore(self.state_db)
        state = load_state_from_db_or_genesis(self.state_store, genesis)

        # -- app + handshake -------------------------------------------
        if app is None and config.base.abci == "socket":
            # external app over the ABCI socket protocol (reference
            # proxy/client.go DefaultClientCreator "socket" branch)
            from tendermint_tpu.abci.socket import SocketAppConns

            self.app = None
            self.app_conns = SocketAppConns(config.base.proxy_app)
        elif app is None and config.base.abci == "grpc":
            from tendermint_tpu.abci.grpc_app import GRPCAppConns

            self.app = None
            self.app_conns = GRPCAppConns(config.base.proxy_app)
        else:
            if app is None:
                app = _builtin_app(config.base.proxy_app,
                                   snapshot_interval=config.base.snapshot_interval)
            self.app = app
            self.app_conns = AppConns(app)

        # -- event bus + indexer ---------------------------------------
        self.event_bus = EventBus()
        if config.tx_index.indexer == "kv":
            self.tx_indexer = KVTxIndexer(self.tx_index_db)
        else:
            self.tx_indexer = NullTxIndexer()
        self.indexer_service = IndexerService(self.tx_indexer, self.event_bus, self.logger)

        # -- handshake (replays blocks into the app) -------------------
        self.handshaker = Handshaker(
            self.state_store, state, self.block_store, genesis,
            event_bus=None, logger=self.logger,
        )
        state = self.handshaker.handshake(self.app_conns)
        self.initial_state = state

        # -- validator key ---------------------------------------------
        self.priv_validator = None
        self._pv_remote = ""  # "" (local file) | "socket" | "grpc"
        if not config.base.priv_validator_laddr:
            self.priv_validator = load_or_gen_file_pv(
                config.priv_validator_key_file, config.priv_validator_state_file
            )
        elif config.base.priv_validator_laddr.startswith("grpc://"):
            # gRPC signer: the SIGNER serves, the node dials
            # (reference privval/grpc/client.go)
            from tendermint_tpu.privval.grpc_pv import GRPCSignerClient

            self.priv_validator = GRPCSignerClient(
                config.base.priv_validator_laddr, logger=self.logger
            )
            self._pv_remote = "grpc"
        else:
            # socket signer: the node listens, the signer process dials in
            # (reference node/node.go:695-710 + privval/signer_client.go)
            from tendermint_tpu.privval.socket_pv import SignerClient

            host, port = _parse_laddr(config.base.priv_validator_laddr)
            self.priv_validator = SignerClient(host, port, logger=self.logger)
            self.priv_validator.start()
            self._pv_remote = "socket"

        # -- p2p ---------------------------------------------------------
        self.node_key = load_or_gen_node_key(config.node_key_file)
        if transport is None:
            if config.p2p.transport == "tcp" and config.p2p.laddr:
                host, port = _parse_laddr(config.p2p.laddr, default_port=26656)
                transport = TCPTransport(
                    self.node_key, network=genesis.chain_id,
                    host=host, port=port, moniker=config.base.moniker,
                    logger=self.logger,
                    max_incoming_connections=config.p2p.max_num_inbound_peers,
                    send_rate=config.p2p.send_rate,
                    recv_rate=config.p2p.recv_rate,
                )
            else:
                # private in-memory net (single-node / in-proc tests)
                transport = MemoryNetwork().create_transport(self.node_key.node_id)
        self.transport = transport
        self.router = Router(
            self.node_key.node_id,
            transport,
            logger=self.logger,
            ping_interval=config.p2p.ping_interval_s,
            pong_timeout=config.p2p.pong_timeout_s,
        )
        self.p2p_addr: tuple[str, int] | None = None
        self._dialer_task: asyncio.Task | None = None
        # persistent-peer dial state (reference switch.go reconnectToPeer),
        # mutated at runtime by add_persistent_peer.  Backoff policy:
        # capped exponential with seeded jitter and flap detection
        # (p2p/backoff.py) — a peer that accepts then dies keeps climbing
        # the ladder instead of being redialed at the floor forever.
        from tendermint_tpu.p2p.backoff import DialBackoff

        self._persistent_targets: dict[str, str] = {}
        self._dial_backoff = DialBackoff()
        self._persistent_next_try: dict[str, float] = {}

        # -- PEX / address book (reference p2p/pex; node/node.go:820-856)
        self.pex_reactor = None
        if config.p2p.pex and isinstance(transport, TCPTransport):
            from tendermint_tpu.p2p.pex import AddrBook, PexReactor

            book = AddrBook(config.addr_book_file,
                            strict=config.p2p.addr_book_strict,
                            logger=self.logger)
            for addr in config.p2p.seeds.split(","):
                addr = addr.strip()
                if addr:
                    book.add_address(addr)
                    transport.add_peer_address(addr)
            self.pex_reactor = PexReactor(
                self.router, book, transport,
                max_outbound=config.p2p.max_num_outbound_peers,
                seed_mode=config.p2p.seed_mode,
                private_ids={p.strip().lower() for p in
                             config.p2p.private_peer_ids.split(",") if p.strip()},
                logger=self.logger,
            )

        # -- mempool / evidence / executor ------------------------------
        self.mempool = Mempool(config.mempool, self.app_conns.mempool())
        self.evidence_pool = EvidencePool(
            self.evidence_db, self.state_store, self.block_store, logger=self.logger
        )
        # -- metrics (reference node/node.go:112-126,925-928) -----------
        self.metrics = None
        if config.instrumentation.prometheus:
            from tendermint_tpu.node.metrics import NodeMetrics

            self.metrics = NodeMetrics(self, namespace=config.instrumentation.namespace)

        self.executor = BlockExecutor(
            self.state_store,
            self.app_conns.consensus(),
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            event_bus=self.event_bus,
            metrics=self.metrics.state if self.metrics else None,
        )

        # -- consensus --------------------------------------------------
        self.wal = WAL(config.wal_file)
        cs_cls, cs_kw = ConsensusState, {}
        mis_env = os.environ.get("TM_TPU_MISBEHAVIORS")
        if mis_env:
            # byzantine e2e node (reference test/maverick; selected per
            # height from the e2e manifest)
            from tendermint_tpu.e2e.maverick import MaverickConsensusState

            cs_cls = MaverickConsensusState
            cs_kw = {
                "misbehaviors": {int(k): v for k, v in json.loads(mis_env).items()},
                "raw_key": getattr(self.priv_validator, "priv_key", None),
            }
        self.consensus = cs_cls(
            config.consensus,
            state,
            self.executor,
            self.block_store,
            wal=self.wal,
            priv_validator=self.priv_validator,
            evidence_pool=self.evidence_pool,
            logger=self.logger,
            **cs_kw,
        )
        self.consensus.event_bus = self.event_bus
        # structured event journal (TM_TPU_JOURNAL; consensus/eventlog.py):
        # NOP unless the env asks for one, so the FSM pays one branch per
        # event site when off
        from tendermint_tpu.consensus import eventlog as _eventlog

        self.consensus.journal = _eventlog.from_env(
            node=config.base.moniker or self.node_key.node_id[:8],
            data_dir=config.db_dir,
        )
        # tx lifecycle tracer (TM_TPU_TXLIFE, default on; utils/txlife.py):
        # one store per node, shared by the RPC ingress hooks, the
        # mempool admission/gossip hooks and the consensus commit/apply
        # hooks; tx_* journal lines ride the consensus journal above
        from tendermint_tpu.utils import txlife as _txlife

        self.txlife = _txlife.from_env(
            journal=self.consensus.journal,
            node=config.base.moniker or self.node_key.node_id[:8],
        )
        self.consensus.lifecycle = self.txlife
        self.mempool.lifecycle = self.txlife
        self.consensus_reactor = ConsensusReactor(
            self.consensus, self.router, self.block_store, logger=self.logger
        )
        if mis_env:
            from tendermint_tpu.consensus.messages import VoteMessage
            from tendermint_tpu.p2p.types import Envelope

            self.consensus.broadcast_vote = lambda v: self.consensus_reactor.vote_ch.try_send(
                Envelope(message=VoteMessage(v), broadcast=True)
            )
        def _peer_consensus_height(node_id: str):
            ps = self.consensus_reactor.peers.get(node_id)
            return ps.prs.height if ps is not None else None

        self.mempool_reactor = MempoolReactor(
            self.mempool, self.router, logger=self.logger,
            broadcast=config.mempool.broadcast,
            peer_height=_peer_consensus_height,
        )
        self.evidence_reactor = EvidenceReactor(
            self.evidence_pool, self.router, logger=self.logger
        )

        # -- sync reactors ---------------------------------------------
        self._caught_up = asyncio.Event()
        self.blocksync_reactor = BlocksyncReactor(
            state,
            self.executor,
            self.block_store,
            self.router,
            on_caught_up=self._on_caught_up,
            logger=self.logger,
        )
        if state_provider is None and config.statesync.enable:
            # config-driven: light-client state provider over the
            # configured RPC servers (reference statesync/stateprovider.go:47
            # via node/node.go startStateSync)
            from tendermint_tpu.light.client import TrustOptions
            from tendermint_tpu.light.http_provider import HTTPProvider
            from tendermint_tpu.statesync import LightClientStateProvider

            providers = [HTTPProvider(genesis.chain_id, url)
                         for url in config.statesync.rpc_servers]
            state_provider = LightClientStateProvider(
                genesis.chain_id, genesis, providers,
                TrustOptions(
                    period_ns=config.statesync.trust_period_s * 10**9,
                    height=config.statesync.trust_height,
                    hash=bytes.fromhex(config.statesync.trust_hash),
                ),
            )
        self.statesync_reactor = StateSyncReactor(
            self.app_conns.snapshot(), self.router, state_provider, logger=self.logger
        )

        # -- health watchdog (TM_TPU_HEALTH, default on; utils/health.py)
        # samples consensus progress, verify-service depth, peer churn,
        # process vitals and devmon compile counters on a daemon-thread
        # cadence; flight-recorder bundles land under <home>/health/.
        # One branch per call site when off (the NOP singleton).
        from tendermint_tpu.utils import health as _health

        def _consensus_probe():
            return {"height": self.block_store.height(),
                    "round": self.consensus.rs.round}

        def _peer_probe():
            r = self.router
            depths = [d for _pid, _cid, d in r.send_queue_depths()]
            return {"peers": len(r.peers),
                    "peer_disconnects": r.peers_disconnected,
                    "send_queue_max": max(depths, default=0)}

        self.health = _health.from_env(
            node=config.base.moniker or self.node_key.node_id[:8],
            root=config.home,
            probes={"consensus": _consensus_probe, "peers": _peer_probe},
            journal=self.consensus.journal,
            journal_path=getattr(self.consensus.journal, "path", ""),
            expected_block_s=max(1.0,
                                 config.consensus.timeout_commit_ms / 1e3),
        )

        # -- remediation controller (TM_TPU_REMEDIATE, default on;
        # utils/remediate.py): detector transitions from the watchdog
        # drive admission control (mempool shedding), compile-storm
        # re-warm/retune, and peer eviction/quarantine.  The dialer
        # consults `quarantined()` before every redial; eviction severs
        # through the router from the watchdog's thread via the loop.
        from tendermint_tpu.utils import remediate as _remediate

        self._loop: asyncio.AbstractEventLoop | None = None

        def _evict_peer(pid: str) -> None:
            loop = self._loop
            if loop is not None and loop.is_running():
                asyncio.run_coroutine_threadsafe(
                    self.router.disconnect(pid), loop)

        self.remediate = _remediate.from_env(
            node=config.base.moniker or self.node_key.node_id[:8],
            mempool=self.mempool,
            backoff=self._dial_backoff,
            evict_peer=_evict_peer,
            journal=self.consensus.journal,
        )
        if self.health.enabled and self.remediate.enabled:
            self.health.remediate = self.remediate

        # -- continuous profiler (TM_TPU_PROF, default on;
        # utils/profiler.py): a ~19 Hz statistical sampler attributing
        # CPU time to subsystem buckets; serves /debug/pprof/profile,
        # feeds tendermint_prof_* metrics, and — wired as the health
        # monitor's sink — arms rate-limited trigger captures on
        # critical escalations / slo_burn and rides the flight-recorder
        # bundle (profile.folded).  One branch per call site when off.
        from tendermint_tpu.utils import profiler as _profiler

        self.prof = _profiler.from_env(
            node=config.base.moniker or self.node_key.node_id[:8])
        if self.health.enabled and self.prof.enabled:
            self.health.prof = self.prof

        # -- metric history (TM_TPU_HISTORY, default on;
        # utils/history.py): samples this node's own metrics registry
        # on a cadence into delta-compressed segments under
        # <home>/history/ — serves /debug/pprof/history and the
        # `tendermint-tpu history` CLI, backfills the fleet SLO
        # engine's burn windows, rides the flight-recorder bundle
        # (history.jsonl) and feeds the metric_drift detector.  No
        # registry (prometheus off) = nothing to record.
        from tendermint_tpu.utils import history as _history

        self.history = _history.from_env(
            node=config.base.moniker or self.node_key.node_id[:8],
            root=config.home,
            source=(self.metrics.registry.expose
                    if self.metrics is not None else None),
        )
        if self.health.enabled and self.history.enabled:
            self.health.history = self.history
            self.health.probes["history"] = self.history.drift_probe

        # -- RPC --------------------------------------------------------
        from tendermint_tpu.rpc.core import Environment
        from tendermint_tpu.rpc.server import RPCServer

        # -- light-client gateway (TM_TPU_GATEWAY=1; tendermint_tpu/
        # gateway): the read-path serving mode — height-keyed response
        # cache on the hammered RPC routes, cross-client verify
        # coalescing for in-process light clients, and shed-first
        # degradation driven by the remediation controller's admission
        # level.  Default OFF: every code path below stays bit-identical
        # (no gateway object, the stock route table, no status block).
        self.gateway = None
        if os.environ.get("TM_TPU_GATEWAY", "0") == "1":
            from tendermint_tpu import gateway as _gwmod
            from tendermint_tpu.gateway.service import Gateway as _Gateway

            self.gateway = _Gateway.from_env(
                shed_fn=(self.remediate.shed_level
                         if self.remediate.enabled else None),
                remediate=self.remediate,
                latest_height_fn=self.block_store.height,
            )
            _gwmod.set_active(self.gateway)

        self.rpc_env = Environment(
            config=config,
            genesis=genesis,
            block_store=self.block_store,
            state_store=self.state_store,
            consensus=self.consensus,
            consensus_reactor=self.consensus_reactor,
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            tx_indexer=self.tx_indexer,
            event_bus=self.event_bus,
            app_query_conn=self.app_conns.query(),
            router=self.router,
            transport=self.transport,
            add_persistent_peer=self.add_persistent_peer,
            add_private_peer_id=self.add_private_peer_id,
            node_id=self.node_key.node_id,
            moniker=config.base.moniker,
            txlife=self.txlife,
            health=self.health,
            remediate=self.remediate,
            gateway=self.gateway,
            prof=self.prof,
        )
        self.grpc_server = None
        self.pprof_server = None
        self.pprof_addr = None
        gw_routes = None
        if self.gateway is not None:
            from tendermint_tpu.gateway.routes import wrap_cached_routes
            from tendermint_tpu.rpc import core as _rpc_core

            routes = dict(_rpc_core.ROUTES)
            if getattr(config.rpc, "unsafe", False):
                routes.update(_rpc_core.UNSAFE_ROUTES)
            gw_routes = wrap_cached_routes(routes, self.gateway)
        self.rpc_server = RPCServer(
            self.rpc_env,
            logger=self.logger,
            max_body_bytes=config.rpc.max_body_bytes,
            max_open_connections=config.rpc.max_open_connections,
            cors_allowed_origins=config.rpc.cors_allowed_origins,
            routes=gw_routes,
        )
        self.rpc_addr: tuple[str, int] | None = None

        self._consensus_running = False
        self._started = False
        self._switch_task: asyncio.Task | None = None

    def _open_db(self, backend: str, name: str):
        if backend == "memdb":
            return open_db("memdb")
        path = os.path.join(self.config.db_dir, f"{name}.db")
        return open_db(backend, path)

    # -- lifecycle -------------------------------------------------------
    async def start(self) -> None:
        """reference node.go OnStart :904-992 ordering."""
        if self._started:
            raise RuntimeError("node already started")
        self._started = True
        self._loop = asyncio.get_running_loop()
        # prime the batch verifier (native host-prep build/load) off the
        # event loop, and log its dispatch configuration.  The RTT
        # measurement itself is LAZY (first ≥64-sig batch) — node start
        # must never initiate device/backend init: the first device
        # contact pays backend init and compiles (seconds to minutes)
        # and belongs to a worker thread, never to start-up.  Nothing
        # has been measured yet at this point: crypto/batch logs the
        # measured threshold, the platform that became ready, or the
        # exception that prevented it, when it happens.
        from tendermint_tpu.crypto import batch as _batch

        bv = await asyncio.to_thread(_batch.new_batch_verifier)
        if isinstance(bv, _batch.JAXBatchVerifier):
            self.logger.info(
                "batch verifier ready",
                backend="jax",
                cpu_threshold=(bv.cpu_threshold if bv.cpu_threshold is not None
                               else "measure-at-first-64plus-batch"),
                **_batch.threshold_diagnostics(),
            )
        else:
            self.logger.info("batch verifier ready", backend="cpu")
        # the async verification service every verify surface submits to
        # (crypto.async_verify): constructed here so its native-lib load
        # also stays off the event loop; its worker thread spins up
        # lazily at the first submission
        from tendermint_tpu.crypto import async_verify as _av

        if _av.service_enabled():
            svc = await asyncio.to_thread(_av.get_service)
            self.logger.info(
                "async verify service ready",
                linger_ms=svc.linger_s * 1e3,
                cache_entries=svc.cache.maxsize,
            )
        # shape-plan AOT warm (ISSUE 7): when the operator ran
        # `tendermint-tpu warm`, load/compile its executables on a
        # daemon thread now — a cold node reaches full verify
        # throughput in seconds instead of paying first-call compiles
        # per bucket.  Device contact stays OFF the event loop and off
        # this thread: start_background_warm only spawns the worker (a
        # slow or failing device stalls the worker alone), and it is a
        # strict no-op without a saved plan or with TM_TPU_AOT=0.
        from tendermint_tpu.ops import shape_plan as _sp

        if await asyncio.to_thread(_sp.start_background_warm, "node-start"):
            self.logger.info("shape-plan AOT warm started",
                             plan=_sp.plan_path())
        if self._pv_remote == "socket":
            # block until the remote signer dials in and the pubkey primes
            await asyncio.to_thread(self.priv_validator.wait_for_signer, 30.0)
        elif self._pv_remote == "grpc":
            await asyncio.to_thread(self.priv_validator.connect, 30.0)
        await self.indexer_service.start()
        if self.config.rpc.laddr:
            host, port = _parse_laddr(self.config.rpc.laddr)
            self.rpc_addr = await self.rpc_server.start(host, port)
        if self.config.rpc.grpc_laddr:
            from tendermint_tpu.rpc.grpc_api import GRPCBroadcastServer

            self.grpc_server = GRPCBroadcastServer(self.rpc_env, logger=self.logger)
            await self.grpc_server.start(self.config.rpc.grpc_laddr)
        if self.metrics is not None:
            host, port = _parse_laddr(self.config.instrumentation.prometheus_listen_addr,
                                      default_port=26660)
            addr = await self.metrics.start(host, port)
            self.logger.info("prometheus metrics listening", addr=f"{addr[0]}:{addr[1]}")
        if self.config.rpc.pprof_laddr:
            from tendermint_tpu.node.pprof import PprofServer

            self.pprof_server = PprofServer(logger=self.logger,
                                            health=self.health,
                                            prof=self.prof,
                                            history=self.history)
            host, port = _parse_laddr(self.config.rpc.pprof_laddr, default_port=6060)
            self.pprof_addr = await self.pprof_server.start(host, port)
        if isinstance(self.transport, TCPTransport):
            # advertise the channels the reactors registered (compat check)
            self.transport.channels = bytes(self.router.channels.keys())
            self.p2p_addr = await self.transport.listen()
        await self.router.start()
        if self.pex_reactor is not None:
            await self.pex_reactor.start()
        if isinstance(self.transport, TCPTransport):
            for addr in self.config.p2p.persistent_peers.split(","):
                addr = addr.strip()
                if not addr:
                    continue
                try:
                    self.add_persistent_peer(addr)
                except ValueError as e:
                    self.logger.error("bad persistent peer address",
                                      addr=addr, err=str(e))
            # run when there's work now (configured persistent peers) or
            # when work can arrive later (unsafe dial_peers RPC enabled)
            if self._persistent_targets or self.config.rpc.unsafe:
                self._dialer_task = asyncio.get_running_loop().create_task(
                    self._dial_persistent_peers()
                )
        await self.statesync_reactor.start()

        if self.config.statesync.enable and self.statesync_reactor.syncer.state_provider:
            state, commit = await self.statesync_reactor.sync(
                discovery_time=self.config.statesync.discovery_time_s
            )
            self.state_store.bootstrap(state)
            self.block_store.save_seen_commit(commit.height, commit)
            # re-anchor everything downstream on the restored state: the
            # blocksync pool must start at snapshot+1 (not the stale
            # construction-time height) and a fast_sync=False node must
            # hand consensus the restored state, not the genesis one
            self.blocksync_reactor.reset_pool(state)
            self.initial_state = state
            self.logger.info("state sync complete", height=state.last_block_height)

        await self.mempool_reactor.start()
        await self.evidence_reactor.start()
        await self.consensus_reactor.start()

        # watchdog last: everything it samples exists and is serving
        if self.health.enabled:
            self.health.start()
        if self.prof.enabled:
            self.prof.start()
        if self.history.enabled:
            self.history.start()

        if self.config.base.fast_sync:
            await self.blocksync_reactor.start(sync=True)
        else:
            # serve blocks to syncing peers while running consensus
            await self.blocksync_reactor.start(sync=False)
            await self._start_consensus(self.initial_state)

    def add_persistent_peer(self, addr: str) -> str:
        """Register an id@host:port address for keep-connected dialing
        (reference sw.AddPersistentPeers); callable at runtime via the
        unsafe dial_peers RPC.  Returns the peer id."""
        pid = self.transport.add_peer_address(addr)
        if pid not in self._persistent_targets:
            self._persistent_targets[pid] = addr
            self._persistent_next_try[pid] = 0.0
        return pid

    def add_private_peer_id(self, pid: str) -> None:
        """Exclude a peer id from PEX gossip (reference
        sw.AddPrivatePeerIDs).  Lowercased: every NodeID produced by
        parse_net_address is lowercase hex."""
        if self.pex_reactor is not None:
            self.pex_reactor.private_ids.add(pid.strip().lower())

    async def _dial_persistent_peers(self) -> None:
        """Keep persistent peers connected, with capped exponential
        backoff + seeded jitter per peer (reference p2p/switch.go
        reconnectToPeer; policy in p2p/backoff.py).  The ladder resets
        only after a connection survives min_uptime, so a flapping peer
        converges to cap-spaced dials instead of busy-looping."""
        backoff = self._dial_backoff
        next_try = self._persistent_next_try
        connected: set[str] = set()

        async def try_dial(pid: str) -> None:
            now = asyncio.get_running_loop().time()
            try:
                await self.router.dial(pid)
                backoff.note_connected(pid, now)
                connected.add(pid)
            except (ConnectionError, OSError, asyncio.TimeoutError) as e:
                self.logger.debug("dial failed", peer=pid[:8], err=str(e))
                next_try[pid] = now + backoff.next_delay(pid)

        while True:
            now = asyncio.get_running_loop().time()
            due = []
            for pid in list(self._persistent_targets):
                if pid in self.router.peers:
                    if pid not in connected:
                        # connected via inbound accept: still counts as up
                        backoff.note_connected(pid, now)
                        connected.add(pid)
                    continue
                if pid in connected:
                    # peer just went down: the ladder only resets if the
                    # connection lasted; either way the next dial waits
                    connected.discard(pid)
                    backoff.note_disconnected(pid, now)
                    next_try[pid] = now + backoff.next_delay(pid)
                    continue
                # remediation quarantine (utils/remediate.py): an
                # evicted flapper sits out its window — the dial-flap-
                # dial loop ends here; pardon resets the ladder to
                # rung 0 inside quarantined()
                if self.remediate.enabled and self.remediate.quarantined(pid):
                    continue
                if now >= next_try[pid]:
                    due.append(pid)
            if due:
                # concurrently: one unreachable peer must not stall the rest
                await asyncio.gather(*(try_dial(pid) for pid in due))
            await asyncio.sleep(0.5)

    def _on_caught_up(self, state) -> None:
        """Blocksync finished — switch to consensus
        (reference consensus/reactor.go:106 SwitchToConsensus)."""
        if self._consensus_running or not self._started:
            return
        self._caught_up.set()
        self._switch_task = asyncio.get_running_loop().create_task(
            self._switch_to_consensus(state)
        )

    async def _switch_to_consensus(self, state) -> None:
        if not self._started:
            return
        # drop the sync pipeline but keep serving blocks to other peers
        await self.blocksync_reactor.stop()
        await self.blocksync_reactor.start(sync=False)
        await self._start_consensus(state)

    async def _start_consensus(self, state) -> None:
        if self._consensus_running:
            return
        self._consensus_running = True
        cs = self.consensus
        if state.last_block_height > (cs.state.last_block_height if cs.state else 0):
            # blocksync/statesync advanced past the handshake state
            cs.reconstruct_last_commit(state)
            cs.rs.height = 0  # allow re-prime
            cs.rs.commit_round = -1
            cs.update_to_state(state)
        await cs.start()
        self.logger.info("consensus started", height=cs.rs.height)

    async def stop(self) -> None:
        if not self._started:
            return
        self._started = False
        if self.health.enabled:
            self.health.stop()
        if self.prof.enabled:
            self.prof.stop()
        if self.history.enabled:
            self.history.stop()
        if self._dialer_task is not None:
            self._dialer_task.cancel()
            try:
                await self._dialer_task
            except (asyncio.CancelledError, Exception):
                pass
            self._dialer_task = None
        if self._switch_task is not None:
            self._switch_task.cancel()
            try:
                await self._switch_task
            except (asyncio.CancelledError, Exception):
                pass
            self._switch_task = None
        if self._consensus_running:
            await self.consensus.stop()
            self._consensus_running = False
        await self.blocksync_reactor.stop()
        await self.consensus_reactor.stop()
        await self.evidence_reactor.stop()
        await self.mempool_reactor.stop()
        await self.statesync_reactor.stop()
        if self.pex_reactor is not None:
            await self.pex_reactor.stop()
        await self.router.stop()
        await self.rpc_server.stop()
        if self.gateway is not None:
            from tendermint_tpu import gateway as _gwmod

            self.gateway.close()
            if _gwmod.active_gateway() is self.gateway:
                _gwmod.clear_active()
        if self.grpc_server is not None:
            await self.grpc_server.stop()
        if self.metrics is not None:
            await self.metrics.stop()
        if self.pprof_server is not None:
            await self.pprof_server.stop()
        if self._pv_remote:
            await asyncio.to_thread(self.priv_validator.close)
        await self.indexer_service.stop()
        self.event_bus.shutdown()
        self.wal.close()
        self.mempool.close_wal()
        if hasattr(self.app_conns, "close"):
            self.app_conns.close()  # external socket app connections
        for db in (self.block_db, self.state_db, self.evidence_db, self.tx_index_db):
            try:
                db.close()
            except Exception:
                pass

    # -- convenience -----------------------------------------------------
    async def wait_for_height(self, h: int, timeout: float = 60.0) -> None:
        async def poll():
            while self.block_store.height() < h:
                await asyncio.sleep(0.02)

        await asyncio.wait_for(poll(), timeout)
