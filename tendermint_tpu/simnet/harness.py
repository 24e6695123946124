"""The simnet runner: in-process nodes over the fault layer.

`SimNode` is a full consensus node minus the external servers — stores,
ABCI app + handshake, mempool/evidence/consensus reactors, WAL, event
journal — wired over a `FaultyNetwork` transport.  Its stores live in
MemDBs owned by the RUNNER (the "disk"), and its WAL/journal are real
files in the node home, so an in-process crash-restart models a process
death faithfully: the new incarnation reopens the same WAL, re-handshakes
a fresh app against the surviving block store, and `catchup_replay`
walks the WAL tail — the exact recovery path a real node takes.

Crashes are abrupt by construction: every task is cancelled (or, for
fail-point crashes, the consensus task dies on `FailPointCrash` mid
commit sequence) and the node's connections are severed through
`FaultyNetwork.drop_node`, so peers observe the death exactly like a
closed socket.  Each node's tasks run under a `utils/fail.py` scope so
armed fail points hit only their target node.

`SimnetRunner.run()` drives the whole scenario: start nodes, keep the
mesh dialed (with the p2p DialBackoff policy — a crashed or partitioned
peer is redialed on the capped jittered ladder), offer tx load, apply
the fault schedule, then stop everything and hand the merged journals +
block stores to `verdict.evaluate`.

Time: every stamp in this module reads the runner's `Clock`
(utils/clock.py) and every wait rides the event loop, so a scenario
with `time = "virtual"` runs on the discrete-event scheduler
(simnet/vclock.py) with zero code differences here beyond two
virtual-mode adaptations: health monitors are ticked by a runner task
instead of their daemon threads (threads cannot block on virtual
sleeps), and per-node RNG seams (reactor gossip jitter) are derived
from the scenario seed so two same-seed runs replay bit-identically.
"""

from __future__ import annotations

import asyncio
import hashlib
import os
import random

from tendermint_tpu.abci import AppConns
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.consensus.config import ConsensusConfig
from tendermint_tpu.consensus.eventlog import EventJournal, read_events
from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.evidence import EvidencePool
from tendermint_tpu.evidence.reactor import EvidenceReactor
from tendermint_tpu.mempool import Mempool
from tendermint_tpu.mempool.mempool import MempoolConfig
from tendermint_tpu.mempool.reactor import MempoolReactor
from tendermint_tpu.p2p import Router
from tendermint_tpu.p2p.backoff import DialBackoff
from tendermint_tpu.p2p.types import node_id_from_pubkey
from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
from tendermint_tpu.store import BlockStore, MemDB
from tendermint_tpu.types import GenesisDoc, GenesisValidator
from tendermint_tpu.types.evidence import DuplicateVoteEvidence
from tendermint_tpu.utils import clock as clockmod
from tendermint_tpu.utils import fail
from tendermint_tpu.utils import health as tmhealth
from tendermint_tpu.utils import history as tmhistory
from tendermint_tpu.utils import profiler as tmprof
from tendermint_tpu.utils import remediate as tmremediate
from tendermint_tpu.utils.log import Logger, nop_logger
from tendermint_tpu.utils.txlife import TxLifecycle

from tendermint_tpu.cli.timeline import build_timeline

from .faults import FaultyNetwork, LinkSpec
from .scenario import Scenario
from .verdict import evaluate


class _PV:
    """In-memory privval (the simnet owns the keys; double-sign
    protection is the maverick's to violate, not the harness's)."""

    def __init__(self, key):
        self.key = key

    def get_pub_key(self):
        return self.key.pub_key()

    def sign_vote(self, chain_id, vote):
        vote.signature = self.key.sign(vote.sign_bytes(chain_id))

    def sign_proposal(self, chain_id, proposal):
        proposal.signature = self.key.sign(proposal.sign_bytes(chain_id))


def _node_key(seed: int, index: int) -> bytes:
    return hashlib.sha256(f"simnet-{seed}-val-{index}".encode()).digest()


class SimNode:
    """One in-process node.  Construction performs the ABCI handshake
    (block-store replay into a fresh app), start() performs WAL catchup
    replay — together these ARE the crash-recovery path."""

    def __init__(self, index: int, key, genesis: GenesisDoc,
                 network: FaultyNetwork, home: str, disk: dict,
                 consensus_config: ConsensusConfig,
                 misbehaviors: dict[int, str] | None = None,
                 gossip_sleep_ms: int = 10,
                 detector_overrides: dict | None = None,
                 clock: clockmod.Clock | None = None,
                 logger: Logger | None = None):
        self.index = index
        self.name = f"node{index}"
        self.key = key
        # the runner's clock: WALL for wall scenarios (bit-identical to
        # the pre-seam behavior), the VirtualClock for time="virtual".
        # `clock.virtual` also decides the health-sampling drive: thread
        # in wall mode, runner ticks in virtual mode.
        self.clock = clock or clockmod.get()
        self.genesis = genesis
        self.network = network
        self.home = home
        self.disk = disk
        self.logger = logger or nop_logger()
        self.node_id = node_id_from_pubkey(key.pub_key())
        self.crashed = False
        os.makedirs(home, exist_ok=True)

        self.state_store = StateStore(disk["state"])
        self.block_store = BlockStore(disk["block"])
        state = self.state_store.load()
        if state is None:
            state = make_genesis_state(genesis)
            self.state_store.save(state)

        # fresh app every incarnation; the handshake replays the block
        # store into it (consensus/replay.py — reference Handshake)
        self.app = KVStoreApplication()
        conns = AppConns(self.app)
        self.handshaker = Handshaker(
            self.state_store, state, self.block_store, genesis,
            logger=self.logger)
        state = self.handshaker.handshake(conns)
        self.handshake_blocks = self.handshaker.n_blocks

        self.mempool = Mempool(MempoolConfig(), conns.mempool())
        self.evpool = EvidencePool(disk["evidence"], self.state_store,
                                   self.block_store)
        self.executor = BlockExecutor(
            self.state_store, conns.consensus(),
            mempool=self.mempool, evidence_pool=self.evpool,
        )
        self.wal = WAL(os.path.join(home, "cs.wal"))
        # how much WAL tail the new incarnation will replay (for the
        # verdict's "WAL replay verified" evidence)
        tail, found = self.wal.search_for_end_height(state.last_block_height)
        self.wal_tail_records = len(tail) if found else 0

        cs_cls, cs_kw = ConsensusState, {}
        if misbehaviors:
            from tendermint_tpu.e2e.maverick import MaverickConsensusState

            cs_cls = MaverickConsensusState
            cs_kw = {"misbehaviors": dict(misbehaviors), "raw_key": key}
        self.cs = cs_cls(
            consensus_config, state, self.executor, self.block_store,
            wal=self.wal, priv_validator=_PV(key), evidence_pool=self.evpool,
            logger=self.logger, **cs_kw,
        )
        self.journal_path = os.path.join(home, "journal.jsonl")
        self.cs.journal = EventJournal(self.journal_path, node=self.name)
        # tx lifecycle tracer: milestones (admit/gossip/propose/commit/
        # apply) ride this node's journal as tx_* lines, which is what
        # the verdict's finality percentiles and `txtrace` read back
        self.txlife = TxLifecycle(journal=self.cs.journal, node=self.name)
        self.cs.lifecycle = self.txlife
        self.mempool.lifecycle = self.txlife

        self.router = Router(self.node_id,
                             network.create_transport(self.node_id),
                             logger=self.logger)
        # health watchdog (TM_TPU_HEALTH, default on): each SimNode
        # self-diagnoses like a real node, so the verdict can say which
        # detector fired on which node first.  Fast cadence + a stall
        # horizon scaled to the (50ms-class) test timeouts; bundles land
        # under the node home, and the runner feeds fault windows in so
        # in-window transitions read back as excused.
        # fault-injection overrides merged into every health sample
        # (LAST, so an injected verify_queue_depth/cold_compiles beats
        # the real probes) — the runner's flood/compile_storm ops write
        # here and the detectors react exactly as they would live
        self.fault_inject: dict = {}
        self.health = tmhealth.from_env(
            node=self.name,
            root=home,
            probes={
                "consensus": lambda: {"height": self.block_store.height(),
                                      "round": self.cs.rs.round},
                "peers": lambda: {
                    "peers": len(self.router.peers),
                    "peer_disconnects": self.router.peers_disconnected,
                },
                "inject": lambda: dict(self.fault_inject),
            },
            journal=self.cs.journal,
            journal_path=self.journal_path,
            expected_block_s=max(0.2,
                                 4 * consensus_config.timeout_commit_ms / 1e3),
            interval_s=0.25,
            clock=self.clock.monotonic,
            # detector-window overrides: the RUNNER passes test-scale
            # compile-storm grace / peer-flap spans ONLY for scenarios
            # that inject those triggers (compile_storm/flap ops) — a
            # blanket min-span cut would make one partition disconnect
            # read as a high per-minute rate over a tiny span and flap
            # peer_flap in scenarios that never touch the links
            **(detector_overrides or {}),
        )
        # per-node dial ladder: the runner's mesh keeper climbs it for
        # every peer THIS node dials, so its flap counters are the
        # remediation controller's eviction score (same policy as the
        # real node's persistent-peer dialer)
        self.dial_backoff = DialBackoff(base_s=0.1, cap_s=2.0,
                                        min_uptime_s=2.0, rng=network.rng)
        self._loop: asyncio.AbstractEventLoop | None = None

        def _evict_peer(pid: str) -> None:
            loop = self._loop
            if loop is not None and loop.is_running():
                asyncio.run_coroutine_threadsafe(
                    self.router.disconnect(pid), loop)

        # remediation controller (TM_TPU_REMEDIATE, default on): wired
        # like the real node's, with test-scale quarantine windows and
        # a recording-only rewarm — simnet nodes share one process, so
        # a REAL background warm would compile in-process; the action
        # (and its journal row) is what scenarios assert.
        self.remediate = tmremediate.NOP
        if tmremediate.env_enabled():
            self.remediate = tmremediate.RemediationController(
                node=self.name,
                mempool=self.mempool,
                backoff=self.dial_backoff,
                evict_peer=_evict_peer,
                rewarm=lambda reason: False,
                journal=self.cs.journal,
                rewarm_min_s=30.0,
                # test scale: a flap op churns every ~0.4s, so two
                # early deaths already prove the pattern; production
                # keeps the env-tuned threshold of 3
                flap_threshold=2,
                quarantine_s=2.0,
                quarantine_cap_s=8.0,
                rng=random.Random(f"remediate-{genesis.chain_id}-{index}"),
                clock=self.clock.monotonic,
            )
        if self.health.enabled and self.remediate.enabled:
            self.health.remediate = self.remediate
        # continuous profiler (TM_TPU_PROF, default on): the sampler is
        # a WALL-clock daemon thread, so it only runs in wall mode (see
        # start()); in virtual mode the report stays empty rather than
        # sampling a wall cadence against a virtual timeline.  Window
        # boundaries ride the node clock so wall-mode folds line up
        # with the journal.
        self.prof = tmprof.from_env(node=self.name,
                                    clock=self.clock.monotonic)
        if self.health.enabled and self.prof.enabled:
            self.health.prof = self.prof
        # flight-data history (TM_TPU_HISTORY, default on): memory-mode
        # recorder (no root — simnet homes are throwaway; the in-memory
        # tail covers drift detection and the verdict's retrospective
        # SLO replay) over a synthetic exposition of this node's core
        # series.  Wall stamps ride the clock seam, so virtual runs
        # record at deterministic virtual instants and the verdict's
        # history block is byte-identical across same-seed runs.  Test-
        # scale cadence matches the health monitor's.
        self.history = tmhistory.from_env(
            node=self.name,
            source=self._expose_history,
            clock=self.clock.monotonic,
            interval_s=0.25,
        )
        if self.health.enabled and self.history.enabled:
            self.health.history = self.history
            self.health.probes["history"] = self.history.drift_probe
        self.reactor = ConsensusReactor(
            self.cs, self.router, self.block_store,
            gossip_sleep_ms=gossip_sleep_ms, maj23_sleep_ms=500,
            # per-node seeded gossip jitter: the reactor's default rng
            # seed folds id(self) in, which differs between two same-
            # seed runs in one process — fatal to the virtual mode's
            # byte-identical-verdict contract (and a free improvement
            # to wall-mode replayability)
            jitter_rng=random.Random(f"gossip-{genesis.chain_id}-{index}"),
            logger=self.logger,
        )
        if misbehaviors:
            from tendermint_tpu.consensus.messages import VoteMessage
            from tendermint_tpu.p2p.types import Envelope

            self.cs.broadcast_vote = lambda v: self.reactor.vote_ch.try_send(
                Envelope(message=VoteMessage(v), broadcast=True))
        # mempool/evidence gossip cadence scales with the consensus
        # cadence: big nets oversubscribe one event loop (n^2 gossip
        # loops), and a starved loop fires consensus timeouts that say
        # nothing about the protocol
        self.mp_reactor = MempoolReactor(
            self.mempool, self.router,
            gossip_sleep_ms=max(20, 2 * gossip_sleep_ms),
            batch_txs=64)
        self.ev_reactor = EvidenceReactor(
            self.evpool, self.router,
            gossip_sleep_ms=max(50, 5 * gossip_sleep_ms))

    def _expose_history(self) -> str:
        """Synthetic exposition for the history recorder: the node's
        own core series in the live `/metrics` shape, so recorded
        states replay through the same promparse path as real scrapes
        (commits doubles as height — a monotone counter the drift
        probe can rate)."""
        h = self.block_store.height()
        return (f"tendermint_consensus_height {h}\n"
                f"tendermint_p2p_peers {len(self.router.peers)}\n"
                f"tendermint_sim_commits_total {h}\n")

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        # bind every task this node creates to its fail-point scope
        token = fail.set_scope(self.name)
        try:
            await self.router.start()
            await self.reactor.start()
            await self.mp_reactor.start()
            await self.ev_reactor.start()
            await self.cs.start()   # runs catchup_replay first
        finally:
            fail.reset_scope(token)
        if self.health.enabled and not self.clock.virtual:
            # virtual mode: no daemon thread (it would sample on the
            # WALL cadence against a virtual clock — both the wrong
            # timeline and a nondeterministic one); the runner's
            # _health_ticker task drives sample() instead
            self.health.start()
        if self.prof.enabled and not self.clock.virtual:
            # same contract as the health ticker: the sampler blocks a
            # real thread between sweeps, so virtual mode skips it
            # entirely (no task drives it — stack sampling of a paused
            # virtual timeline would attribute everything to the
            # scheduler)
            self.prof.start()
        if self.history.enabled and not self.clock.virtual:
            # virtual mode: the runner's _history_ticker drives
            # sample() at virtual instants instead of a wall thread
            self.history.start()

    async def stop(self) -> None:
        """Clean shutdown (end of run)."""
        if self.history.enabled:
            self.history.stop()
        if self.prof.enabled:
            self.prof.stop()
        if self.health.enabled:
            self.health.stop()
        await self.cs.stop()
        await self.reactor.stop()
        await self.mp_reactor.stop()
        await self.ev_reactor.stop()
        await self.router.stop()

    async def crash(self) -> None:
        """Abrupt death: cancel everything, sever every connection, no
        clean-shutdown work beyond releasing file handles (their content
        is already on disk — the WAL flushes per write)."""
        self.crashed = True
        if self.history.enabled:
            self.history.stop(timeout=0.2)
        if self.prof.enabled:
            self.prof.stop()
        if self.health.enabled:
            self.health.stop(timeout=0.2)
        fail.uninstall(self.name)
        self.cs._stopping = True
        self.cs.ticker.stop()
        tasks = []
        if self.cs._task is not None:
            self.cs._task.cancel()
            tasks.append(self.cs._task)
        for reactor in (self.reactor, self.mp_reactor, self.ev_reactor):
            for t in list(reactor._tasks):
                t.cancel()
                tasks.append(t)
            for ts in reactor._peer_tasks.values():
                ts = ts if isinstance(ts, list) else [ts]
                for t in ts:
                    t.cancel()
                    tasks.append(t)
        for t in self.router._tasks:
            t.cancel()
            tasks.append(t)
        for peer in self.router.peers.values():
            for t in peer.tasks:
                t.cancel()
                tasks.append(t)
        await asyncio.gather(*tasks, return_exceptions=True)
        await self.network.drop_node(self.node_id)
        self.wal.close()
        self.cs.journal.close()

    def consensus_dead(self) -> BaseException | None:
        """The exception that killed the consensus task, if any (a
        FailPointCrash when a scoped fail point fired)."""
        t = self.cs._task
        if t is None or not t.done() or t.cancelled():
            return None
        return t.exception()

    def height(self) -> int:
        return self.block_store.height()


class SimnetRunner:
    def __init__(self, scenario: Scenario, root: str,
                 logger: Logger | None = None):
        scenario.validate()
        self.scenario = scenario
        self.root = root
        self.logger = logger or nop_logger()
        # the active process clock: WALL normally; the VirtualClock when
        # run_scenario dispatched this run through run_in_virtual_time
        # (which installs it before this constructor executes)
        self.clock = clockmod.get()
        self.virtual = scenario.time == "virtual"
        self.network = FaultyNetwork(seed=scenario.seed)
        self.nodes: list[SimNode] = []
        self._disks: list[dict] = []
        self._keys: list = []
        self.genesis: GenesisDoc | None = None
        self._ccfg = self._consensus_config()
        self._byzantine = scenario.byzantine_nodes()
        self._maverick_map = scenario.maverick_map()
        # test-scale detector windows, only where the schedule injects
        # the matching trigger (production defaults otherwise)
        ops = {op.op for op in scenario.faults}
        self._detector_overrides: dict = {}
        if "compile_storm" in ops:
            self._detector_overrides.update(
                compile_grace_s=1.5, compile_window_s=10.0)
        if "flap" in ops:
            self._detector_overrides.update(
                flap_window_s=12.0, flap_min_span_s=3.0)
        # bookkeeping for the verdict
        self.accepted_tx = 0
        self.offered_tx = 0
        self.restarts: dict[int, int] = {}
        self.wal_replays: dict[int, list] = {}
        self.fault_log: list[dict] = []
        self.fault_windows: list[dict] = []   # {kind, nodes, t0_ns, t1_ns}
        self._open_windows: dict[str, dict] = {}
        self.heal_times_ns: list[int] = []
        self._mesh: list[tuple[int, int]] = []
        self._aux: list[asyncio.Task] = []
        self._applying = False
        # flood-op load spike: the driver multiplies its offered rate
        # by this for the duration of the injection window
        self._load_factor = 1.0
        # fleet-scope SLOs (scenario [[slo_objectives]]): the sampler
        # task feeds availability ticks into the burn engine through
        # the run; _finish evaluates every objective against the
        # synthesized fleet snapshot and the verdict gains a `fleet`
        # block.  Availability here means "the node is serving": alive
        # AND committed within the stall-budget horizon — a quorum-loss
        # partition reads as the whole fleet going unavailable, exactly
        # like its RPC rows would read to the live scraper.
        self._slo_objectives = scenario.parsed_slo_objectives()
        self._slo_engine = None
        self._avail_ticks: list[float] = []   # per-tick serving ratio
        self._slo_burn_episode: set[str] = set()
        if self._slo_objectives:
            from tendermint_tpu.fleet.slo import BurnEngine

            self._slo_engine = BurnEngine(clock=self.clock.monotonic)

    # -- construction ----------------------------------------------------
    def _consensus_config(self) -> ConsensusConfig:
        cc = ConsensusConfig.test_config()
        s = self.scenario.timeout_scale
        if s != 1.0:
            for f in ("timeout_propose_ms", "timeout_propose_delta_ms",
                      "timeout_prevote_ms", "timeout_prevote_delta_ms",
                      "timeout_precommit_ms", "timeout_precommit_delta_ms",
                      "timeout_commit_ms"):
                setattr(cc, f, max(1, int(getattr(cc, f) * s)))
        return cc

    def _build_genesis(self) -> GenesisDoc:
        sc = self.scenario
        from tendermint_tpu.crypto.keys import priv_key_from_seed

        self._keys = [priv_key_from_seed(_node_key(sc.seed, i))
                      for i in range(sc.validators)]
        weights = sc.live_weights()
        validators = [
            GenesisValidator(pub_key=k.pub_key(), power=w)
            for k, w in zip(self._keys, weights)
        ]
        # passive validator slots: scale the validator set (commit width,
        # verify load, proposer rotation) without running more nodes —
        # the "hundreds-to-thousands of validator slots" axis
        for i in range(sc.validators, sc.total_slots()):
            pk = priv_key_from_seed(_node_key(sc.seed, i))
            validators.append(
                GenesisValidator(pub_key=pk.pub_key(), power=sc.slot_power))
        return GenesisDoc(
            chain_id=f"simnet-{sc.name}",
            genesis_time_ns=1_700_000_000 * 10**9,
            validators=validators,
        )

    def _make_node(self, index: int) -> SimNode:
        node = SimNode(
            index, self._keys[index], self.genesis, self.network,
            home=os.path.join(self.root, f"node{index}"),
            disk=self._disks[index],
            consensus_config=self._ccfg,
            misbehaviors=self._maverick_map.get(index),
            gossip_sleep_ms=self.scenario.gossip_sleep_ms,
            detector_overrides=self._detector_overrides,
            clock=self.clock,
            logger=self.logger,
        )
        return node

    # -- fault-window bookkeeping (verdict stall exclusions) -------------
    def _window_open(self, key: str, kind: str, nodes: list[int]) -> None:
        self._open_windows[key] = {
            "kind": kind, "nodes": list(nodes), "t0_ns": self.clock.wall_ns()}
        # every node's watchdog learns a fault window is open (the
        # verdict's rule: ALL windows count — a partition stalls the
        # majority via lost proposers too), so detector transitions
        # inside it are recorded as excused rather than suppressed
        for node in self.nodes:
            if node is not None and not node.crashed \
                    and node.health.enabled:
                node.health.fault_begin()

    def _window_close(self, key: str) -> None:
        w = self._open_windows.pop(key, None)
        if w is not None:
            w["t1_ns"] = self.clock.wall_ns()
            self.fault_windows.append(w)
            for node in self.nodes:
                if node is not None and not node.crashed \
                        and node.health.enabled:
                    node.health.fault_end()

    def _close_all_windows(self) -> None:
        for key in list(self._open_windows):
            self._window_close(key)

    # -- run -------------------------------------------------------------
    async def run(self) -> dict:
        sc = self.scenario
        self.genesis = self._build_genesis()
        self._disks = [
            {"state": MemDB(), "block": MemDB(), "evidence": MemDB()}
            for _ in range(sc.validators)
        ]
        self.nodes = [None] * sc.validators
        for i in range(sc.validators):
            self.nodes[i] = self._make_node(i)
        self._fault_queue = list(sc.faults)
        self._apply_baseline_links()
        t_start_ns = self.clock.wall_ns()
        t0 = self.clock.monotonic()
        for node in self.nodes:
            await node.start()
        await self._dial_mesh()

        loop = asyncio.get_running_loop()
        self._aux = [
            loop.create_task(self._mesh_keeper()),
            loop.create_task(self._crash_watcher()),
            loop.create_task(self._fault_schedule()),
        ]
        if sc.load_rate > 0:
            self._aux.append(loop.create_task(self._load_driver()))
        if self._slo_objectives:
            self._aux.append(loop.create_task(self._fleet_sampler()))
        if self.virtual:
            self._aux.append(loop.create_task(self._health_ticker()))
            self._aux.append(loop.create_task(self._history_ticker()))

        try:
            await asyncio.wait_for(
                self._wait_target_height(), timeout=sc.max_runtime_s)
            timed_out = False
        except asyncio.TimeoutError:
            timed_out = True
        finally:
            for t in self._aux:
                t.cancel()
            await asyncio.gather(*self._aux, return_exceptions=True)
            for node in self.nodes:
                if not node.crashed:
                    await node.stop()
        self._close_all_windows()
        duration_s = self.clock.monotonic() - t0

        return self._finish(t_start_ns, duration_s, timed_out)

    def _finish(self, t_start_ns: int, duration_s: float,
                timed_out: bool) -> dict:
        sc = self.scenario
        journals = {}
        for node in self.nodes:
            try:
                journals[node.name] = read_events(node.journal_path)
            except OSError:
                journals[node.name] = []
        report = build_timeline(journals)

        honest_alive = [n for n in self.nodes
                        if n.index not in self._byzantine and not n.crashed]
        header_hashes: dict[int, dict[str, str]] = {}
        if honest_alive:
            upto = min(n.height() for n in honest_alive)
            for h in range(1, upto + 1):
                header_hashes[h] = {}
                for n in honest_alive:
                    block = n.block_store.load_block(h)
                    if block is not None:
                        header_hashes[h][n.name] = block.hash().hex()

        evidence_committed = 0
        if honest_alive:
            probe = honest_alive[0]
            for h in range(1, probe.height() + 1):
                block = probe.block_store.load_block(h)
                if block is not None:
                    evidence_committed += sum(
                        1 for e in block.evidence
                        if isinstance(e, DuplicateVoteEvidence))

        health_reports = {
            node.name: (node.health.report() if node.health.enabled
                        else {"enabled": False})
            for node in self.nodes
        }
        remediation_reports = {
            node.name: (node.remediate.report() if node.remediate.enabled
                        else {"enabled": False})
            for node in self.nodes
        }
        profile_reports = {
            node.name: (node.prof.report()
                        if node.prof.enabled and not self.clock.virtual
                        else {"enabled": False})
            for node in self.nodes
        }
        history_reports = {
            node.name: (node.history.report() if node.history.enabled
                        else {"enabled": False})
            for node in self.nodes
        }

        fleet_block = None
        if self._slo_objectives:
            from tendermint_tpu.fleet.slo import evaluate as slo_evaluate
            from tendermint_tpu.fleet.slo import evaluate_history

            snap = self._fleet_snapshot(report)
            fleet_block = {
                **snap,
                "slo": slo_evaluate(self._slo_objectives, snap,
                                    engine=self._slo_engine),
            }
            # retrospective twin: replay each node's RECORDED series
            # through a fresh dual-window engine.  With history on this
            # must agree with the live verdict above (the verdict block
            # asserts it); with history off it degrades to no-data and
            # the gate skips rather than fails.
            histories = {node.name: node.history.records()
                         for node in self.nodes if node.history.enabled}
            fleet_block["slo_history"] = evaluate_history(
                self._slo_objectives, histories)

        run_info = {
            "t_start_ns": t_start_ns,
            "fleet": fleet_block,
            "health": health_reports,
            "remediation": remediation_reports,
            "profile": profile_reports,
            "history": history_reports,
            "duration_s": duration_s,
            "timed_out": timed_out,
            "timeout_commit_ms": self._ccfg.timeout_commit_ms,
            "round_ms": (self._ccfg.timeout_propose_ms
                         + self._ccfg.timeout_prevote_ms
                         + self._ccfg.timeout_precommit_ms
                         + self._ccfg.timeout_commit_ms),
            "nodes": [
                {
                    "name": n.name,
                    "index": n.index,
                    "honest": n.index not in self._byzantine,
                    "crashed": n.crashed,
                    "height": n.height(),
                    "restarts": self.restarts.get(n.index, 0),
                }
                for n in self.nodes
            ],
            "header_hashes": header_hashes,
            "evidence_committed": evidence_committed,
            "fault_windows": list(self.fault_windows),
            "heal_times_ns": list(self.heal_times_ns),
            "accepted_tx": self.accepted_tx,
            "offered_tx": self.offered_tx,
            "wal_replays": {str(k): v for k, v in self.wal_replays.items()},
            "network": self.network.stats(),
            "fault_log": list(self.fault_log),
        }
        return evaluate(sc, report, run_info)

    def _apply_baseline_links(self) -> None:
        """Install the scenario's permanent [[links]] topology (geo
        latency and the like) before anything dials.  NOT a fault: no
        window opens, so the stall and health invariants stay armed —
        the net must meet its budgets THROUGH the WAN it declares."""
        for ln in self.scenario.links:
            spec = LinkSpec(
                latency_ms=float(ln.get("latency_ms", 0.0)),
                jitter_ms=float(ln.get("jitter_ms", 0.0)),
                drop=float(ln.get("drop", 0.0)),
                bandwidth=int(ln.get("bandwidth", 0)),
            )
            srcs = [self.nodes[int(i)].node_id for i in ln["nodes"]]
            if ln.get("to_nodes"):
                dsts = [self.nodes[int(i)].node_id for i in ln["to_nodes"]]
            else:
                dsts = [n.node_id for n in self.nodes]
            for a in srcs:
                for b in dsts:
                    if a != b:
                        self.network.set_link(a, b, spec)

    # -- mesh ------------------------------------------------------------
    def _mesh_pairs(self) -> list[tuple[int, int]]:
        """Topology: full mesh by default; with scenario.mesh_degree, a
        ring + seeded random chords until every node has >= degree
        neighbors.  A 20+ node full mesh floods every vote over O(n^2)
        links and the duplicate decode work alone saturates the event
        loop — real deployments don't run all-to-all either."""
        n = len(self.nodes)
        d = self.scenario.mesh_degree
        if d <= 0 or d >= n - 1:
            return [(i, j) for i in range(n) for j in range(i + 1, n)]
        pairs: set[tuple[int, int]] = set()
        deg = {i: 0 for i in range(n)}

        def add(i: int, j: int) -> None:
            key = (min(i, j), max(i, j))
            if i != j and key not in pairs:
                pairs.add(key)
                deg[i] += 1
                deg[j] += 1

        for i in range(n):  # ring: baseline connectivity
            add(i, (i + 1) % n)
        import random as _random

        rng = _random.Random(f"mesh-{self.scenario.seed}")
        attempts = 0
        while min(deg.values()) < d and attempts < 20 * n * d:
            attempts += 1
            i = min(deg, key=lambda k: deg[k])
            add(i, rng.randrange(n))
        return sorted(pairs)

    async def _dial_mesh(self) -> None:
        self._mesh = self._mesh_pairs()
        for i, j in self._mesh:
            try:
                await self.nodes[i].router.dial(self.nodes[j].node_id)
            except ConnectionError:
                pass  # partitioned/crashed at start: keeper retries

    async def _mesh_keeper(self) -> None:
        """Keep the mesh dialed through churn: a restarted node or a
        healed partition is redialed on the DIALING node's DialBackoff
        ladder — the same policy the real node's persistent-peer dialer
        runs.  Disconnects are noted against the ladder so a flapping
        target accumulates flap score, the remediation controller can
        evict + quarantine it (the keeper honors the quarantine), and a
        pardoned peer restarts from rung 0."""
        next_try: dict[tuple[int, int], float] = {}
        connected: set[tuple[int, int]] = set()
        loop = asyncio.get_running_loop()
        while True:
            now = loop.time()
            for i, j in self._mesh:
                a, b = self.nodes[i], self.nodes[j]
                key = (i, j)
                if a.crashed or b.crashed:
                    connected.discard(key)
                    continue
                if b.node_id in a.router.peers:
                    if key not in connected:
                        a.dial_backoff.note_connected(b.node_id, now)
                        connected.add(key)
                    continue
                if key in connected:
                    # the link just died: flap-or-reset is the ladder's
                    # call (survived min_uptime or not)
                    connected.discard(key)
                    a.dial_backoff.note_disconnected(b.node_id, now)
                    next_try[key] = now + a.dial_backoff.next_delay(b.node_id)
                    continue
                if a.remediate.enabled and a.remediate.quarantined(b.node_id):
                    continue
                if now < next_try.get(key, 0.0):
                    continue
                try:
                    await a.router.dial(b.node_id)
                    a.dial_backoff.note_connected(b.node_id, now)
                    connected.add(key)
                except (ConnectionError, OSError):
                    next_try[key] = now + a.dial_backoff.next_delay(b.node_id)
            await asyncio.sleep(0.1)

    # -- load ------------------------------------------------------------
    async def _load_driver(self) -> None:
        """Offer `load_rate` tx/s round-robin into honest live mempools
        (they gossip from there — reference test/e2e/runner/load.go)."""
        sc = self.scenario
        i = 0
        while sc.load_total <= 0 or self.offered_tx < sc.load_total:
            interval = 1.0 / (sc.load_rate * self._load_factor)
            targets = [n for n in self.nodes
                       if not n.crashed and n.index not in self._byzantine]
            if targets:
                node = targets[i % len(targets)]
                tx = f"load-{i}={sc.seed}".encode()
                self.offered_tx += 1
                try:
                    res = node.mempool.check_tx(tx)
                    if getattr(res, "code", 1) == 0:
                        self.accepted_tx += 1
                except Exception:
                    pass  # full mempool / dup under churn: offered, not accepted
            i += 1
            await asyncio.sleep(interval)

    # -- virtual-mode health drive ---------------------------------------
    async def _health_ticker(self) -> None:
        """The virtual-time replacement for the monitors' daemon threads
        (the vclock thread-tick contract, docs/simnet.md): sample every
        live node's HealthMonitor on its own cadence from INSIDE the
        event loop, so sampling happens at deterministic virtual
        instants — a thread sleeping real seconds against a virtual
        clock would sample at wall-dependent, irreproducible points."""
        interval = min((n.health.interval_s for n in self.nodes
                        if n is not None and n.health.enabled),
                       default=0.25)
        while True:
            await asyncio.sleep(interval)
            for node in self.nodes:
                if node is None or node.crashed or not node.health.enabled:
                    continue
                try:
                    # guarded by the compound continue above (enabled
                    # checked there); the analyzer only models the
                    # single-condition guard shape
                    node.health.sample()  # tmlint: disable=ungated-observability
                except Exception as e:  # noqa: BLE001 — watchdog survives
                    self.logger.warning("health tick failed",
                                        node=node.name, err=repr(e))

    async def _history_ticker(self) -> None:
        """The history recorders' virtual-time drive, same contract as
        _health_ticker: sample every live node's recorder from inside
        the event loop at deterministic virtual instants, so recorded
        wall stamps (and everything derived from them — drift probes,
        the verdict's retrospective SLO replay) are byte-identical
        across same-seed runs."""
        interval = min((n.history.interval_s for n in self.nodes
                        if n is not None and n.history.enabled),
                       default=0.25)
        while True:
            await asyncio.sleep(interval)
            for node in self.nodes:
                if node is None or node.crashed or not node.history.enabled:
                    continue
                try:
                    # guarded by the compound continue above (enabled
                    # checked there); the analyzer only models the
                    # single-condition guard shape
                    node.history.sample()  # tmlint: disable=ungated-observability
                except Exception as e:  # noqa: BLE001 — recorder survives
                    self.logger.warning("history tick failed",
                                        node=node.name, err=repr(e))

    # -- fleet SLO sampling ----------------------------------------------
    def _round_ms(self) -> int:
        return (self._ccfg.timeout_propose_ms + self._ccfg.timeout_prevote_ms
                + self._ccfg.timeout_precommit_ms
                + self._ccfg.timeout_commit_ms)

    def _avail_horizon_s(self) -> float:
        """A node counts as serving while it committed within this
        horizon — the verdict's stall budget reused, so 'unavailable'
        and 'stalled' mean the same thing."""
        if self.scenario.stall_factor > 0:
            return (self.scenario.stall_factor
                    * self._ccfg.timeout_commit_ms / 1e3)
        return max(5.0, 6.0 * self._round_ms() / 1e3)

    async def _fleet_sampler(self) -> None:
        """The in-process twin of the live fleet scraper: tick the
        per-node serving state, feed availability-kind objectives into
        the burn engine, and on a good→bad edge push an `slo_burn`
        record into every live node's HealthMonitor + journal — the
        fleet layer telling the nodes their deployment is burning."""
        from tendermint_tpu.fleet import slo as fleet_slo

        horizon = self._avail_horizon_s()
        loop = asyncio.get_running_loop()
        last_height: dict[int, int] = {}
        last_advance: dict[int, float] = {}
        avail_objs = [o for o in self._slo_objectives
                      if o.kind == "availability"]
        while True:
            now = loop.time()
            serving = 0
            for node in self.nodes:
                if node is None or node.crashed:
                    last_height.pop(node.index if node else -1, None)
                    continue
                h = node.height()
                if h != last_height.get(node.index):
                    last_height[node.index] = h
                    last_advance[node.index] = now
                ok = now - last_advance.get(node.index, now) <= horizon
                if ok:
                    serving += 1
                if node.history.enabled:
                    # the serving bit rides the node's own history as a
                    # sticky gauge, so the retrospective SLO replay
                    # (fleet.evaluate_history) reads availability from
                    # the record exactly as the live scraper reads RPC
                    node.history.record("serving", 1.0 if ok else 0.0)
            ratio = serving / len(self.nodes) if self.nodes else 0.0
            self._avail_ticks.append(ratio)
            for obj in avail_objs:
                good = ratio >= (obj.min if obj.min is not None else 0.0)
                self._slo_engine.feed(obj.name, good)
                if good:
                    self._slo_burn_episode.discard(obj.name)
                elif obj.name not in self._slo_burn_episode:
                    # one slo_burn per bad episode, fanned out to every
                    # live node's monitor + journal (both sink-gated)
                    self._slo_burn_episode.add(obj.name)
                    for node in self.nodes:
                        if node is None or node.crashed:
                            continue
                        if node.health.enabled:
                            node.health.record(
                                "slo_burn", {"objective": obj.name,
                                             "value": round(ratio, 4)})
                        if node.cs.journal.enabled:
                            node.cs.journal.log(
                                "slo_burn", objective=obj.name,
                                value=round(ratio, 4),
                                detail="fleet availability under bound")
            await asyncio.sleep(0.25)

    def _fleet_snapshot(self, report) -> dict:
        """The simnet-side fleet aggregate: the same field paths
        fleet/aggregate.py produces, synthesized from the run instead
        of scraped — availability from the sampler's ticks, finality
        percentiles from the merged tx_* journal lifecycles WITHOUT
        fault-window exclusion ('the fleet met its objective THROUGH
        the fault window' is exactly the question), health from the
        monitors."""
        ticks = self._avail_ticks
        live = sum(1 for n in self.nodes if n is not None and not n.crashed)
        samples: list[float] = []
        for tv in report.txs.values():
            start = tv.first.get("rpc") or tv.first.get("admit")
            end = tv.first.get("apply") or tv.first.get("commit")
            if start is None or end is None or end[0] < start[0]:
                continue
            samples.append((end[0] - start[0]) / 1e9)
        samples.sort()

        def pct(q: float):
            if not samples:
                return None
            idx = min(len(samples) - 1, int(q * (len(samples) - 1) + 0.5))
            return round(samples[idx], 4)

        finality = None
        if samples:
            finality = {
                "count": len(samples),
                "mean_s": round(sum(samples) / len(samples), 4),
                "p50_s": pct(0.50), "p95_s": pct(0.95), "p99_s": pct(0.99),
            }
        levels = [n.health.level() for n in self.nodes
                  if n is not None and not n.crashed and n.health.enabled]
        return {
            "availability": {
                "total": len(self.nodes),
                "serving": live,
                "ratio": (round(sum(ticks) / len(ticks), 4)
                          if ticks else (1.0 if live == len(self.nodes)
                                         else 0.0)),
                "min_ratio": round(min(ticks), 4) if ticks else None,
                "samples": len(ticks),
            },
            "histograms": {"finality": finality},
            "health": {"level": max(levels) if levels else None},
        }

    # -- progress --------------------------------------------------------
    def _honest_live(self) -> list[SimNode]:
        return [n for n in self.nodes
                if not n.crashed and n.index not in self._byzantine]

    async def _wait_target_height(self) -> None:
        target = self.scenario.target_height
        while True:
            live = self._honest_live()
            if live and all(n.height() >= target for n in live) \
                    and not self._pending_faults:
                return
            await asyncio.sleep(0.1)

    async def _wait_any_height(self, h: int) -> None:
        while not any(n.height() >= h for n in self._honest_live()):
            await asyncio.sleep(0.05)

    # -- fault schedule --------------------------------------------------
    @property
    def _pending_faults(self) -> bool:
        # an op mid-apply counts: a crash op is still "pending" through
        # its restart delay, or the run could end at target height with
        # the victim down and silently skip the restart + WAL replay
        return bool(self._fault_queue) or self._applying

    async def _fault_schedule(self) -> None:
        # height-triggered ops run in schedule order; time-triggered ops
        # fire at their offsets.  One task walks the list sequentially —
        # scenarios are scripts, not concurrent programs.
        t0 = asyncio.get_running_loop().time()
        try:
            while self._fault_queue:
                op = self._fault_queue[0]
                if op.at_height is not None:
                    await self._wait_any_height(op.at_height)
                else:
                    delay = t0 + float(op.at_s) - asyncio.get_running_loop().time()
                    if delay > 0:
                        await asyncio.sleep(delay)
                self._applying = True
                self._fault_queue.pop(0)
                try:
                    await self._apply(op)
                except Exception as e:
                    self.fault_log.append({"op": op.op, "error": repr(e)})
                finally:
                    self._applying = False
        except asyncio.CancelledError:
            self._fault_queue = []
            raise

    async def _apply(self, op) -> None:
        sc = self.scenario
        self.fault_log.append({
            "op": op.op, "nodes": list(op.nodes),
            "t_ns": self.clock.wall_ns(),
            "at_height": op.at_height, "at_s": op.at_s,
        })
        ids = [self.nodes[int(i)].node_id for i in op.nodes]
        if op.op == "partition":
            minority = set(ids)
            rest = {n.node_id for n in self.nodes} - minority
            if op.one_way:
                # asymmetric cut: minority's frames die, the rest's
                # frames still arrive
                for a in minority:
                    for b in rest:
                        self.network.set_link(a, b, LinkSpec(blocked=True),
                                              symmetric=False)
            else:
                self.network.partition([rest, minority])
            self._window_open("partition", "partition",
                              [int(i) for i in op.nodes])
        elif op.op == "heal":
            # lifts group partitions AND per-link blocks (one-way cuts);
            # slow-phase degradation stays until an explicit "clear"
            self.network.heal()
            self.network.unblock_links()
            self._window_close("partition")
            self.heal_times_ns.append(self.clock.wall_ns())
        elif op.op == "slow":
            spec = LinkSpec(latency_ms=op.latency_ms, jitter_ms=op.jitter_ms,
                            drop=op.drop, bandwidth=op.bandwidth)
            if not op.nodes:
                self.network.set_default(spec)
            elif op.to_nodes:
                # inter-group degradation only (geo topologies: the
                # nodes<->to_nodes edges are the WAN hop, links inside
                # each group stay fast)
                for a in ids:
                    for b in [self.nodes[int(i)].node_id
                              for i in op.to_nodes]:
                        if a != b:
                            self.network.set_link(a, b, spec)
            else:
                others = [n.node_id for n in self.nodes]
                for a in ids:
                    for b in others:
                        if a != b:
                            self.network.set_link(a, b, spec)
            self._window_open("slow", "slow", [int(i) for i in op.nodes])
        elif op.op == "clear":
            self.network.set_default(None)
            self.network.undegrade_links()
            self._window_close("slow")
        elif op.op == "isolate":
            for b in [n.node_id for n in self.nodes]:
                if b != ids[0]:
                    self.network.set_link(ids[0], b, LinkSpec(blocked=True))
            self._window_open(f"isolate-{op.nodes[0]}", "isolate",
                              [int(op.nodes[0])])
        elif op.op == "rejoin":
            for b in [n.node_id for n in self.nodes]:
                if b != ids[0]:
                    self.network.set_link(ids[0], b, None)
            self._window_close(f"isolate-{op.nodes[0]}")
            self.heal_times_ns.append(self.clock.wall_ns())
        elif op.op == "crash":
            await self._crash_op(op)
        elif op.op == "restart":
            await self._restart(int(op.nodes[0]))
        elif op.op == "flood":
            await self._flood_op(op)
        elif op.op == "compile_storm":
            await self._compile_storm_op(op)
        elif op.op == "flap":
            await self._flap_op(op)

    # -- remediation-trigger injections ----------------------------------
    def _inject_targets(self, op) -> list[SimNode]:
        if op.nodes:
            return [self.nodes[int(i)] for i in op.nodes]
        return [n for n in self.nodes
                if not n.crashed and n.index not in self._byzantine]

    async def _flood_op(self, op) -> None:
        """Overload: saturate the targets' verify-queue signal while the
        load driver spikes real offered traffic — the detector escalates,
        the controller sheds, and admission must recover after."""
        targets = self._inject_targets(op)
        duration = op.duration_s or 3.0
        depth = op.queue_depth or 4096
        self._window_open("flood", "flood",
                          [n.index for n in targets])
        self._load_factor = op.load_multiplier or 5.0
        for n in targets:
            n.fault_inject["verify_queue_depth"] = depth
        try:
            await asyncio.sleep(duration)
        finally:
            for n in targets:
                n.fault_inject.pop("verify_queue_depth", None)
            self._load_factor = 1.0
            self._window_close("flood")

    async def _compile_storm_op(self, op) -> None:
        """Cache-wipe signal: inject cold-compile growth so the
        compile_storm detector escalates and the controller's
        rate-limited re-warm fires."""
        targets = self._inject_targets(op)
        duration = op.duration_s or 3.0
        growth = op.cold_compiles or 5
        self._window_open("compile_storm", "compile_storm",
                          [n.index for n in targets])
        for n in targets:
            n.fault_inject["cold_compiles"] = growth
        try:
            await asyncio.sleep(duration)
        finally:
            for n in targets:
                n.fault_inject.pop("cold_compiles", None)
            self._window_close("compile_storm")

    async def _flap_op(self, op) -> None:
        """Link churn: sever the victim's connections every period so
        its peers' dial ladders accumulate flaps, the peer_flap detector
        escalates, and the controller evicts + quarantines — ending the
        dial-flap-dial loop the keeper would otherwise run forever."""
        index = int(op.nodes[0])
        victim = self.nodes[index]
        duration = op.duration_s or 4.0
        period = op.period_s or 0.4
        self._window_open(f"flap-{index}", "flap", [index])
        loop = asyncio.get_running_loop()
        t_end = loop.time() + duration
        try:
            while loop.time() < t_end:
                if not victim.crashed:
                    await self.network.churn_node(victim.node_id)
                await asyncio.sleep(period)
        finally:
            self._window_close(f"flap-{index}")

    async def _crash_op(self, op) -> None:
        index = int(op.nodes[0])
        node = self.nodes[index]
        if node.crashed:
            return
        self._window_open(f"crash-{index}", "crash", [index])
        if op.fail_label or op.fail_index:
            # arm the fail point and wait for the consensus task to die
            # on it (the crash watcher does the teardown).  Bounded: the
            # fail point only fires on the node's NEXT matching call, so
            # a victim already past the trigger height (or a stalled net)
            # might never make one — fall back to a hard crash instead of
            # spin-waiting the run out.
            labels = [op.fail_label] if op.fail_label else None
            fail.install(node.name, op.fail_index, labels=labels)
            deadline = asyncio.get_running_loop().time() + 30.0
            while not node.crashed:
                if asyncio.get_running_loop().time() > deadline:
                    fail.uninstall(node.name)
                    self.fault_log.append({
                        "op": "crash-fallback", "nodes": [node.index],
                        "label": op.fail_label, "t_ns": self.clock.wall_ns()})
                    await node.crash()
                    break
                await asyncio.sleep(0.05)
        else:
            await node.crash()
        if op.restart_after_s >= 0:
            await asyncio.sleep(op.restart_after_s)
            await self._restart(index)

    async def _crash_watcher(self) -> None:
        """Reap nodes whose consensus task died on an armed fail point:
        finish the abrupt teardown so the net sees a full process death,
        not a zombie with live gossip tasks."""
        while True:
            for node in self.nodes:
                if node.crashed:
                    continue
                exc = node.consensus_dead()
                if isinstance(exc, fail.FailPointCrash):
                    self.logger.info("fail point fired", node=node.name,
                                     label=exc.label, index=exc.index)
                    self.fault_log.append({
                        "op": "fail-point", "nodes": [node.index],
                        "label": exc.label, "index": exc.index,
                        "t_ns": self.clock.wall_ns(),
                    })
                    node.cs._task = None  # consumed; crash() re-cancel is moot
                    await node.crash()
                elif exc is not None:
                    # a consensus task dying on a real exception is a harness
                    # finding, not a scheduled fault: record it, leave the
                    # node in the honest set (its stalled height fails the
                    # progress invariant instead of being excused)
                    self.logger.error("consensus task died", node=node.name,
                                      err=repr(exc))
                    self.fault_log.append({
                        "op": "consensus-died", "nodes": [node.index],
                        "error": repr(exc), "t_ns": self.clock.wall_ns(),
                    })
                    node.cs._task = None  # report once
            await asyncio.sleep(0.05)

    async def _restart(self, index: int) -> None:
        old = self.nodes[index]
        if not old.crashed:
            return
        self.restarts[index] = self.restarts.get(index, 0) + 1
        node = self._make_node(index)
        self.nodes[index] = node
        if node.health.enabled:
            # the new incarnation's watchdog inherits every still-open
            # fault window (its own crash window included) so its
            # resync-time transitions read back as excused
            for _ in self._open_windows:
                node.health.fault_begin()
        self.wal_replays.setdefault(index, []).append({
            "handshake_blocks": node.handshake_blocks,
            "wal_tail_records": node.wal_tail_records,
            "height_at_restart": node.height(),
        })
        await node.start()
        self._window_close(f"crash-{index}")
        self.heal_times_ns.append(self.clock.wall_ns())


async def run_scenario_async(scenario: Scenario, root: str,
                             logger: Logger | None = None) -> dict:
    return await SimnetRunner(scenario, root, logger=logger).run()


def run_scenario(scenario: Scenario, root: str,
                 logger: Logger | None = None) -> dict:
    """Synchronous entry point (CLI, bench, tests).  `time = "wall"`
    scenarios run exactly as before; `time = "virtual"` runs on the
    discrete-event scheduler with the VirtualClock installed as the
    process clock for the duration (simnet/vclock.py)."""
    if scenario.time == "virtual":
        from .vclock import run_in_virtual_time

        return run_in_virtual_time(
            lambda: run_scenario_async(scenario, root, logger=logger),
            seed=scenario.seed)
    return asyncio.run(run_scenario_async(scenario, root, logger=logger))
