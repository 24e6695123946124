"""The Tendermint BFT consensus state machine, as an asyncio actor.

Parity: reference consensus/state.go:84-2240 — step transitions
enterNewRound (:908) → enterPropose (:990) → enterPrevote (:1161) →
enterPrevoteWait (:1222) → enterPrecommit (:1256) → enterPrecommitWait
(:1368) → enterCommit (:1395) → finalizeCommit (:1490), POL
locking/unlocking (:1960-2000), WAL-before-act discipline (:730-751),
proposer timeout escalation, updateToState (:565) + scheduleRound0.

Design (tpu-first, SURVEY §7): where the reference serializes everything
through receiveRoutine's channel select, this class is a single-task
async actor — `receive_loop` selects over (peer queue, internal queue,
timeout tock) and dispatches into the same synchronous transition
functions the reference has, so the FSM itself is deterministic and
directly unit-testable without a running loop.  Vote verification runs
through VoteSet.add_votes → BatchVerifier, so every vote slice a
scheduler tick delivers becomes ONE device call (reference verifies one
signature inline per addVote, types/vote_set.go:203).
"""

from __future__ import annotations

import asyncio

from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import State
from tendermint_tpu.utils import clock as _clock
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.utils import txlife as _txlife
from tendermint_tpu.utils.metrics import Histogram
from tendermint_tpu.types import (
    Block,
    BlockID,
    Commit,
    Proposal,
    Vote,
)
from tendermint_tpu.types.basic import PartSetHeader, SignedMsgType, now_ns
from tendermint_tpu.types.part_set import Part, PartSet
from tendermint_tpu.types.vote_set import ConflictingVoteError, VoteSet
from tendermint_tpu.utils.fail import fail_point
from tendermint_tpu.utils.log import Logger, nop_logger

from .config import ConsensusConfig
from .messages import (
    BlockPartMessage,
    EndHeightMessage,
    MsgInfo,
    ProposalMessage,
    TimeoutInfo,
    VoteMessage,
)
from . import eventlog
from .round_state import HeightVoteSet, RoundState, Step
from .ticker import TimeoutTicker
from .wal import NopWAL

TIME_IOTA_NS = 1_000_000  # 1ms minimum inter-block time grain

# Matches upstream Tendermint's consensus_step_duration_seconds
# (consensus/metrics.go StepDuration): time spent in each FSM step,
# labeled by the step being LEFT.  Process-wide like the verify-service
# histograms; node/metrics.py registers it for /metrics exposition.
# Observed only at step transitions (a handful per block), so this does
# not violate the "no metrics code in the hot path" rule.
STEP_DURATION_SECONDS = Histogram(
    "step_duration_seconds",
    "Time spent per consensus step, labeled by the step being left",
    namespace="tendermint", subsystem="consensus",
    label_names=("step",),
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0, 30.0),
)


class ConsensusFailureError(Exception):
    """Unrecoverable consensus-safety failure: the node must halt rather
    than continue in an inconsistent state (the reference panics —
    state.go:700-713, :1540-1557)."""


class ConsensusState:
    def __init__(
        self,
        config: ConsensusConfig,
        state: State,
        block_exec: BlockExecutor,
        block_store,
        wal=None,
        priv_validator=None,
        evidence_pool=None,
        logger: Logger | None = None,
    ):
        self.config = config
        self.block_exec = block_exec
        self.block_store = block_store
        self.wal = wal if wal is not None else NopWAL()
        self.priv_validator = priv_validator
        self.evpool = evidence_pool
        self.logger = logger or nop_logger()

        self.rs = RoundState()
        self.state: State | None = None  # sm.State as of last commit

        self.peer_msg_queue: asyncio.Queue[MsgInfo] = asyncio.Queue(maxsize=1000)
        self.internal_msg_queue: asyncio.Queue[MsgInfo] = asyncio.Queue(maxsize=1000)
        self.ticker = TimeoutTicker()
        self.replay_mode = False
        self._tx_notifier = None  # Mempool with txs_available enabled
        self.done_height: asyncio.Event = asyncio.Event()  # pulsed every commit
        self.on_event = None  # callable(name: str, payload) — reactor hook
        self.event_bus = None  # types.events.EventBus — external observers
        # structured event journal (consensus/eventlog.py): NOP unless the
        # node wires a real one; every site guards on `.enabled` so the
        # disabled path costs one branch (bench.py journal-overhead stage)
        self.journal = eventlog.NOP
        # tx lifecycle store (utils/txlife.py): NOP unless the node wires
        # one; same one-branch-when-off contract as the journal
        self.lifecycle = _txlife.NOP
        self._task: asyncio.Task | None = None
        self._stopping = False
        self._step_t0: float | None = None  # when the current step began
        # quorum-wait anchors: "prevote"/"precommit" -> (h, r, mono t0),
        # set when this node enters the step (casts its own vote) and
        # consumed when the matching +2/3 quorum forms
        self._quorum_t0: dict[str, tuple[int, int, float]] = {}

        self.reconstruct_last_commit(state)
        self.update_to_state(state)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    async def start(self) -> None:
        """WAL catchup replay, then launch the receive loop."""
        self.catchup_replay()
        self._task = asyncio.get_running_loop().create_task(self.receive_loop())
        self.schedule_round_0()

    async def stop(self) -> None:
        self._stopping = True
        self.ticker.stop()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        self.wal.close()
        self.journal.close()

    # ------------------------------------------------------------------
    # external API (reactor / RPC entry points)
    # ------------------------------------------------------------------

    def set_tx_notifier(self, mempool) -> None:
        """Wire the mempool's txs-available signal into the receive loop
        (needed for create_empty_blocks=False; reference txNotifier,
        state.go:143 + handleTxsAvailable :874)."""
        mempool.enable_txs_available()
        self._tx_notifier = mempool

    def send_internal(self, msg) -> None:
        self.internal_msg_queue.put_nowait(MsgInfo(msg, ""))

    async def add_peer_message(self, msg, peer_id: str) -> None:
        await self.peer_msg_queue.put(MsgInfo(msg, peer_id))

    def is_proposer(self, address: bytes) -> bool:
        return self.rs.validators.get_proposer().address == address

    def privval_address(self) -> bytes | None:
        if self.priv_validator is None:
            return None
        return self.priv_validator.get_pub_key().address()

    # ------------------------------------------------------------------
    # the serialization point (reference receiveRoutine, state.go:685)
    # ------------------------------------------------------------------

    async def receive_loop(self) -> None:
        while not self._stopping:
            peer_get = asyncio.ensure_future(self.peer_msg_queue.get())
            internal_get = asyncio.ensure_future(self.internal_msg_queue.get())
            tock_get = asyncio.ensure_future(self.ticker.tock.get())
            waiters = [peer_get, internal_get, tock_get]
            txs_get = None
            if self._tx_notifier is not None:
                txs_get = asyncio.ensure_future(self._tx_notifier.txs_available().wait())
                waiters.append(txs_get)
            try:
                done, pending = await asyncio.wait(
                    waiters, return_when=asyncio.FIRST_COMPLETED
                )
            finally:
                # also reached via task cancellation from stop(): never
                # orphan the getter tasks
                for w in waiters:
                    if not w.done():
                        w.cancel()
            if txs_get is not None and txs_get in done:
                self.handle_txs_available()
            for d in done:
                if d is txs_get:
                    continue
                item = d.result()
                try:
                    if d is tock_get:
                        self.wal.write(item)
                        self.handle_timeout(item)
                    elif d is internal_get:
                        # own votes/proposals must hit disk before dispatch
                        # (crash ⇒ no double-sign; reference state.go:741-751)
                        self.wal.write_sync(item)
                        fail_point("own-msg-fsynced")  # reference state.go:747 (own msg fsynced)
                        # errors here (e.g. a locally built oversized
                        # proposal) fall through to the outer log-and-
                        # continue handler — same containment as the peer
                        # batch below (reference state.go returns the error
                        # from addProposalBlockPart)
                        self.handle_msg(item)
                    else:
                        # drain everything else that arrived this tick and
                        # batch-verify all vote signatures in it as one
                        # device call (SURVEY §7 stage 6); each message is
                        # then processed in arrival order exactly as the
                        # sequential path would
                        batch = [item]
                        while len(batch) < 256:
                            try:
                                batch.append(self.peer_msg_queue.get_nowait())
                            except asyncio.QueueEmpty:
                                break
                        if len(batch) > 1:
                            self._precheck_vote_sigs(batch)
                        for mi in batch:
                            try:
                                self.wal.write(mi)
                                self.handle_msg(mi)
                            except (ConsensusFailureError, OSError):
                                raise
                            except Exception as e:
                                # one bad peer message must not drop the
                                # rest of the tick's batch
                                self.logger.error("consensus msg error",
                                                  err=repr(e))
                except (ConsensusFailureError, OSError):
                    # safety failures (broken commit path, WAL/disk errors)
                    # halt the node — continuing could double-sign or fork
                    # (the reference panics here)
                    self.logger.error("CONSENSUS FAILURE — halting")
                    self._stopping = True
                    raise
                except Exception as e:
                    # bad peer input must not kill consensus: log and go on
                    self.logger.error("consensus msg error", err=repr(e))

    def _precheck_vote_sigs(self, batch: list[MsgInfo]) -> None:
        """Verify the signatures of every vote in this tick's peer
        messages as ONE batched call (SURVEY §7 stage 6: amortize device
        dispatch across the scheduler tick).  Valid signatures are marked
        on the vote so the per-vote verify in VoteSet.add_vote
        short-circuits; invalid ones are NOT marked and fail identically
        in the sequential path.  Pure crypto — no consensus state is
        touched, so WAL-before-act ordering is unaffected.  Never raises:
        any backend failure just means no markers, and every message
        still flows through the per-vote path."""
        from tendermint_tpu.types.vote import batch_verify_votes

        rs = self.rs
        jobs = []
        for mi in batch:
            m = mi.msg
            if not isinstance(m, VoteMessage):
                continue
            v = m.vote
            if v.height == rs.height:
                vals = rs.validators
            elif v.height + 1 == rs.height and v.type == SignedMsgType.PRECOMMIT:
                vals = rs.last_validators  # late precommits for H-1
            else:
                continue
            if vals is None or not (0 <= v.validator_index < vals.size()):
                continue
            val = vals.get_by_index(v.validator_index)
            if val is None or val.address != v.validator_address:
                continue
            # gossip floods re-deliver admitted votes (every peer relays
            # until it sees our HasVote): skip their crypto here —
            # add_vote's duplicate check drops them without verifying.
            # Without this, a 20-node simnet burned ~47x the necessary
            # signature verifications and starved the event loop.
            if v.height == rs.height:
                if rs.votes.has_exact(v):
                    continue
            elif rs.last_commit is not None and rs.last_commit.has_exact(v):
                continue
            jobs.append((v, val.pub_key))
        if len(jobs) < 2:
            return  # nothing to amortize
        chain_id = self.state.chain_id
        try:
            oks = batch_verify_votes(chain_id, jobs)
            for (v, pk), ok in zip(jobs, oks):
                if ok:
                    v.mark_sig_verified(chain_id, pk)
        except Exception as e:
            # a transient backend failure (device OOM, a failed compile)
            # must not drop the drained tick: without markers every vote simply
            # re-verifies individually
            self.logger.error("vote precheck batch failed", err=repr(e))

    def handle_msg(self, mi: MsgInfo) -> None:
        msg, peer_id = mi.msg, mi.peer_id
        if isinstance(msg, ProposalMessage):
            self.set_proposal(msg.proposal, peer_id)
        elif isinstance(msg, BlockPartMessage):
            self.add_proposal_block_part(msg.height, msg.part, peer_id)
        elif isinstance(msg, VoteMessage):
            self.try_add_vote(msg.vote, peer_id)
        else:
            self.logger.error("unknown msg type", type=type(msg).__name__)

    def handle_txs_available(self) -> None:
        """Reference handleTxsAvailable (state.go:874): only relevant at
        round 0 while waiting for txs."""
        if self._tx_notifier is not None:
            self._tx_notifier.txs_available().clear()
        rs = self.rs
        if rs.round != 0:
            return
        if rs.step == Step.NEW_HEIGHT:
            # still inside timeout_commit: re-arm a NEW_ROUND tick for the
            # remainder so propose starts promptly once it elapses
            remaining_ms = max(0, (rs.start_time_ns - now_ns()) // 1_000_000) + 1
            self._schedule(remaining_ms, rs.height, 0, Step.NEW_ROUND)
        elif rs.step == Step.NEW_ROUND:
            self.enter_propose(rs.height, 0)

    def handle_timeout(self, ti: TimeoutInfo) -> None:
        """Reference handleTimeout (state.go:832): drop stale ticks, then
        drive the step the timeout was armed for."""
        rs = self.rs
        if ti.height != rs.height or ti.round < rs.round or (
            ti.round == rs.round and ti.step < int(rs.step)
        ):
            return
        step = Step(ti.step)
        if self.journal.enabled and not self.replay_mode:
            self.journal.log("timeout", h=ti.height, r=ti.round,
                             step=step.name, dur_ms=ti.duration_ms)
        if step == Step.NEW_HEIGHT:
            self.enter_new_round(ti.height, 0)
        elif step == Step.NEW_ROUND:
            self.enter_propose(ti.height, 0)
        elif step == Step.PROPOSE:
            self._publish_timeout("propose")
            self.enter_prevote(ti.height, ti.round)
        elif step == Step.PREVOTE_WAIT:
            self._publish_timeout("wait")
            self.enter_precommit(ti.height, ti.round)
        elif step == Step.PRECOMMIT_WAIT:
            self._publish_timeout("wait")
            self.enter_precommit(ti.height, ti.round)
            self.enter_new_round(ti.height, ti.round + 1)

    def _publish_timeout(self, kind: str) -> None:
        if self.event_bus is None or self.replay_mode:
            return
        from tendermint_tpu.types import events as tmevents

        rs = tmevents.EventDataRoundState(self.rs.height, self.rs.round, self.rs.step.name)
        if kind == "propose":
            self.event_bus.publish_timeout_propose(rs)
        else:
            self.event_bus.publish_timeout_wait(rs)

    # ------------------------------------------------------------------
    # state resets
    # ------------------------------------------------------------------

    def reconstruct_last_commit(self, state: State) -> None:
        """Rebuild LastCommit VoteSet from the stored seen-commit on
        restart (reference state.go:548-563 via CommitToVoteSet)."""
        if state.last_block_height == 0:
            return
        seen = self.block_store.load_seen_commit(state.last_block_height)
        if seen is None:
            raise RuntimeError(
                f"no seen commit for height {state.last_block_height}"
            )
        from tendermint_tpu.types.vote_set import commit_to_vote_set

        vs = commit_to_vote_set(state.chain_id, seen, state.last_validators)
        if not vs.has_two_thirds_majority():
            raise RuntimeError("reconstructed last commit lacks +2/3")
        self.rs.last_commit = vs

    def update_to_state(self, state: State) -> None:
        """Reference updateToState (state.go:565): prime the RoundState
        for height state.last_block_height+1."""
        rs = self.rs
        if rs.commit_round > -1 and 0 < rs.height and rs.height != state.last_block_height:
            raise RuntimeError(
                f"update_to_state at height {rs.height} != state height "
                f"{state.last_block_height}"
            )
        last_precommits: VoteSet | None = None
        if rs.commit_round > -1 and rs.votes is not None:
            pc = rs.votes.precommits(rs.commit_round)
            if pc is None or not pc.has_two_thirds_majority():
                raise RuntimeError("commit round has no +2/3 precommits")
            last_precommits = pc
        elif rs.last_commit is not None:
            last_precommits = rs.last_commit

        height = state.last_block_height + 1
        if height == 1:
            height = state.initial_height

        self._observe_step()  # COMMIT (or startup) -> NEW_HEIGHT
        prev_step = rs.step
        rs.height = height
        rs.round = 0
        rs.step = Step.NEW_HEIGHT
        if _trace.enabled() and not self.replay_mode:
            _trace.instant("consensus.new_height", height=height)
        if self.journal.enabled and not self.replay_mode:
            self.journal.log("step", h=height, r=0,
                             step=Step.NEW_HEIGHT.name, prev=prev_step.name)
        if rs.commit_time_ns == 0:
            rs.start_time_ns = now_ns() + self.config.timeout_commit_ms * 1_000_000
        else:
            rs.start_time_ns = rs.commit_time_ns + self.config.timeout_commit_ms * 1_000_000
        rs.validators = state.validators
        rs.proposal = None
        rs.proposal_block = None
        rs.proposal_block_parts = None
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        rs.valid_round = -1
        rs.valid_block = None
        rs.valid_block_parts = None
        rs.votes = HeightVoteSet(state.chain_id, height, state.validators)
        rs.commit_round = -1
        rs.last_commit = last_precommits
        rs.last_validators = state.last_validators
        rs.triggered_timeout_precommit = False
        self.state = state
        self._emit("new_round_step")

    def schedule_round_0(self) -> None:
        sleep_ms = max(0, (self.rs.start_time_ns - now_ns()) // 1_000_000)
        self.ticker.schedule_timeout(
            TimeoutInfo(sleep_ms, self.rs.height, 0, int(Step.NEW_HEIGHT))
        )

    def _update_round_step(self, round_: int, step: Step) -> None:
        if not self.replay_mode:
            pass  # (reference fires newStep events here)
        self._observe_step()
        prev = self.rs.step
        self.rs.round = round_
        self.rs.step = step
        # perf stamps ride the pluggable clock seam: the derived wait_ms
        # lands on journal polka/commit_maj lines, which a virtual-time
        # simnet run must reproduce byte-for-byte across same-seed runs
        if step == Step.PREVOTE:
            self._quorum_t0["prevote"] = (
                self.rs.height, round_, _clock.perf())
        elif step == Step.PRECOMMIT:
            self._quorum_t0["precommit"] = (
                self.rs.height, round_, _clock.perf())
        if self.journal.enabled and not self.replay_mode:
            self.journal.log("step", h=self.rs.height, r=round_,
                             step=step.name, prev=prev.name)
        self._emit("new_round_step")

    def _quorum_wait(self, kind: str, height: int, round_: int) -> float | None:
        """Seconds from this node entering the `kind` vote step (casting
        its own vote) to the +2/3 quorum forming — observed once per
        quorum into QUORUM_WAIT_SECONDS.  None (no observation) when the
        anchor is missing or belongs to another (height, round), e.g.
        after a round skip, or during WAL replay."""
        ent = self._quorum_t0.pop(kind, None)
        if ent is None or self.replay_mode:
            return None
        h, r, t0 = ent
        if h != height or r != round_:
            return None
        dt = _clock.perf() - t0
        _txlife.QUORUM_WAIT_SECONDS.observe(dt, type=kind)
        return dt

    def _observe_step(self) -> None:
        """Record how long the step we are leaving lasted — the
        step_duration histogram plus (when tracing) a complete span
        carrying height/round.  WAL replay transitions are synthetic and
        are excluded, same as event publication."""
        now = _clock.perf()
        t0, self._step_t0 = self._step_t0, now
        if self.replay_mode or t0 is None:
            return
        prev = self.rs.step
        STEP_DURATION_SECONDS.observe(now - t0, step=prev.name)
        if _trace.enabled():
            _trace.record("consensus.step", t0, now - t0, step=prev.name,
                          height=self.rs.height, round=self.rs.round)

    def _emit(self, name: str, payload=None) -> None:
        if self.on_event is not None:
            self.on_event(name, payload if payload is not None else self.rs)
        if self.event_bus is not None and not self.replay_mode:
            self._publish_event(name, payload)

    def _publish_event(self, name: str, payload) -> None:
        """Mirror reactor-hook events onto the EventBus (reference
        consensus/state.go publishes EventDataRoundState family via the
        bus at the same transition points)."""
        from tendermint_tpu.types import events as tmevents

        rs = tmevents.EventDataRoundState(self.rs.height, self.rs.round, self.rs.step.name)
        bus = self.event_bus
        if name == "new_round_step":
            bus.publish_new_round_step(rs)
        elif name == "polka":
            bus.publish_polka(rs)
        elif name == "lock":
            bus.publish_lock(rs)
        elif name == "relock":
            bus.publish_relock(rs)
        elif name == "unlock":
            bus.publish_unlock(rs)
        elif name == "valid_block":
            bus.publish_valid_block(rs)
        elif name == "complete_proposal":
            block = payload
            bid = None
            if block is not None:
                from tendermint_tpu.types.basic import BlockID

                bid = BlockID(block.hash(), self.rs.proposal_block_parts.header())
            bus.publish_complete_proposal(
                tmevents.EventDataCompleteProposal(
                    self.rs.height, self.rs.round, self.rs.step.name, bid
                )
            )
        elif name == "vote":
            bus.publish_vote(payload)

    def _schedule(self, duration_ms: int, height: int, round_: int, step: Step) -> None:
        self.ticker.schedule_timeout(TimeoutInfo(duration_ms, height, round_, int(step)))

    # ------------------------------------------------------------------
    # step transitions
    # ------------------------------------------------------------------

    def enter_new_round(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step != Step.NEW_HEIGHT
        ):
            return
        validators = rs.validators
        if rs.round < round_:
            validators = validators.copy_increment_proposer_priority(round_ - rs.round)
        rs.validators = validators
        if _trace.enabled() and not self.replay_mode:
            _trace.instant("consensus.new_round", height=height, round=round_)
        if self.journal.enabled and not self.replay_mode:
            prop = validators.get_proposer()
            self.journal.log(
                "new_round", h=height, r=round_,
                proposer=prop.address.hex() if prop else "",
                val=(validators.get_by_address(prop.address)[0]
                     if prop else -1),
            )
        self._update_round_step(round_, Step.NEW_ROUND)
        if round_ != 0:
            # round 0 keeps proposals from NewHeight; later rounds start over
            rs.proposal = None
            rs.proposal_block = None
            rs.proposal_block_parts = None
        rs.votes.set_round(round_ + 1)
        rs.triggered_timeout_precommit = False
        if self.event_bus is not None and not self.replay_mode:
            from tendermint_tpu.types import events as tmevents

            proposer = rs.validators.get_proposer()
            self.event_bus.publish_new_round(
                tmevents.EventDataNewRound(
                    height,
                    round_,
                    Step.NEW_ROUND.name,
                    proposer.address if proposer else b"",
                    rs.validators.get_by_address(proposer.address)[0] if proposer else -1,
                )
            )

        wait_for_txs = (
            not self.config.create_empty_blocks and round_ == 0 and not self._txs_available()
        )
        if wait_for_txs:
            if self.config.create_empty_blocks_interval_ms > 0:
                self._schedule(
                    self.config.create_empty_blocks_interval_ms,
                    height,
                    round_,
                    Step.NEW_ROUND,
                )
        else:
            self.enter_propose(height, round_)

    def _txs_available(self) -> bool:
        mp = self.block_exec.mempool
        size = getattr(mp, "size", None)
        return bool(size and size())

    def enter_propose(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and Step.PROPOSE <= rs.step
        ):
            return
        try:
            self._schedule(
                self.config.propose_timeout(round_), height, round_, Step.PROPOSE
            )
            addr = self.privval_address()
            if addr is None:
                return
            if not rs.validators.has_address(addr):
                return  # not a validator
            if self.is_proposer(addr):
                self.decide_proposal(height, round_)
        finally:
            self._update_round_step(round_, Step.PROPOSE)
            if self.is_proposal_complete():
                self.enter_prevote(height, rs.round)

    def decide_proposal(self, height: int, round_: int) -> None:
        """Reference defaultDecideProposal (state.go:1062)."""
        rs = self.rs
        if rs.valid_block is not None:
            block, block_parts = rs.valid_block, rs.valid_block_parts
        else:
            block = self.create_proposal_block()
            if block is None:
                return
            block_parts = block.make_part_set()
        prop_block_id = BlockID(hash=block.hash(), part_set_header=block_parts.header())
        proposal = Proposal(
            height=height,
            round=round_,
            pol_round=rs.valid_round,
            block_id=prop_block_id,
            timestamp_ns=now_ns(),
        )
        try:
            self.priv_validator.sign_proposal(self.state.chain_id, proposal)
        except Exception as e:
            self.logger.error("failed signing proposal", err=str(e))
            return
        self.send_internal(ProposalMessage(proposal))
        for i in range(block_parts.total):
            self.send_internal(BlockPartMessage(height, round_, block_parts.get_part(i)))

    def create_proposal_block(self) -> Block | None:
        rs = self.rs
        if rs.height == self.state.initial_height:
            commit = Commit(
                height=0, round=0, block_id=BlockID(), signatures=[]
            )
        elif rs.last_commit is not None and rs.last_commit.has_two_thirds_majority():
            commit = rs.last_commit.make_commit()
        else:
            self.logger.error("cannot propose: no last commit")
            return None
        addr = self.privval_address()
        return self.block_exec.create_proposal_block(rs.height, self.state, commit, addr)

    def is_proposal_complete(self) -> bool:
        rs = self.rs
        if rs.proposal is None or rs.proposal_block is None:
            return False
        if rs.proposal.pol_round < 0:
            return True
        prevotes = rs.votes.prevotes(rs.proposal.pol_round)
        return prevotes is not None and prevotes.has_two_thirds_majority()

    def enter_prevote(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and Step.PREVOTE <= rs.step
        ):
            return
        self._update_round_step(round_, Step.PREVOTE)
        self.do_prevote(height, round_)

    def do_prevote(self, height: int, round_: int) -> None:
        """Reference defaultDoPrevote (state.go:1188)."""
        rs = self.rs
        if rs.locked_block is not None:
            self.sign_add_vote(
                SignedMsgType.PREVOTE,
                rs.locked_block.hash(),
                rs.locked_block_parts.header(),
            )
            return
        if rs.proposal_block is None:
            self.sign_add_vote(SignedMsgType.PREVOTE, b"", PartSetHeader())
            return
        try:
            self.block_exec.validate_block(self.state, rs.proposal_block)
        except Exception as e:
            self.logger.error("prevote nil: invalid proposal block", err=str(e))
            self.sign_add_vote(SignedMsgType.PREVOTE, b"", PartSetHeader())
            return
        self.sign_add_vote(
            SignedMsgType.PREVOTE,
            rs.proposal_block.hash(),
            rs.proposal_block_parts.header(),
        )

    def enter_prevote_wait(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and Step.PREVOTE_WAIT <= rs.step
        ):
            return
        prevotes = rs.votes.prevotes(round_)
        if prevotes is None or not prevotes.has_two_thirds_any():
            raise RuntimeError("enter_prevote_wait without +2/3 prevotes any")
        self._update_round_step(round_, Step.PREVOTE_WAIT)
        self._schedule(
            self.config.prevote_timeout(round_), height, round_, Step.PREVOTE_WAIT
        )

    def enter_precommit(self, height: int, round_: int) -> None:
        """Reference enterPrecommit (state.go:1256): lock/unlock per the
        prevote polka."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and Step.PRECOMMIT <= rs.step
        ):
            return
        self._update_round_step(round_, Step.PRECOMMIT)
        prevotes = rs.votes.prevotes(round_)
        block_id = prevotes.two_thirds_majority() if prevotes else None

        if block_id is None:
            # no polka: precommit nil
            self.sign_add_vote(SignedMsgType.PRECOMMIT, b"", PartSetHeader())
            return

        wait_s = self._quorum_wait("prevote", height, round_)
        if self.journal.enabled and not self.replay_mode:
            fields = {"h": height, "r": round_,
                      "block": block_id.hash[:8].hex()}
            if wait_s is not None:
                fields["wait_ms"] = round(wait_s * 1e3, 3)
            self.journal.log("polka", **fields)
        self._emit("polka", block_id)

        if block_id.is_zero():
            # +2/3 prevoted nil: unlock
            if rs.locked_block is not None:
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                self._emit("unlock")
            self.sign_add_vote(SignedMsgType.PRECOMMIT, b"", PartSetHeader())
            return

        if rs.locked_block is not None and rs.locked_block.hash() == block_id.hash:
            # re-lock on same block at this round
            rs.locked_round = round_
            self._emit("relock")
            self.sign_add_vote(
                SignedMsgType.PRECOMMIT, block_id.hash, block_id.part_set_header
            )
            return

        if rs.proposal_block is not None and rs.proposal_block.hash() == block_id.hash:
            try:
                self.block_exec.validate_block(self.state, rs.proposal_block)
            except Exception as e:
                raise RuntimeError(f"+2/3 prevoted an invalid block: {e}")
            rs.locked_round = round_
            rs.locked_block = rs.proposal_block
            rs.locked_block_parts = rs.proposal_block_parts
            self._emit("lock")
            self.sign_add_vote(
                SignedMsgType.PRECOMMIT, block_id.hash, block_id.part_set_header
            )
            return

        # polka for a block we don't have: unlock, fetch it, precommit nil
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
            block_id.part_set_header
        ):
            rs.proposal_block = None
            rs.proposal_block_parts = PartSet(block_id.part_set_header)
        self._emit("unlock")
        self.sign_add_vote(SignedMsgType.PRECOMMIT, b"", PartSetHeader())

    def enter_precommit_wait(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.triggered_timeout_precommit
        ):
            return
        precommits = rs.votes.precommits(round_)
        if precommits is None or not precommits.has_two_thirds_any():
            raise RuntimeError("enter_precommit_wait without +2/3 precommits any")
        rs.triggered_timeout_precommit = True
        self._schedule(
            self.config.precommit_timeout(round_), height, round_, Step.PRECOMMIT_WAIT
        )

    def enter_commit(self, height: int, commit_round: int) -> None:
        rs = self.rs
        if rs.height != height or Step.COMMIT <= rs.step:
            return
        block_id = rs.votes.precommits(commit_round).two_thirds_majority()
        if block_id is None or block_id.is_zero():
            raise RuntimeError("enter_commit without +2/3 precommits for a block")
        rs.commit_round = commit_round
        rs.commit_time_ns = now_ns()
        wait_s = self._quorum_wait("precommit", height, commit_round)
        if self.journal.enabled and not self.replay_mode:
            fields = {"h": height, "r": commit_round,
                      "block": block_id.hash[:8].hex()}
            if wait_s is not None:
                fields["wait_ms"] = round(wait_s * 1e3, 3)
            self.journal.log("commit_maj", **fields)
        self._update_round_step(rs.round, Step.COMMIT)

        if rs.locked_block is not None and rs.locked_block.hash() == block_id.hash:
            rs.proposal_block = rs.locked_block
            rs.proposal_block_parts = rs.locked_block_parts
        if rs.proposal_block is None or rs.proposal_block.hash() != block_id.hash:
            if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                block_id.part_set_header
            ):
                # we don't have the committed block yet; wait for parts
                rs.proposal_block = None
                rs.proposal_block_parts = PartSet(block_id.part_set_header)
                self._emit("valid_block")
        self.try_finalize_commit(height)

    def try_finalize_commit(self, height: int) -> None:
        rs = self.rs
        if rs.height != height:
            raise RuntimeError("try_finalize_commit height mismatch")
        block_id = rs.votes.precommits(rs.commit_round).two_thirds_majority()
        if block_id is None or block_id.is_zero():
            return
        if rs.proposal_block is None or rs.proposal_block.hash() != block_id.hash:
            return  # still waiting for the block
        self.finalize_commit(height)

    def finalize_commit(self, height: int) -> None:
        """Reference finalizeCommit (state.go:1490): save → WAL barrier →
        apply → advance."""
        rs = self.rs
        if rs.height != height or rs.step != Step.COMMIT:
            return
        block_id = rs.votes.precommits(rs.commit_round).two_thirds_majority()
        block, block_parts = rs.proposal_block, rs.proposal_block_parts
        block.validate_basic()
        self.block_exec.validate_block(self.state, block)

        # from here on, failure is a safety violation: +2/3 precommitted
        # this block, so an error storing/applying it must halt the node
        try:
            fail_point("commit-before-save")  # reference state.go:1524 (before save)
            if self.block_store.height() < block.header.height:
                seen_commit = rs.votes.precommits(rs.commit_round).make_commit()
                self.block_store.save_block(block, block_parts, seen_commit)
            fail_point("commit-after-save")  # reference state.go:1538 (saved, before WAL barrier)

            # crash barrier: replay resumes AFTER this record (reference
            # state.go:1540-1557)
            self.wal.write_sync(EndHeightMessage(height))
            fail_point("commit-after-barrier")  # reference state.go:1559 (barrier written, before apply)

            state_copy, retain_height = self.block_exec.apply_block(
                self.state.copy(), block_id, block
            )
            fail_point("commit-after-apply")  # reference state.go:1577 (applied, before state save/advance)
        except ConsensusFailureError:
            raise
        except Exception as e:
            raise ConsensusFailureError(
                f"failed to commit block {height}: {e}"
            ) from e
        if self.journal.enabled and not self.replay_mode:
            self.journal.log("commit", h=height, r=rs.commit_round,
                             block=block_id.hash[:8].hex(),
                             txs=len(block.data.txs))
        if self.lifecycle.enabled and not self.replay_mode:
            # committed-and-applied: both milestones stamp here, after
            # the critical section (a lifecycle/journal I/O error must
            # never read as a consensus-safety failure).  `commit` closes
            # the mempool-residency window, `apply` the time-to-finality
            # one and retires the tx from the live store.
            self._stamp_block_txs(block, "commit")
            self._stamp_block_txs(block, "apply")
        if retain_height > 0:
            try:
                pruned = self.block_store.prune_blocks(retain_height)
                self.block_exec.store.prune_states(
                    self.block_store.base(), retain_height
                )
                self.logger.info("pruned blocks", count=pruned)
            except Exception as e:
                self.logger.error("prune failed", err=str(e))

        self.update_to_state(state_copy)
        ev = self.done_height
        self.done_height = asyncio.Event()
        ev.set()
        self.schedule_round_0()

    # ------------------------------------------------------------------
    # message ingestion
    # ------------------------------------------------------------------

    def _stamp_block_txs(self, block: Block, milestone: str) -> None:
        """Stamp every tx in `block` with `milestone` (lifecycle store +
        tx_* journal lines when the journal is on)."""
        from tendermint_tpu.crypto.tmhash import sum_sha256

        life = self.lifecycle
        h = block.header.height
        for tx in block.data.txs:
            # both call sites hold the `lifecycle.enabled and not
            # replay_mode` guard; this helper only shares the hash loop
            # tmlint: disable=ungated-observability
            life.stamp(sum_sha256(bytes(tx)), milestone, h=h)

    def set_proposal(self, proposal: Proposal, peer_id: str = "") -> None:
        """Reference defaultSetProposal (state.go:1719)."""
        rs = self.rs
        if rs.proposal is not None:
            return
        if proposal.height != rs.height or proposal.round != rs.round:
            return
        if proposal.pol_round < -1 or (
            proposal.pol_round >= 0 and proposal.pol_round >= proposal.round
        ):
            raise ValueError("invalid proposal POL round")
        proposer = rs.validators.get_proposer()
        if not proposal.verify(self.state.chain_id, proposer.pub_key):
            raise ValueError("invalid proposal signature")
        rs.proposal = proposal
        if rs.proposal_block_parts is None:
            rs.proposal_block_parts = PartSet(proposal.block_id.part_set_header)
        if self.journal.enabled and not self.replay_mode:
            self.journal.log(
                "proposal", h=proposal.height, r=proposal.round,
                proposer=proposer.address.hex(),
                block=proposal.block_id.hash[:8].hex(),
                pol_round=proposal.pol_round,
                **{"from": peer_id},
            )
        self._emit("proposal", proposal)

    def add_proposal_block_part(self, height: int, part: Part, peer_id: str = "") -> bool:
        """Reference addProposalBlockPart (state.go:1760). Returns True if
        the part was added."""
        rs = self.rs
        if height != rs.height:
            return False
        if rs.proposal_block_parts is None:
            return False
        added = rs.proposal_block_parts.add_part(part)
        if added and rs.proposal_block_parts.byte_size > (
            self.state.consensus_params.block.max_bytes
        ):
            # oversized proposal: drop it entirely so the round times out
            # and we prevote nil (reference state.go addProposalBlockPart's
            # ByteSize > MaxBytes error path)
            rs.proposal_block_parts = None
            raise ValueError(
                "total size of proposal block parts exceeds block.max_bytes"
            )
        if not added or not rs.proposal_block_parts.is_complete():
            return added

        rs.proposal_block = Block.decode(rs.proposal_block_parts.assemble())
        if self.lifecycle.enabled and not self.replay_mode:
            # proposal-inclusion milestone: the first time this node saw
            # each tx inside a (completed) proposed block — the proposer
            # itself assembles through the same internal-parts path
            self._stamp_block_txs(rs.proposal_block, "propose")
        self._emit("complete_proposal", rs.proposal_block)

        prevotes = rs.votes.prevotes(rs.round)
        block_id = prevotes.two_thirds_majority() if prevotes else None
        if (
            block_id is not None
            and not block_id.is_zero()
            and rs.valid_round < rs.round
            and rs.proposal_block.hash() == block_id.hash
        ):
            rs.valid_round = rs.round
            rs.valid_block = rs.proposal_block
            rs.valid_block_parts = rs.proposal_block_parts

        if rs.step <= Step.PROPOSE and self.is_proposal_complete():
            self.enter_prevote(height, rs.round)
            if block_id is not None and not block_id.is_zero():
                self.enter_precommit(height, rs.round)
        elif rs.step == Step.COMMIT:
            self.try_finalize_commit(height)
        return True

    def try_add_vote(self, vote: Vote, peer_id: str = "") -> bool:
        """Reference tryAddVote (state.go:1845): equivocation becomes
        evidence; own conflicts are logged loudly."""
        try:
            return self.add_vote(vote, peer_id)
        except ConflictingVoteError as e:
            addr = self.privval_address()
            if addr is not None and vote.validator_address == addr:
                self.logger.error(
                    "found conflicting vote from ourselves; did you restart with "
                    "a stale privval state?",
                    height=vote.height,
                )
                return False
            if self.evpool is not None:
                self.evpool.report_conflicting_votes(e.vote_a, e.vote_b)
            return False
        except ValueError as e:
            self.logger.info("bad vote", err=str(e))
            return False

    def _journal_vote(self, vote: Vote, peer_id: str) -> None:
        """One journal line per ADMITTED vote, attributed to the peer
        that delivered it ("" = our own, via the internal queue).  `at_r`
        is the round this node was in at arrival — what the timeline
        analyzer uses to flag late votes."""
        # both call sites hold the `journal.enabled and not replay_mode`
        # guard; this helper only exists to share the formatting
        # tmlint: disable=ungated-observability
        self.journal.log(
            "vote", h=vote.height, r=vote.round,
            type=("prevote" if vote.type == SignedMsgType.PREVOTE
                  else "precommit"),
            val=vote.validator_index,
            block=vote.block_id.hash[:8].hex(),
            at_r=self.rs.round,
            **{"from": peer_id},
        )

    def add_vote(self, vote: Vote, peer_id: str = "") -> bool:
        """Reference addVote (state.go:1892)."""
        rs = self.rs

        # late precommit for the previous height
        if vote.height + 1 == rs.height and vote.type == SignedMsgType.PRECOMMIT:
            if rs.step != Step.NEW_HEIGHT:
                return False
            if rs.last_commit is None:
                return False
            added = rs.last_commit.add_vote(vote)
            if added:
                if self.journal.enabled and not self.replay_mode:
                    self._journal_vote(vote, peer_id)
                self._emit("vote", vote)
                if self.config.skip_timeout_commit and rs.last_commit.has_all():
                    self.enter_new_round(rs.height, 0)
            return added

        if vote.height != rs.height:
            return False

        added = rs.votes.add_vote(vote, peer_id)
        if not added:
            return False
        if self.journal.enabled and not self.replay_mode:
            self._journal_vote(vote, peer_id)
        self._emit("vote", vote)

        if vote.type == SignedMsgType.PREVOTE:
            self._on_prevote_added(vote)
        else:
            self._on_precommit_added(vote)
        return added

    def _on_prevote_added(self, vote: Vote) -> None:
        rs = self.rs
        prevotes = rs.votes.prevotes(vote.round)
        block_id = prevotes.two_thirds_majority()
        if block_id is not None:
            # unlock on a later-round polka for a different block
            # (reference state.go:1960-1985)
            if (
                rs.locked_block is not None
                and rs.locked_round < vote.round
                and vote.round <= rs.round
                and rs.locked_block.hash() != block_id.hash
            ):
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                self._emit("unlock")
            # track the most recent valid block
            if (
                not block_id.is_zero()
                and rs.valid_round < vote.round
                and vote.round == rs.round
            ):
                if rs.proposal_block is not None and rs.proposal_block.hash() == block_id.hash:
                    rs.valid_round = vote.round
                    rs.valid_block = rs.proposal_block
                    rs.valid_block_parts = rs.proposal_block_parts
                else:
                    # polka for a block we don't have: start fetching it
                    rs.proposal_block = None
                    if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                        block_id.part_set_header
                    ):
                        rs.proposal_block_parts = PartSet(block_id.part_set_header)
                self._emit("valid_block")

        # round-skip only on votes STRICTLY ahead of us (reference uses
        # cs.Round < vote.Round here; <= would cut the NEW_HEIGHT
        # commit-timeout wait short on round-equal prevotes)
        if rs.round < vote.round and prevotes.has_two_thirds_any():
            self.enter_new_round(rs.height, vote.round)
        if rs.round == vote.round and Step.PREVOTE <= rs.step:
            if block_id is not None and (self.is_proposal_complete() or block_id.is_zero()):
                self.enter_precommit(rs.height, vote.round)
            elif prevotes.has_two_thirds_any():
                self.enter_prevote_wait(rs.height, vote.round)
        if (
            rs.proposal is not None
            and 0 <= rs.proposal.pol_round
            and rs.proposal.pol_round == vote.round
            and rs.step <= Step.PROPOSE
            and self.is_proposal_complete()
        ):
            self.enter_prevote(rs.height, rs.round)

    def _on_precommit_added(self, vote: Vote) -> None:
        rs = self.rs
        precommits = rs.votes.precommits(vote.round)
        block_id = precommits.two_thirds_majority()
        if block_id is not None:
            self.enter_new_round(rs.height, vote.round)
            self.enter_precommit(rs.height, vote.round)
            if not block_id.is_zero():
                self.enter_commit(rs.height, vote.round)
                if self.config.skip_timeout_commit and precommits.has_all():
                    self.enter_new_round(rs.height, 0)
            else:
                self.enter_precommit_wait(rs.height, vote.round)
        elif rs.round <= vote.round and precommits.has_two_thirds_any():
            self.enter_new_round(rs.height, vote.round)
            self.enter_precommit_wait(rs.height, vote.round)

    # ------------------------------------------------------------------
    # vote signing
    # ------------------------------------------------------------------

    def sign_add_vote(
        self, msg_type: SignedMsgType, hash_: bytes, header: PartSetHeader
    ) -> Vote | None:
        if self.priv_validator is None:
            return None
        addr = self.privval_address()
        if not self.rs.validators.has_address(addr):
            return None
        vote = self.sign_vote(msg_type, hash_, header)
        if vote is not None:
            self.send_internal(VoteMessage(vote))
        return vote

    def sign_vote(
        self, msg_type: SignedMsgType, hash_: bytes, header: PartSetHeader
    ) -> Vote | None:
        rs = self.rs
        addr = self.privval_address()
        idx, _ = rs.validators.get_by_address(addr)
        vote = Vote(
            type=msg_type,
            height=rs.height,
            round=rs.round,
            block_id=BlockID(hash=hash_ or b"", part_set_header=header),
            timestamp_ns=self.vote_time(),
            validator_address=addr,
            validator_index=idx,
        )
        try:
            self.priv_validator.sign_vote(self.state.chain_id, vote)
            return vote
        except Exception as e:
            self.logger.error("failed signing vote", err=str(e))
            return None

    def vote_time(self) -> int:
        """now, but never before (previous block time + iota) (reference
        voteTime, state.go:2040)."""
        now = now_ns()
        min_vote_time = 0
        if self.rs.locked_block is not None:
            min_vote_time = self.rs.locked_block.header.time_ns + TIME_IOTA_NS
        elif self.rs.proposal_block is not None:
            min_vote_time = self.rs.proposal_block.header.time_ns + TIME_IOTA_NS
        return max(now, min_vote_time)

    # ------------------------------------------------------------------
    # WAL catchup replay (reference consensus/replay.go:94)
    # ------------------------------------------------------------------

    def catchup_replay(self) -> None:
        """Re-apply WAL messages recorded after the last committed height's
        end barrier, without re-writing them."""
        # the barrier before the first height of the chain is height 0 —
        # NOT initial_height-1 (reference replay.go:126-137)
        end_height = self.state.last_block_height
        msgs, found = self.wal.search_for_end_height(end_height)
        if not found:
            # No barrier for our height.  A WAL whose newest barrier is
            # BEHIND the chain is normal: the state advanced without
            # consensus (fast sync / state sync / fresh WAL at its initial
            # EndHeight(0) on an existing chain) — nothing to replay.  A
            # barrier AHEAD of the chain means this WAL belongs to a
            # different data dir: refuse to run on it.
            last_barrier = -1
            for tm in self.wal.all_messages():
                if isinstance(tm.msg, EndHeightMessage):
                    last_barrier = max(last_barrier, tm.msg.height)
            if last_barrier > end_height:
                raise RuntimeError(
                    f"WAL is ahead of the chain: barrier {last_barrier} > "
                    f"state height {end_height}"
                )
            return
        self.replay_mode = True
        try:
            for tm in msgs:
                m = tm.msg
                if isinstance(m, MsgInfo):
                    try:
                        self.handle_msg(m)
                    except Exception as e:
                        self.logger.error("replay msg failed", err=str(e))
                elif isinstance(m, TimeoutInfo):
                    # timeouts ARE replayed (reference readReplayMessage →
                    # handleTimeout): round transitions must survive a crash
                    # or the validator would double-sign at a stale round
                    self.handle_timeout(m)
                elif isinstance(m, EndHeightMessage):
                    pass
        finally:
            self.replay_mode = False


