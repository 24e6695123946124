"""Fast-sync reactor: serve blocks to peers, download + verify + apply
the chain until caught up, then hand off to consensus.

Parity: reference blockchain/v0/reactor.go — channel 0x40, BlockRequest
service from the store (:187), poolRoutine verify+apply (:413-560),
SwitchToConsensus handoff (:566 via consensus/reactor.go:106).

TPU redesign of the hot loop: the reference verifies one block pair per
10ms tick (VerifyCommitLight, one sequential sig loop per block).  Here
the whole downloaded window of consecutive blocks is verified as ONE
batched device call — every LastCommit in the window full-verified plus
one light pair-check for the newest block — then the window is applied
with signature checks already done (strictly ≥ the reference's checks:
it light-verifies each pair AND full-verifies each commit one height
later; we full-verify each commit exactly once, in the batch).

Round 6: batch_verify_commits submits through the async verification
service (crypto.async_verify), so a blocksync window verifying while
consensus or a light client is also active coalesces into shared device
dispatches, and catching up over blocks whose commits were already
verified (restart replay) resolves from the verified-signature cache.
"""

from __future__ import annotations

import asyncio

from tendermint_tpu.p2p.types import ChannelDescriptor, Envelope, PeerStatus
from tendermint_tpu.types.basic import BlockID
from tendermint_tpu.types.validator import CommitVerifyJob, batch_verify_commits
from tendermint_tpu.utils.log import Logger, nop_logger

from .messages import (
    BlockRequest,
    BlockResponse,
    NoBlockResponse,
    StatusRequest,
    StatusResponse,
    decode_blocksync_message,
    encode_blocksync_message,
)
from .pool import BlockPool

BLOCKSYNC_CHANNEL = 0x40


def _descriptor() -> ChannelDescriptor:
    return ChannelDescriptor(
        channel_id=BLOCKSYNC_CHANNEL,
        priority=5,
        encode=encode_blocksync_message,
        decode=decode_blocksync_message,
        recv_buffer_capacity=1024,
        max_msg_bytes=22 * 1024 * 1024,  # a max-size block + envelope
    )


class BlocksyncReactor:
    def __init__(
        self,
        state,
        executor,
        block_store,
        router,
        logger: Logger | None = None,
        on_caught_up=None,  # callback(state) once synced; consensus handoff
        status_interval_s: float = 2.0,
        startup_grace_s: float = 5.0,
    ):
        self.state = state
        self.executor = executor
        self.store = block_store
        self.router = router
        self.logger = (logger or nop_logger()).with_(module="blocksync")
        self.on_caught_up = on_caught_up
        self.status_interval_s = status_interval_s
        self.pool = BlockPool(state.last_block_height + 1, startup_grace_s)
        self.channel = router.open_channel(_descriptor())
        self.peer_updates = router.subscribe_peer_updates()
        self._tasks: list[asyncio.Task] = []
        self.synced = asyncio.Event()

    # -- lifecycle -------------------------------------------------------
    async def start(self, sync: bool = True) -> None:
        """sync=False: serve blocks + answer statuses only — the mode of
        a node already in consensus (reference v0 reactor with
        fastSync=false skips poolRoutine but still serves requests)."""
        loop = asyncio.get_running_loop()
        self._serve_only = not sync
        if self._serve_only:
            # in-flight requesters will never fill (responses are ignored
            # in serve-only mode) — drop them so the timeout sweep can't
            # ban honest peers after the consensus handoff
            self.pool.requesters.clear()
        self._tasks = [
            loop.create_task(self._recv_loop(serve_only=self._serve_only)),
            loop.create_task(self._peer_update_loop()),
            loop.create_task(self._status_ticker()),
        ]
        if sync:
            self._tasks.append(loop.create_task(self._request_sender()))
            self._tasks.append(loop.create_task(self._sync_loop()))

    def reset_pool(self, state) -> None:
        """Re-anchor the download pipeline on `state` (used after state
        sync bootstraps the stores past the construction-time height —
        reference node.go startStateSync → bcR.SwitchToBlockSync)."""
        self.state = state
        grace = self.pool._grace
        self.pool = BlockPool(state.last_block_height + 1, grace)

    async def stop(self) -> None:
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        self._tasks = []

    # -- serving + intake ------------------------------------------------
    async def _recv_loop(self, serve_only: bool = False) -> None:
        while True:
            env = await self.channel.receive()
            msg, frm = env.message, env.from_
            if isinstance(msg, BlockRequest):
                await self._respond_block(frm, msg.height)
            elif isinstance(msg, StatusRequest):
                await self._send_status(frm)
            elif serve_only:
                continue  # not pulling blocks; ignore sync responses
            elif isinstance(msg, BlockResponse):
                if not self.pool.add_block(frm, msg.block):
                    self.logger.debug("unsolicited block", peer=frm[:8])
            elif isinstance(msg, NoBlockResponse):
                self.pool.no_block(frm, msg.height)
            elif isinstance(msg, StatusResponse):
                self.pool.set_peer_range(frm, msg.base, msg.height)

    async def _respond_block(self, to: str, height: int) -> None:
        block = self.store.load_block(height)
        msg = BlockResponse(block) if block is not None else NoBlockResponse(height)
        await self.channel.send(
            Envelope(message=msg, to=to, channel_id=BLOCKSYNC_CHANNEL)
        )

    async def _send_status(self, to: str = "", broadcast: bool = False) -> None:
        msg = StatusResponse(height=self.store.height(), base=self.store.base())
        await self.channel.send(
            Envelope(
                message=msg, to=to, broadcast=broadcast, channel_id=BLOCKSYNC_CHANNEL
            )
        )

    async def _peer_update_loop(self) -> None:
        while True:
            update = await self.peer_updates.get()
            if update.status == PeerStatus.UP:
                self.pool.add_peer(update.node_id)
                # announce our range + ask for theirs (reference AddPeer)
                await self._send_status(to=update.node_id)
                await self.channel.send(
                    Envelope(
                        message=StatusRequest(),
                        to=update.node_id,
                        channel_id=BLOCKSYNC_CHANNEL,
                    )
                )
            else:
                self.pool.remove_peer(update.node_id)

    async def _request_sender(self) -> None:
        while True:
            height, peer_id = await self.pool.request_q.get()
            await self.channel.send(
                Envelope(
                    message=BlockRequest(height),
                    to=peer_id,
                    channel_id=BLOCKSYNC_CHANNEL,
                )
            )

    async def _status_ticker(self) -> None:
        while True:
            await asyncio.sleep(self.status_interval_s)
            await self.channel.send(
                Envelope(
                    message=StatusRequest(),
                    broadcast=True,
                    channel_id=BLOCKSYNC_CHANNEL,
                )
            )
            if not getattr(self, "_serve_only", False):
                self.pool.retry_timeouts()
                await self._disconnect_banned()

    # -- the batched verify+apply pipeline -------------------------------
    def _window_jobs(self, window: list) -> tuple[list, list[CommitVerifyJob]]:
        """Trim `window` to the static-valset prefix and build the single
        device batch covering it.

        applied  = window[:-1] restricted to blocks whose ValidatorsHash
                   equals the current valset's (the valset can only change
                   at a header boundary, where the batch must stop because
                   future valsets aren't known until the app runs).
        jobs     = full-verify of every applied block's LastCommit
                   + light pair-check of the newest applied block's commit
                   (carried by its successor's LastCommit).
        """
        applied = self._static_valset_prefix(window)
        if not applied:
            return [], []
        chain_id = self.state.chain_id
        jobs = []
        for i, b in enumerate(applied):
            if b.header.height == self.state.initial_height:
                continue  # first block ever has an empty LastCommit
            val_set = (
                self.state.last_validators if i == 0 else self.state.validators
            )
            jobs.append(
                CommitVerifyJob(
                    val_set=val_set,
                    chain_id=chain_id,
                    block_id=b.header.last_block_id,
                    height=b.header.height - 1,
                    commit=b.last_commit,
                    mode="full",
                )
            )
        # pair-check: successor's LastCommit proves the newest applied block
        last = applied[-1]
        successor = window[len(applied)]
        part_set = last.make_part_set()
        last_id = BlockID(hash=last.hash(), part_set_header=part_set.header())
        if successor.header.last_block_id != last_id:
            raise ValueError(
                f"successor of height {last.header.height} points at a "
                "different block"
            )
        jobs.append(
            CommitVerifyJob(
                val_set=self.state.validators,
                chain_id=chain_id,
                block_id=last_id,
                height=last.header.height,
                commit=successor.last_commit,
                mode="light",
            )
        )
        return applied, jobs

    async def _sync_loop(self) -> None:
        while True:
            try:
                await asyncio.wait_for(self.pool.blocks_available.wait(), timeout=0.25)
            except asyncio.TimeoutError:
                if self.pool.is_caught_up():
                    self.logger.info(
                        "caught up; switching to consensus",
                        height=self.state.last_block_height,
                    )
                    self.synced.set()
                    if self.on_caught_up is not None:
                        res = self.on_caught_up(self.state)
                        if asyncio.iscoroutine(res):
                            await res
                    return
                continue

            window = self.pool.window()
            if len(window) < 2:
                self.pool.blocks_available.clear()
                continue
            try:
                applied, jobs = self._window_jobs(window)
                if not applied:
                    # An honest block at the apply point always carries
                    # ValidatorsHash == current valset hash; an empty
                    # prefix means the first pending block is forged —
                    # refetch it from another peer and ban the sender
                    # (without this the loop would spin forever on the
                    # bad block).
                    self.logger.info(
                        "bad validators hash at sync point, refetching",
                        height=window[0].header.height,
                    )
                    self.pool.redo(window[0].header.height)
                    await self._disconnect_banned()
                    self.pool.blocks_available.clear()
                    continue
                # ONE device call for the whole window's signatures — on
                # a worker thread: the first flush at a new rung pays a
                # compile (seconds to minutes), and an event loop blocked
                # that long serves no peer, answers no RPC, and wakes to
                # find its in-flight block requests past REQUEST_TIMEOUT_S
                # (the status ticker then bans the honest peers that
                # could not answer a loop that was not running)
                await asyncio.to_thread(batch_verify_commits, jobs)
            except ValueError as e:
                self.logger.info("bad window, refetching", err=str(e))
                self._redo_per_block(window)
                await self._disconnect_banned()
                continue
            for b in applied:
                part_set = b.make_part_set()
                block_id = BlockID(hash=b.hash(), part_set_header=part_set.header())
                try:
                    # validate fully BEFORE persisting anything, then save
                    # the block BEFORE applying — the crash-safe order of
                    # the consensus finalize path: on restart, a saved
                    # block with a state one height behind is replayed by
                    # the handshake, while an advanced state with no block
                    # would be unrecoverable
                    self.executor.validate_block(
                        self.state, b, commit_sigs_verified=True
                    )
                    self.store.save_block(b, part_set, self._commit_for(b, window))
                    self.state, _ = self.executor.apply_block(
                        self.state, block_id, b,
                        commit_sigs_verified=True, pre_validated=True,
                    )
                except ValueError as e:
                    # structural failure (hashes, time, proposer…): the
                    # block is bad even though signatures checked out
                    self.logger.info(
                        "invalid block", height=b.header.height, err=str(e)
                    )
                    self.pool.redo(b.header.height)
                    break
                self.pool.pop(b.header.height)
            await self._disconnect_banned()
            # yield so request/recv tasks keep the pipeline full
            await asyncio.sleep(0)

    def _static_valset_prefix(self, window: list) -> list:
        """Leading blocks of the window whose ValidatorsHash matches the
        current set — the slice batch verification and per-block redo must
        both scan (past the valset boundary different signers apply)."""
        cur_hash = self.state.validators.hash()
        prefix = []
        for b in window[:-1]:
            if b.header.validators_hash != cur_hash:
                break
            prefix.append(b)
        return prefix

    def _commit_for(self, block, window: list):
        """SeenCommit for a fast-synced block = its successor's LastCommit."""
        for b in window:
            if b.header.height == block.header.height + 1:
                return b.last_commit
        raise AssertionError("applied block without successor in window")

    async def _disconnect_banned(self) -> None:
        """Evict banned peers from the router (reference StopPeerForError)."""
        for pid in self.pool.take_banned():
            await self.channel.error(pid, "blocksync: bad block or timeout")

    def _redo_per_block(self, window: list) -> None:
        """Batch verification failed somewhere in the window: find the
        first bad height with per-block checks so only the offending peers
        are banned (reference redo bans the sender of the failing pair).
        Scans exactly the static-valset prefix _window_jobs batched —
        past the valset boundary different signers apply and honest blocks
        would fail a naive check."""
        state = self.state
        applied = self._static_valset_prefix(window)
        for i, b in enumerate(applied):
            try:
                if b.header.height > state.initial_height:
                    val_set = (
                        state.last_validators if i == 0 else state.validators
                    )
                    val_set.verify_commit(
                        state.chain_id,
                        b.header.last_block_id,
                        b.header.height - 1,
                        b.last_commit,
                    )
            except ValueError:
                self.pool.redo(b.header.height)
                return
        # commits fine ⇒ the light pair-check on the newest applied block
        # (carried by its successor) failed
        if applied:
            self.pool.redo(applied[-1].header.height)
