"""Fast-sync reactor: serve blocks to peers, download + verify + apply
the chain until caught up, then hand off to consensus.

Parity: reference blockchain/v0/reactor.go — channel 0x40, BlockRequest
service from the store (:187), poolRoutine verify+apply (:413-560),
SwitchToConsensus handoff (:566 via consensus/reactor.go:106).

TPU redesign of the hot loop: the reference verifies one block pair per
10ms tick (VerifyCommitLight, one sequential sig loop per block).  Here
a step (`verify_window`) verifies as many leading blocks of the downloaded
window as ONE flush of the verify service holds — every LastCommit of the
blocks it takes full-verified plus one light pair-check for the newest of
them — as one batched device call, then applies those blocks with
signature checks already done (strictly ≥ the reference's checks: it
light-verifies each pair AND full-verifies each commit one height later;
we full-verify each commit exactly once, in the batch).  The blocks past
the cut stay in the pool and head the next step's window, so a catch-up
from a peer faster than the verifier runs one device program (the top
rung, `MAX_COALESCE` rows) step after step, whatever the window's length;
only the short windows near the tip meet smaller rungs.

Round 6: batch_verify_commits submits through the async verification
service (crypto.async_verify), so a blocksync window verifying while
consensus or a light client is also active coalesces into shared device
dispatches, and catching up over blocks whose commits were already
verified (restart replay) resolves from the verified-signature cache.
"""

from __future__ import annotations

import asyncio

from tendermint_tpu.crypto.async_verify import MAX_COALESCE
from tendermint_tpu.p2p.types import ChannelDescriptor, Envelope, PeerStatus
from tendermint_tpu.types.basic import BlockID
from tendermint_tpu.types.validator import CommitVerifyJob, batch_verify_commits
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.utils.log import Logger, nop_logger
from tendermint_tpu.utils.metrics import Counter

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


# Bumped once a step, in `verify_window` (process-wide; registered by
# node/metrics.py): how many steps ran, the blocks and the rows they
# verified, and how often a step left downloaded blocks for the next one.
WINDOWS_TOTAL = Counter(
    "windows_total", "Window verify steps (one batched device call each)",
    namespace="tendermint", subsystem="blocksync",
)
WINDOW_BLOCKS_TOTAL = Counter(
    "window_blocks_total", "Blocks verified by window steps",
    namespace="tendermint", subsystem="blocksync",
)
WINDOW_ROWS_TOTAL = Counter(
    "window_rows_total",
    "Commit rows window steps were sized by (signature counts of their jobs)",
    namespace="tendermint", subsystem="blocksync",
)
WINDOW_CUTS_TOTAL = Counter(
    "window_cuts_total",
    "Window steps that left downloaded blocks for the next step",
    namespace="tendermint", subsystem="blocksync",
)
WINDOW_COUNTERS = (WINDOWS_TOTAL, WINDOW_BLOCKS_TOTAL, WINDOW_ROWS_TOTAL,
                   WINDOW_CUTS_TOTAL)


def _static_valset_prefix(state, window: list) -> list:
    """Leading blocks of window[:-1] whose ValidatorsHash matches the
    current set — the slice batch verification and per-block redo must
    both scan (past the valset boundary different signers apply)."""
    cur_hash = state.validators.hash()
    prefix = []
    for b in window[:-1]:
        if b.header.validators_hash != cur_hash:
            break
        prefix.append(b)
    return prefix


def _commit_rows(block) -> int:
    return len(block.last_commit.signatures) if block.last_commit is not None else 0


def _cut(state, window: list, max_rows: int) -> list:
    """The blocks one step takes: the longest leading run of the
    static-valset prefix whose jobs fit one flush — the LastCommits of
    the blocks taken plus the successor's (the pair check), each counted
    at its signature count, an upper bound on the rows the job submits
    that needs no pass over them.  Never fewer than one block: a commit
    wider than a flush is still verified, and the service cuts the flush."""
    prefix = _static_valset_prefix(state, window)
    if not prefix:
        return []
    rows = _commit_rows(window[0]) + _commit_rows(window[1])
    k = 1
    while k < len(prefix) and rows + _commit_rows(window[k + 1]) <= max_rows:
        rows += _commit_rows(window[k + 1])
        k += 1
    return prefix[:k]


def window_jobs(state, window: list,
                max_rows: int = MAX_COALESCE) -> tuple[list, list[CommitVerifyJob]]:
    """The blocks one step takes off `window` (consecutive downloaded
    blocks from the apply point of `state`) and the single device batch
    that covers them.

    applied  = the leading blocks of window[:-1] whose ValidatorsHash
               equals the current valset's (the valset can only change
               at a header boundary, where the batch must stop because
               future valsets aren't known until the app runs), cut to
               what one flush of `max_rows` rows holds (`_cut`).
    jobs     = full-verify of every applied block's LastCommit
               + light pair-check of the newest applied block's commit
               (carried by its successor's LastCommit).
    """
    with _trace.span("blocksync.window_jobs", downloaded=len(window)) as sp:
        applied = _cut(state, window, max_rows)
        if not applied:
            return [], []
        chain_id = state.chain_id
        jobs = []
        for i, b in enumerate(applied):
            if b.header.height == state.initial_height:
                continue  # first block ever has an empty LastCommit
            val_set = state.last_validators if i == 0 else state.validators
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
                val_set=state.validators,
                chain_id=chain_id,
                block_id=last_id,
                height=last.header.height,
                commit=successor.last_commit,
                mode="light",
            )
        )
        sp.set(applied=len(applied), jobs=len(jobs))
    return applied, jobs


def verify_window(state, window: list, max_rows: int = MAX_COALESCE) -> list:
    """One step of a catch-up: verify the leading blocks of `window` that
    one flush holds as ONE batched device call and return them, ready to
    be applied with their signature checks done.  An empty list: the first
    block's ValidatorsHash is not the current set's.  Raises ValueError
    naming the first failing commit's height (`batch_verify_commits`)."""
    with _trace.span("blocksync.window", downloaded=len(window)) as sp:
        applied, jobs = window_jobs(state, window, max_rows)
        if applied:
            rows = sum(len(job.commit.signatures) for job in jobs)
            cut = len(applied) < len(window) - 1
            sp.set(applied=len(applied), jobs=len(jobs), rows=rows, cut=cut)
            WINDOWS_TOTAL.inc()
            WINDOW_BLOCKS_TOTAL.inc(len(applied))
            WINDOW_ROWS_TOTAL.inc(rows)
            if cut:
                WINDOW_CUTS_TOTAL.inc()
            batch_verify_commits(jobs)
    return applied


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
        # rows one step's flush holds; tests shrink it so that the cut
        # bites at small sizes
        self.max_rows = MAX_COALESCE
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
                # ONE device call for the signatures of the blocks a step
                # takes — on a worker thread: the first flush at a new
                # rung pays a compile (seconds to minutes), and an event
                # loop blocked that long serves no peer, answers no RPC,
                # and wakes to find its in-flight block requests past
                # REQUEST_TIMEOUT_S (the status ticker then bans the
                # honest peers that could not answer a loop that was not
                # running)
                applied = await asyncio.to_thread(
                    verify_window, self.state, window, self.max_rows
                )
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
        Scans exactly the blocks `verify_window` batched: the cut
        static-valset prefix — past the valset boundary different signers
        apply and honest blocks would fail a naive check, and past the cut
        nothing was verified."""
        state = self.state
        applied = _cut(state, window, self.max_rows)
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
