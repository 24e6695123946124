"""BlockPool: schedules block downloads across peers for fast sync.

Parity: reference blockchain/v0/pool.go — peer height/base tracking,
bounded request pipeline ahead of the apply point, peer banning on bad
blocks/timeouts, IsCaughtUp (pool.go:176).  Redesigned for asyncio:
instead of one goroutine per in-flight height (pool.go:115 bpRequester),
a single `schedule()` pass assigns pending heights to peers and the
reactor owns the send loop — same pipelining, two tasks total.

The pool's output is not one block at a time (pool.go:194 PeekTwoBlocks)
but a *verifiable window*: the longest run of consecutive downloaded
blocks, of which the reactor verifies, per step, as many leading blocks
as one flush holds as ONE batched device call (reactor.verify_window →
types.batch_verify_commits) — the TPU-shaped replacement for the
reference's per-block VerifyCommitLight (reactor.go:517).
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

from tendermint_tpu.types.block import Block
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.utils.metrics import Histogram

# reference pool.go:31-35: bounds on outstanding requests
MAX_PENDING_AHEAD = 600  # how far past the apply point we request
MAX_PENDING_PER_PEER = 20
REQUEST_TIMEOUT_S = 15.0  # ban a peer that sits on a request this long

# Schedule-to-arrival round trip per block request (process-wide;
# registered by node/metrics.py).  Top bucket == the ban deadline.
REQUEST_DURATION_SECONDS = Histogram(
    "request_duration_seconds",
    "Block request round trip, schedule to block arrival",
    namespace="tendermint", subsystem="blocksync",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
             REQUEST_TIMEOUT_S),
)


@dataclass
class _PoolPeer:
    base: int = 0
    height: int = 0
    pending: set = field(default_factory=set)  # heights requested from this peer
    reported: bool = True  # False until the first StatusResponse arrives
    connected_at: float = field(default_factory=time.monotonic)


@dataclass
class _Requester:
    height: int
    peer_id: str
    sent_at: float
    block: Block | None = None


class BlockPool:
    def __init__(self, start_height: int, startup_grace_s: float = 5.0):
        self.height = start_height  # next height to verify+apply
        self.peers: dict[str, _PoolPeer] = {}
        self.requesters: dict[int, _Requester] = {}
        self.request_q: asyncio.Queue = asyncio.Queue()  # (height, peer_id)
        self.blocks_available = asyncio.Event()
        self.banned: set[str] = set()
        self._newly_banned: list[str] = []  # drained by the reactor → disconnect
        self._started_at = time.monotonic()
        self._grace = startup_grace_s
        self._max_seen_height = 0  # monotonic; survives peer bans/removals

    # -- peers -----------------------------------------------------------
    def add_peer(self, peer_id: str) -> None:
        """Peer connected, StatusResponse not yet in: its chain tip is
        unknown, so it blocks the caught-up verdict (bounded by the
        grace window in is_caught_up)."""
        if peer_id in self.banned or peer_id in self.peers:
            return
        self.peers[peer_id] = _PoolPeer(reported=False)

    def set_peer_range(self, peer_id: str, base: int, height: int) -> None:
        """StatusResponse from a peer (pool.go SetPeerRange)."""
        if peer_id in self.banned:
            return
        p = self.peers.setdefault(peer_id, _PoolPeer())
        p.base, p.height = base, height
        p.reported = True
        self._max_seen_height = max(self._max_seen_height, height)
        self.schedule()

    def remove_peer(self, peer_id: str) -> None:
        """Peer disconnected: its undelivered requests are reassigned;
        already-delivered blocks are kept (they'll be verified anyway)."""
        p = self.peers.pop(peer_id, None)
        if p is None:
            return
        for h in list(p.pending):
            r = self.requesters.get(h)
            if r is not None and r.block is None:
                del self.requesters[h]
        self.schedule()

    def ban_peer(self, peer_id: str) -> None:
        """Peer sent a bad block / timed out: evict EVERYTHING it gave us
        (its cached blocks are suspect), remember the ban so the next
        status broadcast can't re-admit it, and queue it for disconnect
        (reference StopPeerForError via RedoRequest, pool.go:218)."""
        if peer_id in self.banned:
            return
        self.banned.add(peer_id)
        self._newly_banned.append(peer_id)
        self.peers.pop(peer_id, None)
        for h in [h for h, r in self.requesters.items() if r.peer_id == peer_id]:
            del self.requesters[h]
        head = self.requesters.get(self.height)
        if head is None or head.block is None:
            self.blocks_available.clear()
        self.schedule()

    def take_banned(self) -> list[str]:
        """Peers banned since the last call (reactor disconnects them)."""
        out, self._newly_banned = self._newly_banned, []
        return out

    def max_peer_height(self) -> int:
        return max((p.height for p in self.peers.values()), default=0)

    # -- scheduling ------------------------------------------------------
    def _pick_peer(self, height: int) -> str | None:
        best, best_load = None, MAX_PENDING_PER_PEER
        for pid, p in self.peers.items():
            if not (p.base <= height <= p.height):
                continue
            if len(p.pending) < best_load:
                best, best_load = pid, len(p.pending)
        return best

    def schedule(self) -> None:
        """Fill the request pipeline: every height in
        [self.height, min(height+MAX_PENDING_AHEAD, max_peer_height)]
        gets exactly one outstanding requester."""
        top = min(self.height + MAX_PENDING_AHEAD, self.max_peer_height())
        for h in range(self.height, top + 1):
            if h in self.requesters:
                continue
            pid = self._pick_peer(h)
            if pid is None:
                continue
            self.requesters[h] = _Requester(h, pid, time.monotonic())
            self.peers[pid].pending.add(h)
            self.request_q.put_nowait((h, pid))

    def retry_timeouts(self) -> list[str]:
        """Ban peers sitting on requests past the deadline; returns banned
        peer ids (reference pool.go:140 timeout ban)."""
        now = time.monotonic()
        stale = {
            r.peer_id
            for r in self.requesters.values()
            if r.block is None and now - r.sent_at > REQUEST_TIMEOUT_S
        }
        for pid in stale:
            self.ban_peer(pid)
        return list(stale)

    # -- block intake ----------------------------------------------------
    def add_block(self, peer_id: str, block: Block) -> bool:
        """Accept a block iff we requested that height from that peer
        (pool.go AddBlock).  Returns False on unsolicited blocks."""
        h = block.header.height
        r = self.requesters.get(h)
        if r is None or r.peer_id != peer_id or r.block is not None:
            return False
        r.block = block
        dur = time.monotonic() - r.sent_at
        REQUEST_DURATION_SECONDS.observe(dur)
        if _trace.enabled():
            _trace.record("blocksync.request", time.perf_counter() - dur,
                          dur, height=h, peer=peer_id)
        # wake the sync loop whenever the apply point has a block — NOT
        # only when h == self.height: the loop may have drained the event
        # on a too-short window, and a later height extending the run must
        # re-arm it or the pipeline deadlocks
        head = self.requesters.get(self.height)
        if head is not None and head.block is not None:
            self.blocks_available.set()
        return True

    def no_block(self, peer_id: str, height: int) -> None:
        """Peer says it lacks a height it claimed: shrink its advertised
        range and reassign."""
        p = self.peers.get(peer_id)
        if p is not None:
            p.height = min(p.height, height - 1)
            p.pending.discard(height)
        r = self.requesters.get(height)
        if r is not None and r.peer_id == peer_id and r.block is None:
            del self.requesters[height]
        self.schedule()

    # -- the verifiable window ------------------------------------------
    def window(self) -> list[Block]:
        """Longest run of downloaded consecutive blocks starting at the
        apply point.  The LAST block of the run is the 'second' block
        whose LastCommit proves its predecessor; only blocks[:-1] can be
        applied this round (reference PeekTwoBlocks generalized)."""
        out = []
        h = self.height
        while True:
            r = self.requesters.get(h)
            if r is None or r.block is None:
                break
            out.append(r.block)
            h += 1
        return out

    def pop(self, height: int) -> None:
        """Block at `height` was verified+applied (pool.go PopRequest)."""
        r = self.requesters.pop(height, None)
        if r is not None:
            p = self.peers.get(r.peer_id)
            if p is not None:
                p.pending.discard(height)
        self.height = max(self.height, height + 1)
        nxt = self.requesters.get(self.height)
        if nxt is None or nxt.block is None:
            self.blocks_available.clear()
        self.schedule()

    def redo(self, height: int) -> None:
        """Verification failed at `height`: the block (and its successor,
        which carried the bogus commit) came from misbehaving peers — ban
        both and refetch (reference reactor.go:525-540)."""
        for h in (height, height + 1):
            r = self.requesters.get(h)
            if r is not None:
                self.ban_peer(r.peer_id)
        self.schedule()

    # -- caught-up test --------------------------------------------------
    def is_caught_up(self) -> bool:
        """True once the startup grace has passed and we are within one
        block of the highest advertised peer height (reference
        pool.go:176-184 semantics)."""
        now = time.monotonic()
        if now - self._started_at <= self._grace:
            return False
        # Connected peers whose StatusResponse hasn't arrived yet block
        # the caught-up verdict (reference pool.go:180 requires peers
        # before declaring caught up) — their status may still reveal a
        # higher chain tip.  Each unreported peer blocks for at most the
        # grace window so a silent peer can't wedge the sync forever.
        for p in self.peers.values():
            if not p.reported and now - p.connected_at <= self._grace:
                return False
        # Monotonic target: banning/losing the peer that advertised the
        # chain tip must NOT flip us to "caught up" while its heights are
        # still unapplied (reference keeps maxPeerHeight monotonic too).
        # One block of slack (reference pool.go:184 `height >=
        # maxPeerHeight-1`): the tip block can't be applied until its
        # successor's commit exists, so requiring exact equality would
        # chase a moving tip forever.
        return self.height >= self._max_seen_height - 1
