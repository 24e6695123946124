"""Fast sync: pool scheduling, cross-block batched commit verification,
and a full two-node sync over the memory transport.

Models reference blockchain/v0/reactor_test.go + pool_test.go.
"""

import asyncio
import copy
import dataclasses
import functools
import random

import pytest

from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference import window_rules
from chipbench.reference.signbytes import precommit_sign_bytes
from tendermint_tpu.blocksync import BlockPool, BlocksyncReactor
from tendermint_tpu.blocksync import reactor as bsync
from tendermint_tpu.blocksync.messages import (
    BlockResponse,
    StatusResponse,
    decode_blocksync_message,
    encode_blocksync_message,
)
from tendermint_tpu.crypto.batch import set_default_backend
from tendermint_tpu.p2p import MemoryNetwork, Router
from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
from tendermint_tpu.store import BlockStore, MemDB
from tendermint_tpu.abci import AppConns
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.types.validator import CommitVerifyJob, batch_verify_commits
from tendermint_tpu.utils import trace

from helpers import ChainBuilder


@pytest.fixture(autouse=True)
def cpu_backend():
    set_default_backend("cpu")
    yield
    set_default_backend("auto")


# ---------------------------------------------------------------------------
# pool unit tests
# ---------------------------------------------------------------------------


def test_pool_scheduling_and_window():
    async def run():
        pool = BlockPool(1)
        pool.set_peer_range("peerA", 1, 10)
        # every height 1..10 gets exactly one outstanding request
        reqs = []
        while not pool.request_q.empty():
            reqs.append(pool.request_q.get_nowait())
        assert [h for h, _ in reqs] == list(range(1, 11))

        chain = ChainBuilder(n_vals=1).build(10)
        # deliver heights 1..3 and 5 — window stops at the gap
        for h in [1, 2, 3, 5]:
            assert pool.add_block("peerA", chain.block_store.load_block(h))
        win = pool.window()
        assert [b.header.height for b in win] == [1, 2, 3]
        # unsolicited block (wrong peer) rejected
        assert not pool.add_block("peerB", chain.block_store.load_block(4))
        # pop advances the apply point
        pool.pop(1)
        assert pool.height == 2

    asyncio.run(run())


def test_pool_peer_removal_reassigns():
    async def run():
        pool = BlockPool(1)
        pool.set_peer_range("peerA", 1, 5)
        while not pool.request_q.empty():
            pool.request_q.get_nowait()
        pool.set_peer_range("peerB", 1, 5)
        pool.remove_peer("peerA")
        # peerA's heights reassigned to peerB
        reqs = []
        while not pool.request_q.empty():
            reqs.append(pool.request_q.get_nowait())
        assert {p for _, p in reqs} == {"peerB"}
        assert sorted(h for h, _ in reqs) == [1, 2, 3, 4, 5]

    asyncio.run(run())


# ---------------------------------------------------------------------------
# cross-commit batch verification
# ---------------------------------------------------------------------------


def _commit_jobs(chain, heights, mode="full"):
    jobs = []
    for h in heights:
        commit = chain.block_store.load_seen_commit(h)
        vals = chain.state_store.load_validators(h)
        jobs.append(
            CommitVerifyJob(
                val_set=vals,
                chain_id=chain.genesis.chain_id,
                block_id=commit.block_id,
                height=h,
                commit=commit,
                mode=mode,
            )
        )
    return jobs


def test_batch_verify_commits_accepts_valid_window():
    chain = ChainBuilder().build(6)
    batch_verify_commits(_commit_jobs(chain, range(1, 7), "full"))
    batch_verify_commits(_commit_jobs(chain, range(1, 7), "light"))


def test_batch_verify_commits_rejects_corrupt_commit():
    chain = ChainBuilder().build(4)
    jobs = _commit_jobs(chain, range(1, 5))
    bad = jobs[2].commit.signatures[0]
    bad.signature = bytes(64)
    with pytest.raises(ValueError, match="height 3"):
        batch_verify_commits(jobs)


def test_batch_verify_commits_empty():
    batch_verify_commits([])


# ---------------------------------------------------------------------------
# the window step: blocksync.reactor.verify_window held to the plain
# reference (chipbench/reference/: the cut, the jobs, the answer from
# per-row ZIP-215 verdicts; imports nothing of the program)
# ---------------------------------------------------------------------------

N_VALS = 4


@functools.cache
def _run_chain():
    """One 14-block chain of 4 validators for all window cases (built once)."""
    return ChainBuilder(n_vals=N_VALS).build(14)


def _run(first, n_blocks):
    """(state the reactor holds at `first`, deep copies of blocks first ..
    first + n_blocks - 1) of the static-valset chain."""
    chain = _run_chain()
    state = dataclasses.replace(chain.state, last_block_height=first - 1)
    return state, [copy.deepcopy(chain.block_store.load_block(h))
                   for h in range(first, first + n_blocks)]


def _reference_answer(state, window, max_rows):
    """What `window_rules` says of the step from the plain reference's
    verdict on EVERY row of the run's commits."""
    powers = [v.voting_power for v in state.validators.validators]
    pubs = [v.pub_key.bytes_() for v in state.validators.validators]
    commits = [b.last_commit for b in window]

    def row_ok(i, r):
        c, cs = commits[i], commits[i].signatures[r]
        psh = c.block_id.part_set_header
        msg = precommit_sign_bytes(state.chain_id, c.height, c.round, c.block_id.hash,
                                   psh.total, psh.hash, cs.timestamp_ns)
        return ref.verify(pubs[r], msg, cs.signature)

    step = window_rules.jobs([len(c.signatures) for c in commits], max_rows)
    return step, window_rules.expected_step(
        step, powers, [c.height for c in commits],
        [range(len(c.signatures)) for c in commits], row_ok)


def _answer(state, window, max_rows):
    try:
        return ("accept", len(bsync.verify_window(state, window, max_rows)))
    except ValueError as e:
        return ("refused", str(e))


# (case, blocks offered, max_rows, where the corrupted row goes): with 4
# rows a commit, max_rows 16 holds 3 blocks and the pair check
WINDOW_CASES = [
    ("longer_than_the_cut", 8, 16, None),
    ("shorter_than_the_cut", 3, 16, None),
    ("cut_of_one_block", 5, 4, None),
    ("fits_the_default_flush", 8, None, None),
    ("bad_row_inside_the_cut", 8, 16, "full"),
    ("bad_row_in_the_newest_taken_block", 8, 16, "newest"),
    ("bad_row_in_the_pair_check", 8, 16, "pair"),
    ("bad_row_past_the_pair_checks_two_thirds", 8, 16, "pair_unconsulted"),
    ("bad_row_past_the_cut", 8, 16, "past_cut"),
]


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
@pytest.mark.parametrize("case,n_blocks,max_rows,bad", WINDOW_CASES,
                         ids=[c[0] for c in WINDOW_CASES])
def test_verify_window_against_the_reference(case, n_blocks, max_rows, bad, seed):
    rng = random.Random(seed)
    max_rows = bsync.MAX_COALESCE if max_rows is None else max_rows
    state, window = _run(rng.randrange(2, 6), n_blocks)
    taken = window_rules.cut([N_VALS] * n_blocks, max_rows)
    assert taken == {"longer_than_the_cut": 3, "shorter_than_the_cut": 2,
                     "cut_of_one_block": 1, "fits_the_default_flush": 7}.get(case, 3)
    if bad is not None:
        commit, row = {
            # not the newest taken block's LastCommit: see "newest" below
            "full": lambda: (rng.randrange(taken - 1), rng.randrange(N_VALS)),
            "newest": lambda: (taken - 1, rng.randrange(N_VALS)),
            "pair": lambda: (taken, rng.randrange(3)),        # 3 of 4 rows reach +2/3
            "pair_unconsulted": lambda: (taken, 3),
            "past_cut": lambda: (rng.randrange(taken + 1, n_blocks), rng.randrange(N_VALS)),
        }[bad]()
        cs = window[commit].last_commit.signatures[row]
        if rng.random() < 0.5:
            cs.signature = cs.signature[:-1] + bytes([cs.signature[-1] ^ 1])
        else:
            cs.timestamp_ns += 7

    if bad == "newest":
        # the newest taken block's part set is re-made for the pair check,
        # so a LastCommit changed after its successor was built is refused
        # before any signature is looked at
        with pytest.raises(ValueError, match="points at a different block"):
            bsync.window_jobs(state, window, max_rows)
        return

    applied, jobs = bsync.window_jobs(state, window, max_rows)
    step, expected = _reference_answer(state, window, max_rows)
    # the cut and the job order are the reference's
    assert [b.header.height for b in applied] == [
        b.header.height for b in window[:taken]]
    assert [(j.mode, j.height, j.commit) for j in jobs] == [
        (mode, window[i].last_commit.height, window[i].last_commit) for mode, i in step]
    assert [j.val_set for j in jobs] == (
        [state.last_validators] + [state.validators] * taken)

    got = _answer(state, window, max_rows)
    if bad in ("full", "pair"):
        height = window[commit].last_commit.height
        assert expected == ("wrong_signature", (height, row))
        assert got == ("refused", f"wrong signature (#{row}) in commit for height {height}")
    else:
        # rows past the cut and past the pair check's +2/3 are never consulted
        assert expected == got == ("accept", taken)


def test_verify_window_spans_and_counters():
    state, window = _run(2, 8)
    before = [c.samples()[0][2] for c in bsync.WINDOW_COUNTERS]
    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    try:
        assert len(bsync.verify_window(state, window, 16)) == 3
        assert len(bsync.verify_window(state, window[:3], 16)) == 2
    finally:
        trace.set_enabled(was)
    spans = trace.spans()
    trace.clear()
    steps = [s for s in spans if s["name"] == "blocksync.window"]
    assert [s["attrs"] for s in steps] == [
        {"downloaded": 8, "applied": 3, "jobs": 4, "rows": 16, "cut": True},
        {"downloaded": 3, "applied": 2, "jobs": 3, "rows": 12, "cut": False}]
    by_id = {s["id"]: s for s in spans}
    builds = [s for s in spans if s["name"] == "blocksync.window_jobs"]
    assert [by_id[s["parent"]]["name"] for s in builds] == ["blocksync.window"] * 2
    # one span a step, never one a block or a row; the commit.* spans of
    # the step's jobs hang under it
    commit_spans = [s for s in spans if s["name"].startswith("commit.")]
    assert {s["parent"] for s in commit_spans} == {s["id"] for s in steps}
    assert sum(s["name"] == "commit.sign_bytes" for s in commit_spans) == 4 + 3
    # counters: steps, blocks, rows the cut counted, steps that left blocks
    after = [c.samples()[0][2] for c in bsync.WINDOW_COUNTERS]
    assert [a - b for a, b in zip(after, before)] == [2, 5, 28, 1]



# ---------------------------------------------------------------------------
# wire round-trip
# ---------------------------------------------------------------------------


def test_blocksync_message_roundtrip():
    chain = ChainBuilder(n_vals=1).build(1)
    block = chain.block_store.load_block(1)
    msg = BlockResponse(block)
    out = decode_blocksync_message(encode_blocksync_message(msg))
    assert isinstance(out, BlockResponse)
    assert out.block.hash() == block.hash()
    st = decode_blocksync_message(encode_blocksync_message(StatusResponse(42, 7)))
    assert (st.height, st.base) == (42, 7)


# ---------------------------------------------------------------------------
# end-to-end: fresh node fast-syncs a 25-block chain from a served peer
# ---------------------------------------------------------------------------


def _make_node(genesis, network, node_id, block_store=None, on_caught_up=None):
    state_store = StateStore(MemDB())
    state = make_genesis_state(genesis)
    state_store.save(state)
    conns = AppConns(KVStoreApplication())
    executor = BlockExecutor(state_store, conns.consensus())
    store = block_store or BlockStore(MemDB())
    router = Router(node_id, network.create_transport(node_id))
    reactor = BlocksyncReactor(
        state,
        executor,
        store,
        router,
        on_caught_up=on_caught_up,
        status_interval_s=0.1,
        startup_grace_s=0.5,
    )
    return router, reactor


# max_rows 12 = three 4-row commits: a step takes TWO blocks and the pair
# check, so the 24 provable blocks need a dozen cut steps; the default
# flush holds every window a 25-block chain can offer
@pytest.mark.parametrize("max_rows", [None, 12], ids=["default_flush", "cut_steps"])
def test_fast_sync_two_nodes(max_rows):
    async def run():
        chain = ChainBuilder(n_vals=4).build(25)
        network = MemoryNetwork()

        server_router, server = _make_node(
            chain.genesis, network, "aa" * 20, block_store=chain.block_store
        )
        # the serving node is already synced; its state is the chain tip
        server.state = chain.state

        caught_up = asyncio.Event()
        synced_state = {}

        def on_caught_up(state):
            synced_state["state"] = state
            caught_up.set()

        client_router, client = _make_node(
            chain.genesis, network, "bb" * 20, on_caught_up=on_caught_up
        )
        if max_rows is not None:
            client.max_rows = max_rows

        await server_router.start()
        await client_router.start()
        await server.start()
        await client.start()
        await client_router.dial("aa" * 20)

        await asyncio.wait_for(caught_up.wait(), timeout=20)

        final = synced_state["state"]
        # server tip is 25; the client applies everything provable: 1..24
        assert final.last_block_height == 24
        assert client.store.height() == 24
        # app replayed to the same hash the source chain recorded for h=24
        assert final.app_hash == chain.block_store.load_block(25).header.app_hash
        # the synced chain is byte-identical to the source
        for h in range(1, 25):
            assert client.store.load_block(h).hash() == chain.block_store.load_block(h).hash()
        # only the client's steps are cut (the serving node's own loop runs
        # the default): each took two blocks (the first three: block 1's
        # LastCommit is empty) and left the rest for the next
        cut_steps = [s["attrs"] for s in trace.spans()
                     if s["name"] == "blocksync.window" and s["attrs"]["cut"]]
        if max_rows is None:
            assert cut_steps == []
        else:
            assert len(cut_steps) >= 6
            assert {(a["applied"], a["jobs"], a["rows"]) for a in cut_steps} == {
                (2, 3, 12), (3, 3, 12)}

        await client.stop()
        await server.stop()
        await client_router.stop()
        await server_router.stop()

    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    try:
        asyncio.run(run())
    finally:
        trace.set_enabled(was)
        trace.clear()


# ---------------------------------------------------------------------------
# ban semantics
# ---------------------------------------------------------------------------


def test_pool_ban_evicts_blocks_and_blocks_readmission():
    async def run():
        pool = BlockPool(1)
        pool.set_peer_range("peerA", 1, 5)
        while not pool.request_q.empty():
            pool.request_q.get_nowait()
        chain = ChainBuilder(n_vals=1).build(5)
        for h in range(1, 6):
            pool.add_block("peerA", chain.block_store.load_block(h))
        assert len(pool.window()) == 5
        pool.redo(1)
        # everything peerA delivered is gone, it can't come back, and the
        # reactor is told to disconnect it
        assert pool.window() == []
        assert pool.take_banned() == ["peerA"]
        pool.set_peer_range("peerA", 1, 5)
        assert pool.peers == {}
        assert not pool.blocks_available.is_set()

    asyncio.run(run())


def test_fast_sync_survives_byzantine_peer():
    """A peer serving a corrupted block is banned; sync completes from the
    honest peer (reference pool RedoRequest + StopPeerForError)."""

    async def run():
        chain = ChainBuilder(n_vals=4).build(12)

        # evil store: same chain but block 5's commit sig zeroed
        evil_store = BlockStore(MemDB())
        for h in range(1, 13):
            b = chain.block_store.load_block(h)
            sc = chain.block_store.load_seen_commit(h)
            if h == 6:
                import copy

                b = copy.deepcopy(b)
                b.last_commit.signatures[0].signature = bytes(64)
            evil_store.save_block(b, b.make_part_set(), sc)

        network = MemoryNetwork()
        honest_router, honest = _make_node(
            chain.genesis, network, "aa" * 20, block_store=chain.block_store
        )
        honest.state = chain.state
        evil_router, evil = _make_node(
            chain.genesis, network, "cc" * 20, block_store=evil_store
        )
        evil.state = chain.state

        caught_up = asyncio.Event()
        client_router, client = _make_node(
            chain.genesis, network, "bb" * 20, on_caught_up=lambda s: caught_up.set()
        )

        for r in (honest_router, evil_router, client_router):
            await r.start()
        for re in (honest, evil, client):
            await re.start()
        await client_router.dial("aa" * 20)
        await client_router.dial("cc" * 20)

        await asyncio.wait_for(caught_up.wait(), timeout=30)
        assert client.store.height() == 11
        for h in range(1, 12):
            assert (
                client.store.load_block(h).hash()
                == chain.block_store.load_block(h).hash()
            )
        for re in (honest, evil, client):
            await re.stop()
        for r in (honest_router, evil_router, client_router):
            await r.stop()

    asyncio.run(run())


@pytest.mark.parametrize("bad_height,refusal,banned", [
    # block 2's LastCommit is a full job of the first cut step (blocks 1-3)
    (2, "wrong signature \\(#1\\) in commit for height 1", {"p1-3"}),
    # block 4's LastCommit is that step's pair check: the commits of 1-3
    # hold, so the newest taken block and its successor are refetched.  A
    # scan past the cut would full-verify block 4 and blame 4 and 5.
    (4, "wrong signature \\(#1\\) in commit for height 3", {"p1-3", "p4"}),
], ids=["full_job_inside_the_cut", "pair_check_of_the_cut"])
def test_redo_scans_the_cut_window(bad_height, refusal, banned):
    async def run():
        chain = _run_chain()
        _, client = _make_node(chain.genesis, MemoryNetwork(), "bb" * 20)
        client.max_rows = 12        # blocks 1-3 and the pair check from block 4
        pool = client.pool
        for peer, (lo, hi) in {"p1-3": (1, 3), "p4": (4, 4), "p5-9": (5, 9)}.items():
            pool.set_peer_range(peer, lo, hi)
        for h in range(1, 10):
            b = copy.deepcopy(chain.block_store.load_block(h))
            if h == bad_height:
                b.last_commit.signatures[1].signature = bytes(64)
            assert pool.add_block(pool.requesters[h].peer_id, b)
        window = pool.window()
        assert len(window) == 9
        with pytest.raises(ValueError, match=refusal):
            bsync.verify_window(client.state, window, client.max_rows)
        client._redo_per_block(window)
        assert pool.banned == banned

    asyncio.run(run())


# ---------------------------------------------------------------------------
# consensus restart after fast sync (fresh WAL on an advanced chain)
# ---------------------------------------------------------------------------


def test_consensus_starts_with_fresh_wal_on_synced_chain(tmp_path):
    """After fast sync the WAL has only its initial EndHeight(0) barrier
    while the state is at height N — consensus must start cleanly
    (its next commit writes the N+1 barrier)."""

    async def run():
        from tendermint_tpu.consensus.config import ConsensusConfig
        from tendermint_tpu.consensus.state import ConsensusState
        from tendermint_tpu.consensus.wal import WAL

        chain = ChainBuilder(n_vals=1).build(3)
        wal = WAL(str(tmp_path / "cs.wal"))

        class _PV:
            def __init__(self, key):
                self.key = key

            def get_pub_key(self):
                return self.key.pub_key()

            def sign_vote(self, chain_id, vote):
                vote.signature = self.key.sign(vote.sign_bytes(chain_id))

            def sign_proposal(self, chain_id, proposal):
                proposal.signature = self.key.sign(proposal.sign_bytes(chain_id))

        cs = ConsensusState(
            ConsensusConfig.test_config(),
            chain.state,
            chain.executor,
            chain.block_store,
            wal=wal,
            priv_validator=_PV(chain.keys[0]),
        )
        await cs.start()  # raised RuntimeError before the fix
        assert cs.rs.height == 4
        await cs.stop()

    asyncio.run(run())


def test_fast_sync_recovers_from_forged_validators_hash():
    """A block whose header.ValidatorsHash doesn't match the current set
    makes the static-valset prefix empty at the apply point; the reactor
    must redo + ban (not spin), then complete from an honest peer."""

    async def run():
        import copy

        chain = ChainBuilder(n_vals=4).build(12)

        evil_store = BlockStore(MemDB())
        for h in range(1, 13):
            b = chain.block_store.load_block(h)
            sc = chain.block_store.load_seen_commit(h)
            if h == 3:
                b = copy.deepcopy(b)
                b.header.validators_hash = b"\x11" * 32
            evil_store.save_block(b, b.make_part_set(), sc)

        network = MemoryNetwork()
        evil_router, evil = _make_node(
            chain.genesis, network, "cc" * 20, block_store=evil_store
        )
        evil.state = chain.state
        honest_router, honest = _make_node(
            chain.genesis, network, "aa" * 20, block_store=chain.block_store
        )
        honest.state = chain.state

        caught_up = asyncio.Event()
        client_router, client = _make_node(
            chain.genesis, network, "bb" * 20, on_caught_up=lambda s: caught_up.set()
        )

        for r in (evil_router, honest_router, client_router):
            await r.start()
        for re in (evil, honest, client):
            await re.start()
        # evil first: heights are assigned to it before honest joins
        await client_router.dial("cc" * 20)
        await asyncio.sleep(1.0)
        await client_router.dial("aa" * 20)

        await asyncio.wait_for(caught_up.wait(), timeout=30)
        assert client.store.height() == 11
        for h in range(1, 12):
            assert (
                client.store.load_block(h).hash()
                == chain.block_store.load_block(h).hash()
            )

        for re in (evil, honest, client):
            await re.stop()
        for r in (evil_router, honest_router, client_router):
            await r.stop()

    asyncio.run(run())


def test_unreported_peer_blocks_caught_up():
    """Regression: a connected peer whose StatusResponse hasn't arrived
    must block is_caught_up (its status may reveal a higher tip), bounded
    by the grace window so a silent peer can't wedge the sync."""
    import time as _time

    async def run():
        pool = BlockPool(1, startup_grace_s=0.05)
        pool.add_peer("quiet")
        _time.sleep(0.06)  # past the startup grace
        # connected-but-unreported peer within its own grace → not caught up
        pool.peers["quiet"].connected_at = _time.monotonic()
        assert not pool.is_caught_up()
        # once it reports an equal height, we are caught up
        pool.set_peer_range("quiet", 0, 1)
        assert pool.is_caught_up()

    asyncio.run(run())


def test_silent_peer_cannot_wedge_caught_up():
    async def run():
        pool = BlockPool(1, startup_grace_s=0.05)
        pool.add_peer("silent")
        import time as _time

        _time.sleep(0.12)  # past startup grace AND the peer's own grace
        assert pool.is_caught_up()

    asyncio.run(run())


# -- table-driven pool scheduling scenarios (the behavioral content of the
# reference's blockchain/v2 scheduler_test.go tables, expressed against
# this framework's single pool) ------------------------------------------


def _mkblock(builder_blocks, h):
    return builder_blocks[h]


def test_pool_scenarios_table():
    """Each scenario is (setup events, action, expected observable)."""
    from tendermint_tpu.blocksync.pool import BlockPool

    def fresh():
        p = BlockPool(start_height=1, startup_grace_s=0.0)
        p.add_peer("a")
        p.set_peer_range("a", 1, 10)
        p.add_peer("b")
        p.set_peer_range("b", 1, 10)
        return p

    class FakeBlock:
        def __init__(self, h):
            self.header = type("H", (), {"height": h})()

    # 1. unsolicited block (never requested height) is refused
    p = fresh()
    assert p.add_block("a", FakeBlock(99)) is False

    # 2. block from the WRONG peer for a requested height is refused
    p = fresh()
    assigned = {h: r.peer_id for h, r in p.requesters.items()}
    h0 = min(assigned)
    wrong = "b" if assigned[h0] == "a" else "a"
    assert p.add_block(wrong, FakeBlock(h0)) is False
    assert p.add_block(assigned[h0], FakeBlock(h0)) is True

    # 3. duplicate delivery for the same height is refused
    assert p.add_block(assigned[h0], FakeBlock(h0)) is False

    # 4. no_block shrinks the advertised range and reassigns to the other peer
    p = fresh()
    assigned = {h: r.peer_id for h, r in p.requesters.items()}
    h0 = min(assigned)
    pid = assigned[h0]
    p.no_block(pid, h0)
    assert p.peers[pid].height == h0 - 1
    r = p.requesters.get(h0)
    assert r is not None and r.peer_id != pid, "height must be reassigned"

    # 5. removing a peer reassigns its undelivered requests
    p = fresh()
    before = {h for h, r in p.requesters.items() if r.peer_id == "a"}
    assert before
    p.remove_peer("a")
    for h in before:
        r = p.requesters.get(h)
        assert r is None or r.peer_id == "b"

    # 6. ban evicts delivered blocks from the banned peer (suspect data)
    p = fresh()
    assigned = {h: r.peer_id for h, r in p.requesters.items()}
    h_a = min(h for h, pid in assigned.items() if pid == "a")
    assert p.add_block("a", FakeBlock(h_a))
    p.ban_peer("a")
    r = p.requesters.get(h_a)
    assert r is None or r.peer_id != "a", "banned peer's block must be evicted"
    assert "a" in p.take_banned()
    # banned peer cannot re-admit itself via a status broadcast
    p.set_peer_range("a", 1, 20)
    assert "a" not in p.peers

    # 7. redo bans BOTH the block's provider and its successor's provider
    p = fresh()
    assigned = {h: r.peer_id for h, r in p.requesters.items()}
    providers = {assigned[1], assigned[2]}
    p.redo(1)
    assert p.banned >= providers

    # 8. window returns the longest consecutive run from the apply point
    p = fresh()
    assigned = {h: r.peer_id for h, r in p.requesters.items()}
    for h in (1, 2, 4):  # gap at 3
        p.add_block(assigned[h], FakeBlock(h))
    win = [b.header.height for b in p.window()]
    assert win == [1, 2]

    # 9. pop advances the apply point and re-arms scheduling beyond the top
    p = fresh()
    assigned = {h: r.peer_id for h, r in p.requesters.items()}
    p.add_block(assigned[1], FakeBlock(1))
    p.pop(1)
    assert p.height == 2
    assert 1 not in p.requesters

    # 10. caught-up: within one block of the best advertised height,
    # after grace, with all peers reported
    p = BlockPool(start_height=10, startup_grace_s=0.0)
    p.add_peer("a")
    p.set_peer_range("a", 1, 10)
    assert p.is_caught_up()
    # a higher advertisement revokes it
    p.set_peer_range("a", 1, 50)
    assert not p.is_caught_up()
