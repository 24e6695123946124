"""Entry point: a full node's consensus receive loop following a live
chain (`consensus/state.py ConsensusState`, built as node/node.py builds
it: kvstore through the local ABCI client, a file WAL, the block, state
and evidence stores on the default backend, a mempool, an EventBus, no
priv validator, the default timeouts).  A call is ONE height of the
chain, delivered as the consensus reactor's channels deliver it:

  1. the proposal and its block part, as one burst;
  2. the height's prevotes, one from every validator, as one burst;
  3. its precommits, as one burst.

Each burst is decoded (`consensus/messages.decode_consensus_message`) and
put on the state's peer queue by ONE callback on the consensus loop's
thread, so that it lands as one tick — as a connection's backlog does
while the loop is busy — and the next burst waits until the tick is
handled.  The call returns when the state has committed the height.
Every wait has a timeout and raises.

The chain is ONE seeded chain of the pool's heights: the program's own
blocks (`BlockExecutor.create_proposal_block`, kvstore app hashes
chained), each height's proposal and votes signed over the reference's
sign-bytes (chipbench/reference/vote_rules.py) and kept as wire bytes, so
that nothing a decoded message memoizes outlives a call.  A node's heights
only go forward, so the pool is not replayed on one node: SPARE nodes are
built at set-up, each at the chain's genesis, and the first call of every
lap swaps to the next (its consensus routine starts then).  Warm-up items
are the heights of a chain of their own, on a node of their own.

What the calls should cost the program — the rule's cache hits (the
proposed block's LastCommit, once a validation) and the proposals' host
verifications — is added up call by call; `path` compares it with the
counters and clears it.
"""

import asyncio
import math
import os
import random
import shutil
import sys
import tempfile
import threading
from dataclasses import dataclass

from chipbench import correct, data, manifest
from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference import vote_rules as rules

SEC = 10**9
N_PEERS = 10            # peers delivering, round-robin
WAIT_S = 60.0           # the longest any one wait of a call may take
WARM_WAIT_S = 900.0     # ... of a warm-up call, which compiles or loads a program

_loop = None            # the consensus loop every node of the process runs on
_last = {}              # the program's counters as the last `path` read them
_expected = {"cache_hits": 0, "proposals": 0}


def _require_verdicts():
    """A program whose tick precheck leaves a refused vote unmarked (the
    parent of the PR that brought this entry) verifies every forged vote
    a second time, alone: it cannot give this deployment's `once`.  Say
    so and leave as a failed set-up does, before anything is compiled."""
    from tendermint_tpu.types import vote

    if not hasattr(vote, "SINGLE_VERIFIES_TOTAL"):
        print("chipbench: stage 'build' failed (exit 4): the program's consensus "
              "precheck records no refused verdict (no vote.SINGLE_VERIFIES_TOTAL)",
              file=sys.stderr, flush=True)
        raise SystemExit(4)


# -- the data ----------------------------------------------------------------


@dataclass
class Height:
    """One call: a height's three bursts as wire bytes, and the rows they
    carry — row i < n validator i's prevote, row n + i its precommit."""

    height: int
    chain: object           # the Chain it belongs to
    bursts: tuple           # ([proposal, part], [prevotes], [precommits]), wire bytes
    block_hash: bytes
    templates: tuple        # (prevote, precommit) rules.VoteTemplate
    timestamps: list
    signatures: list
    last_commit_rows: int   # rows of its block's LastCommit
    planned: tuple          # the outcome the planted verdicts give
    n_rows: int
    suspects: dict          # row -> "small_order" | "sig_bit"
    warm: bool

    def row(self, i: int):
        n = len(self.chain.pubs)
        return (self.chain.pubs[i % n], self.templates[i >= n].sign_bytes(self.timestamps[i]),
                self.signatures[i])


@dataclass
class Chain:
    name: str
    pubs: list              # in the set's order
    powers: list
    heights: int
    nodes: list             # FullNode at genesis, taken in order, never reused
    taken: int = 0
    node: object = None     # the node the chain's calls go to now


@dataclass
class VotesData:
    powers: list
    pool: list
    warmup: list


def _planted(n, suspects):
    """The planted verdicts: small-order rows are valid under ZIP-215,
    corrupted ones are not."""
    return lambda i: suspects.get(i, "small_order") == "small_order"


def _keys(seed, name, rng, n, n_edge):
    """[(pub, signing key | None)]: n validators of which n_edge hold a
    small-order key (None: their "signature" is a torsion point, s = 0)."""
    from cryptography.hazmat.primitives import serialization as ser

    encs = ref.small_order_encodings()
    keys = [(pub, None) for pub in rng.sample(encs, n_edge)]
    for i in range(n - n_edge):
        key = data.priv(seed, name, i)
        keys.append((key.public_key().public_bytes(ser.Encoding.Raw, ser.PublicFormat.Raw), key))
    return keys, encs


def _chain(seed, name, rng, sizes, adversarial, count, warm):
    """`count` consecutive heights of one chain, from its genesis; a pool
    chain's carry the forged votes `_forged` plans."""
    from tendermint_tpu.abci import AppConns
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.consensus.messages import (
        BlockPartMessage,
        ProposalMessage,
        VoteMessage,
        encode_consensus_message,
    )
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.crypto.keys import PubKey
    from tendermint_tpu.state import BlockExecutor, StateStore, make_genesis_state
    from tendermint_tpu.store import BlockStore, MemDB
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, Proposal, Vote
    from tendermint_tpu.types.basic import BlockID, SignedMsgType
    from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig

    n = sizes["validators"]
    keys, encs = _keys(seed, name, rng, n, adversarial["small_order_validators"])
    by_pub = dict(keys)
    genesis = GenesisDoc(chain_id=data.CHAIN_ID, genesis_time_ns=data.T0_NS,
                         validators=[GenesisValidator(pub_key=PubKey(pub), power=data.POWER)
                                     for pub, _ in keys])
    state = make_genesis_state(genesis)
    vals = state.validators.validators
    pubs = [v.pub_key.bytes_() for v in vals]
    state_store, block_store = StateStore(MemDB()), BlockStore(MemDB())
    state_store.save(state)
    conns = AppConns(KVStoreApplication())
    state = Handshaker(state_store, state, block_store, genesis).handshake(conns)
    executor = BlockExecutor(state_store, conns.consensus())
    chain = Chain(name, pubs, [data.POWER] * n, count, [])
    small = [i for i, pub in enumerate(pubs) if by_pub[pub] is None]
    forged = {} if warm else _forged(rng, adversarial, n, count, small)

    def sign(pub, msg):
        key = by_pub[pub]
        return rng.choice(encs) + bytes(32) if key is None else key.sign(msg)

    items = []
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    for h in range(1, count + 1):
        proposer = state.validators.get_proposer()
        block = executor.create_proposal_block(h, state, last_commit, proposer.address)
        parts = block.make_part_set()
        psh = parts.header()
        block_id = BlockID(hash=block.hash(), part_set_header=psh)
        t_block = block.header.time_ns
        tpls = tuple(rules.VoteTemplate(kind, data.CHAIN_ID, h, 0, block_id.hash, psh.total,
                                        psh.hash) for kind in (rules.PREVOTE, rules.PRECOMMIT))
        timestamps = ([t_block + SEC + i + 1 for i in range(n)]
                      + [t_block + 2 * SEC + i + 1 for i in range(n)])
        signatures = [sign(pubs[i % n], tpls[i >= n].sign_bytes(timestamps[i]))
                      for i in range(2 * n)]
        suspects = {r: "small_order" for i in small for r in (i, n + i)}
        for r in forged.get(h, ()):
            signatures[r] = signatures[r][:-1] + bytes([signatures[r][-1] ^ 1])
            suspects[r] = "sig_bit"
        t_prop = t_block + SEC // 2
        proposal = Proposal(height=h, round=0, pol_round=-1, block_id=block_id,
                            timestamp_ns=t_prop)
        proposal.signature = sign(proposer.pub_key.bytes_(), rules.proposal_sign_bytes(
            data.CHAIN_ID, h, 0, -1, block_id.hash, psh.total, psh.hash, t_prop))
        votes = [encode_consensus_message(VoteMessage(Vote(
            type=SignedMsgType.PREVOTE if i < n else SignedMsgType.PRECOMMIT, height=h,
            round=0, block_id=block_id, timestamp_ns=timestamps[i],
            validator_address=vals[i % n].address, validator_index=i % n,
            signature=signatures[i]))) for i in range(2 * n)]
        bursts = ([encode_consensus_message(ProposalMessage(proposal))]
                  + [encode_consensus_message(BlockPartMessage(h, 0, parts.get_part(p)))
                     for p in range(psh.total)], votes[:n], votes[n:])
        ok = _planted(n, suspects)
        items.append(Height(h, chain, bursts, block_id.hash, tpls, timestamps, signatures,
                            sum(1 for cs in last_commit.signatures if not cs.absent()),
                            rules.outcome(h, chain.powers, ok), 2 * n, suspects, warm))
        state, _ = executor.apply_block(state, block_id, block, commit_sigs_verified=True)
        lc = rules.last_commit(chain.powers, ok)
        last_commit = Commit(height=h, round=0, block_id=block_id, signatures=[
            CommitSig(block_id_flag=BlockIDFlag.COMMIT, validator_address=vals[i].address,
                      timestamp_ns=timestamps[n + i], signature=signatures[n + i])
            if lc[i] else CommitSig.absent_sig() for i in range(n)])
    chain.genesis = genesis
    return chain, items


def _forged(rng, adversarial, n, count, small):
    """height -> rows with a flipped signature bit: `forged_heights`
    heights of the lap carry one forged prevote and one forged precommit
    of seeded honest validators; one more height's forged precommit is the
    LAST honest validator's, which arrives after the height committed."""
    honest = [i for i in range(n) if i not in small]
    heights = rng.sample(range(2, count + 1), min(adversarial["forged_heights"] + 1, count - 1))
    plan = {h: (rng.choice(honest), n + rng.choice(honest)) for h in heights[1:]}
    plan[heights[0]] = (n + honest[-1],)
    return plan


def build(seed, cfg, sizes, cache_capacity, pool_rule, warmup_commits):
    _require_verdicts()
    name, n, adv = cfg["name"], sizes["validators"], cfg["adversarial"]
    rng = random.Random(seed)
    count = data.pool_size(cache_capacity, 2 * n, **pool_rule)
    chain, pool = _chain(seed, f"{name}|pool", rng, sizes, adv, count, False)
    warm, warmup = _chain(seed, f"{name}|warm", rng, sizes, adv, warmup_commits, True)
    # spare nodes for as many laps as a window can make at the two
    # flushes' own floor a height, half as many again
    laps = math.ceil(manifest.load()["run_seconds"] * 1.5 / (sizes["height_floor_s"] * count))
    home = tempfile.mkdtemp(prefix="chipbench-votes-")
    chain.nodes = [FullNode(chain.genesis, os.path.join(home, f"pool{k}")) for k in range(laps)]
    warm.nodes = [FullNode(warm.genesis, os.path.join(home, "warm"))]
    return VotesData(chain.powers, pool, warmup)


# -- the node ----------------------------------------------------------------


class FullNode:
    """One full node's consensus, assembled as node/node.py assembles it,
    at its chain's genesis; its routine runs on the process's one
    consensus loop from `start` (a lap's first call) to `stop`."""

    def __init__(self, genesis, home):
        from tendermint_tpu.abci import AppConns
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.config.config import BaseConfig, MempoolConfig
        from tendermint_tpu.consensus.config import ConsensusConfig
        from tendermint_tpu.consensus.replay import Handshaker
        from tendermint_tpu.consensus.state import ConsensusState
        from tendermint_tpu.consensus.wal import WAL
        from tendermint_tpu.evidence import EvidencePool
        from tendermint_tpu.mempool import Mempool
        from tendermint_tpu.node.node import load_state_from_db_or_genesis
        from tendermint_tpu.state import BlockExecutor, StateStore
        from tendermint_tpu.store import BlockStore, open_db
        from tendermint_tpu.types.events import EventBus

        self.home = home
        os.makedirs(home)
        backend = BaseConfig().db_backend
        self.dbs = [open_db(backend, os.path.join(home, f"{db}.db"))
                    for db in ("blockstore", "state", "evidence")]
        block_store, state_store = BlockStore(self.dbs[0]), StateStore(self.dbs[1])
        state = load_state_from_db_or_genesis(state_store, genesis)
        app_conns = AppConns(KVStoreApplication())
        self.event_bus = EventBus()
        state = Handshaker(state_store, state, block_store, genesis).handshake(app_conns)
        evpool = EvidencePool(self.dbs[2], state_store, block_store)
        executor = BlockExecutor(state_store, app_conns.consensus(),
                                 mempool=Mempool(MempoolConfig(), app_conns.mempool()),
                                 evidence_pool=evpool, event_bus=self.event_bus)
        self.cs = ConsensusState(ConsensusConfig(), state, executor, block_store,
                                 wal=WAL(os.path.join(home, "cs.wal", "wal")),
                                 evidence_pool=evpool)
        self.cs.event_bus = self.event_bus

    async def stop(self):
        await self.cs.stop()
        for db in self.dbs:
            db.close()
        self.event_bus.shutdown()
        shutil.rmtree(self.home, ignore_errors=True)


def _the_loop():
    global _loop
    if _loop is None:
        _loop = asyncio.new_event_loop()
        threading.Thread(target=_loop.run_forever, name="chipbench-consensus",
                         daemon=True).start()
    return _loop


def _on_loop(coro, what, wait_s=WAIT_S):
    fut = asyncio.run_coroutine_threadsafe(coro, _the_loop())
    try:
        return fut.result(wait_s)
    except TimeoutError:
        fut.cancel()
        raise RuntimeError(f"{what}: no answer from the consensus loop in {wait_s:g}s")


def _node_for(item):
    """The chain's node at the item's height: a lap's first call starts
    the next spare node (and stops the one before); no node is reused."""
    chain = item.chain
    if item.height == 1:
        if chain.taken == len(chain.nodes):
            raise RuntimeError(
                f"chain {chain.name!r}: all {len(chain.nodes)} spare nodes used; a window "
                f"ran more laps of {chain.heights} heights than the set-up built nodes for")
        old, chain.node = chain.node, chain.nodes[chain.taken]
        chain.nodes[chain.taken] = None
        chain.taken += 1

        async def swap():
            if old is not None:
                await old.stop()
            await chain.node.cs.start()

        _on_loop(swap(), f"starting node {chain.taken} of chain {chain.name!r}")
    if chain.node is None:
        raise RuntimeError(f"chain {chain.name!r}: height {item.height} before height 1")
    return chain.node


async def _deliver(cs, raws, read):
    """Decode one burst and queue it in ONE callback, then wait until the
    receive loop's tick has taken it (the tick drains and handles the
    burst in one synchronous step: an empty queue means it is handled);
    answers `read()` on the loop before the burst and after its tick."""
    from tendermint_tpu.consensus.messages import MsgInfo, decode_consensus_message
    from tendermint_tpu.utils import trace

    with trace.span("harness.deliver", msgs=len(raws)):
        msgs = [MsgInfo(decode_consensus_message(raw), f"peer{k % N_PEERS}")
                for k, raw in enumerate(raws)]
    before = read()
    for mi in msgs:
        cs.peer_msg_queue.put_nowait(mi)
    while not cs.peer_msg_queue.empty():
        await asyncio.sleep(0)
    return before, read()


def bind(d):
    from tendermint_tpu.consensus.round_state import Step

    _last.update(_program_counters())

    def call(item):
        cs = _node_for(item).cs
        if cs.rs.height != item.height:
            raise RuntimeError(f"the node is at height {cs.rs.height}, the call brings "
                               f"{item.height}")
        proposal, prevotes, precommits = item.bursts
        wait_s = WARM_WAIT_S if item.warm else WAIT_S
        # a proposal that finds the node before its prevote is validated
        # there too (doPrevote): the rule counts the validations
        step, _ = _on_loop(_deliver(cs, proposal, lambda: cs.rs.step),
                           f"height {item.height}: proposal", wait_s)
        _, pv = _on_loop(_deliver(cs, prevotes, lambda: cs.rs.votes.prevotes(0).bit_array()),
                         f"height {item.height}: prevotes", wait_s)

        def after():
            st, rs = cs.state, cs.rs
            pcs = rs.last_commit if st.last_block_height == item.height else \
                rs.votes.precommits(0)
            return st.last_block_height, st.last_block_id.hash, pcs.bit_array()

        _, (last, block_hash, pc) = _on_loop(_deliver(cs, precommits, after),
                                              f"height {item.height}: precommits", wait_s)
        _expected["cache_hits"] += rules.cache_hits(item.last_commit_rows,
                                                    step <= Step.PROPOSE)
        _expected["proposals"] += 1
        n = len(pv)
        refused = tuple(i for i in range(n) if not pv[i]) + \
            tuple(n + i for i in range(n) if not pc[i])
        if last == item.height and block_hash == item.block_hash:
            kind = "committed"
        elif last == item.height:
            kind = "committed_other"
        else:
            kind = "not_committed"
        said = (kind, (item.height, sum(pv), sum(pc), refused))
        if item.warm and said == item.planned:
            return None     # what the harness's warm-up asks of an accepted item
        return said

    return call


# -- the rule, the path ------------------------------------------------------


def expected(d, item, row_ok):
    def ok(i):
        return row_ok(i) if i in item.suspects else True

    return rules.outcome(item.height, d.powers, ok)


implied = rules.implied


def _program_counters():
    """What the program counted: vote signatures verified one at a time
    (the once-counter), and the host verifications of `verify_one` —
    every signature cache lookup that is not a fresh row the service was
    handed (each probe of `verify_one` is one lookup; a miss is verified
    on the caller's thread)."""
    from tendermint_tpu.crypto import async_verify as av
    from tendermint_tpu.types.vote import SINGLE_VERIFIES_TOTAL

    st = av.service_stats()
    return {"single": SINGLE_VERIFIES_TOTAL.samples()[0][2],
            "host_verifies": st["cache_misses"] - st["submitted"]}


def path(before, after, calls, compiles, route, chips):
    """`correct.device_path`'s rows_off_device, host_flushes,
    device_errors, compiles_in_window and route_other, and from the rule:
    the cache hits minus the LastCommit rows its validations read, the
    service's flushes minus two a height, the host verifications minus one
    proposal a height, and the vote signatures verified a second time."""
    now = _program_counters()
    moved = {k: now[k] - _last[k] for k in now}
    _last.update(now)
    want = dict(_expected)
    for k in _expected:
        _expected[k] = 0
    base = correct.device_path(before, after, calls, compiles, route, chips)
    return {
        "rows_off_device": base["rows_off_device"],
        "host_flushes": base["host_flushes"],
        "device_errors": base["device_errors"],
        "cache_hits_off": base["cache_hits"] - want["cache_hits"],
        "flushes_off": after["flushes"] - before["flushes"] - 2 * len(calls),
        "host_verifies_off": moved["host_verifies"] - want["proposals"],
        "reverified": moved["single"],
        "compiles_in_window": base["compiles_in_window"],
        "route_other": base["route_other"],
    }
