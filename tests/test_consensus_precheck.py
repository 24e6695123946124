"""The consensus receive loop's tick precheck against the plain rule
(chipbench/reference/vote_rules.py, which imports nothing of the
program): a full node's `ConsensusState` follows a seeded chain of a small
equal-power set (2 small-order keys, forged votes), each height delivered
as three bursts — proposal and part, prevotes, precommits — that land as
one tick each.  Checked per height: the votes admitted and refused, the
block committed, the LastCommit's rows, the precheck's counters and the
once-counter (no vote verified a second time alone).  And the verdict
marker on its own: a refused verdict raises the sequential path's error
without a second verification, a vote mutated after marking is verified
for real, and the WAL holds the same messages in the same order with the
verify service off.  Host path only (the CPU backend), no device program.
"""

import asyncio
import random

import pytest

from chipbench import manifest
from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference import vote_rules as rules
from tendermint_tpu.consensus import state as cstate
from tendermint_tpu.consensus.messages import VoteMessage, decode_consensus_message
from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto.batch import set_default_backend
from tendermint_tpu.types import vote as tvote

ENTRY = manifest.entry("consensus_votes")
CFG = manifest.cell(manifest.load(), "votes-128.live")["config_file"]
HEIGHTS = 6


@pytest.fixture(autouse=True)
def fresh_host_service():
    set_default_backend("cpu")
    av.clear_service()
    yield
    av.clear_service()
    set_default_backend("auto")


def _chain(seed, n):
    sizes = {**CFG, "validators": n}
    return ENTRY._chain(seed, f"precheck-{n}", random.Random(seed), sizes,
                        CFG["adversarial"], HEIGHTS, False)


def _count(counter):
    return counter.samples()[0][2]


def _counters():
    return [_count(c) for c in cstate.VOTE_VERIFY_COUNTERS]


async def _follow(chain, items, home):
    """Every height through the receive loop as three one-tick bursts;
    per height (prevote bits, committed height, committed block hash,
    LastCommit bits), and the WAL's messages."""
    node = ENTRY.FullNode(chain.genesis, str(home))
    cs = node.cs
    await cs.start()
    seen = []
    try:
        for item in items:
            proposal, prevotes, precommits = item.bursts
            await asyncio.wait_for(ENTRY._deliver(cs, proposal, lambda: None), 30)
            _, pv = await asyncio.wait_for(
                ENTRY._deliver(cs, prevotes, lambda: cs.rs.votes.prevotes(0).bit_array()), 30)
            _, after = await asyncio.wait_for(ENTRY._deliver(cs, precommits, lambda: (
                cs.state.last_block_height, cs.state.last_block_id.hash,
                cs.rs.last_commit.bit_array() if cs.rs.last_commit else None)), 30)
            seen.append((pv, *after))
        wal = [m.msg for m in cs.wal.all_messages()]
    finally:
        await node.stop()
    return seen, wal


@pytest.mark.parametrize("seed,n", [(11, 8), (2**31 + 7, 12), (4_000_000_019, 16)])
def test_three_bursts_admit_commit_and_refuse_as_the_rule(tmp_path, seed, n):
    chain, items = _chain(seed, n)
    before = _counters()
    seen, _ = asyncio.run(_follow(chain, items, tmp_path / "node"))
    rows, refused, once = (b - a for a, b in zip(before, _counters()))
    forged = 0
    for item, (pv, height, block_hash, lc) in zip(items, seen):
        ok = {i: ref.verify(*item.row(i)) for i in item.suspects}
        verdict = lambda i: ok.get(i, True)  # noqa: E731
        kind, (h, n_pv, n_pc, bad) = rules.outcome(item.height, chain.powers, verdict)
        assert kind == "committed" and (height, block_hash) == (item.height, item.block_hash)
        assert [i for i in range(n) if not pv[i]] == [i for i in bad if i < n]
        assert sum(pv) == n_pv and sum(lc) == n_pc
        assert list(lc) == rules.last_commit(chain.powers, verdict)
        forged += len(bad)
    assert forged == 2 * CFG["adversarial"]["forged_heights"] + 1
    assert (rows, refused, once) == (2 * n * HEIGHTS, forged, 0)


def _vote(kind="prevote", forged=True, seed=5, n=8):
    """A decoded vote of the first honest validator at height 2 of a
    seeded chain, an instance of its own, its signature bit flipped when
    `forged`; with the validator's key."""
    from tendermint_tpu.crypto.keys import PubKey

    chain, items = _chain(seed, n)
    item = items[1]
    i = next(i for i in range(n) if i not in item.suspects and n + i not in item.suspects)
    raw = item.bursts[1 if kind == "prevote" else 2][i]
    vote = tvote.Vote.decode(decode_consensus_message(raw).vote.encode())
    if forged:
        vote.signature = vote.signature[:-1] + bytes([vote.signature[-1] ^ 1])
    return vote, PubKey(chain.pubs[i])


@pytest.mark.parametrize("ok", [True, False], ids=["valid", "refused"])
def test_a_verdict_stands_without_a_second_verification(monkeypatch, ok):
    vote, pk = _vote(forged=not ok)
    vote.mark_sig_verdict("chipbench", pk, ok)
    monkeypatch.setattr(av, "verify_one", lambda *a, **k: pytest.fail("verified again"))
    once = _count(tvote.SINGLE_VERIFIES_TOTAL)
    if ok:
        vote.verify("chipbench", pk)
    else:
        with pytest.raises(ValueError, match=r"^invalid signature$"):
            vote.verify("chipbench", pk)
    assert _count(tvote.SINGLE_VERIFIES_TOTAL) == once


@pytest.mark.parametrize("field", ["signature", "timestamp_ns", "chain"])
@pytest.mark.parametrize("ok", [True, False], ids=["valid", "refused"])
def test_a_vote_mutated_after_marking_is_verified_for_real(field, ok):
    """The verdict is bound to what was verified: after any change the
    vote is verified again, alone, and gets the real answer."""
    vote, pk = _vote(forged=not ok)
    vote.mark_sig_verdict("chipbench", pk, ok)
    chain_id = "chipbench"
    if field == "signature":
        vote.signature = vote.signature[:-1] + bytes([vote.signature[-1] ^ 1])
        real = not ok
    elif field == "timestamp_ns":
        vote.timestamp_ns += 1
        real = False
    else:
        chain_id = "another-chain"
        real = False
    once = _count(tvote.SINGLE_VERIFIES_TOTAL)
    if real:
        vote.verify(chain_id, pk)
    else:
        with pytest.raises(ValueError, match=r"^invalid signature$"):
            vote.verify(chain_id, pk)
    assert _count(tvote.SINGLE_VERIFIES_TOTAL) == once + 1


@pytest.mark.parametrize("kind", ["prevote", "precommit"])
def test_a_refused_vote_fails_as_the_sequential_path_does(kind):
    """The same exception and text as the same vote no precheck saw."""
    errors = []
    for marked in (False, True):
        vote, pk = _vote(kind)
        if marked:
            vote.mark_sig_verdict("chipbench", pk, False)
        with pytest.raises(ValueError) as e:
            vote.verify("chipbench", pk)
        errors.append((type(e.value), str(e.value)))
    assert errors[0] == errors[1] == (ValueError, "invalid signature")


def test_a_refused_vote_is_logged_as_the_sequential_path_logs_it(tmp_path):
    """try_add_vote's log line for a vote refused by the precheck's
    verdict equals the one for the same vote verified alone."""
    chain, items = _chain(23, 8)
    lines = []

    class Log:
        def info(self, msg, **kw):
            lines.append((msg, kw))

        def __getattr__(self, _name):
            return lambda *a, **k: None

    async def run(marked):
        node = ENTRY.FullNode(chain.genesis, str(tmp_path / f"n{int(marked)}"))
        cs = node.cs
        cs.logger = Log()
        item = items[0]
        await cs.start()
        try:
            await ENTRY._deliver(cs, item.bursts[0], lambda: None)
            vote = tvote.Vote.decode(decode_consensus_message(item.bursts[1][0]).vote.encode())
            vote.signature = vote.signature[:-1] + bytes([vote.signature[-1] ^ 1])
            if marked:
                pk = cs.rs.validators.get_by_index(vote.validator_index).pub_key
                vote.mark_sig_verdict(cs.state.chain_id, pk, False)
            assert cs.try_add_vote(vote, "peer0") is False
        finally:
            await node.stop()

    asyncio.run(run(False))
    asyncio.run(run(True))
    bad = [ln for ln in lines if ln[0] == "bad vote"]
    assert len(bad) == 2 and bad[0] == bad[1] == ("bad vote", {"err": "invalid signature"})


def _wal_view(msgs):
    """The WAL's messages as (kind, height, what): votes by type and
    validator, the rest by type and height."""
    out = []
    for m in msgs:
        inner = getattr(m, "msg", m)
        if isinstance(inner, VoteMessage):
            v = inner.vote
            out.append(("vote", v.height, (int(v.type), v.validator_index)))
        else:
            out.append((type(inner).__name__, getattr(inner, "height", None), None))
    return out


@pytest.mark.parametrize("seed", [31, 2**32 + 3])
def test_wal_order_is_the_sequential_paths(tmp_path, monkeypatch, seed):
    """The tick's verification changes nothing of what reaches the WAL
    nor its order: the same records with the verify service off
    (TM_TPU_ASYNC_VERIFY=0, a per-caller batch verifier)."""
    chain, items = _chain(seed, 8)
    _, with_service = asyncio.run(_follow(chain, items[:3], tmp_path / "a"))
    monkeypatch.setenv("TM_TPU_ASYNC_VERIFY", "0")
    chain, items = _chain(seed, 8)
    _, without = asyncio.run(_follow(chain, items[:3], tmp_path / "b"))
    view = _wal_view(with_service)
    assert view == _wal_view(without)
    assert sum(1 for k, _, _ in view if k == "vote") == 3 * 16
