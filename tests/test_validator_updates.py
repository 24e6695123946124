"""Dynamic validator sets: ABCI EndBlock updates flowing through
consensus (effective H+2), proposer-priority distribution properties.

Scenario parity: reference types/validator_set_test.go (1711 lines —
proposer distribution ∝ power, new-validator priority penalty) and
test/e2e validator_update schedules + persistent_kvstore ValSetChange.
"""

import asyncio
from collections import Counter
from fractions import Fraction

import pytest

from tendermint_tpu.config import test_config as make_test_config
from tendermint_tpu.crypto.batch import set_default_backend
from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.node import Node
from tendermint_tpu.types import GenesisDoc, GenesisValidator
from tendermint_tpu.types.validator import Validator, ValidatorSet


@pytest.fixture(autouse=True)
def cpu_backend():
    set_default_backend("cpu")
    yield
    set_default_backend("auto")


# ---------------------------------------------------------------------------
# proposer-priority properties (pure)
# ---------------------------------------------------------------------------

def _mkset(powers):
    keys = [priv_key_from_seed(bytes([0xA1 + i]) * 32) for i in range(len(powers))]
    return ValidatorSet([Validator(pub_key=k.pub_key(), voting_power=p)
                         for k, p in zip(keys, powers)]), keys


def test_proposer_frequency_proportional_to_power():
    vals, _ = _mkset([1, 2, 3, 4])
    counts = Counter()
    rounds = 1000
    for _ in range(rounds):
        counts[vals.get_proposer().address] += 1
        vals.increment_proposer_priority(1)
    by_power = sorted(counts.values())
    # a-priori weighted round-robin: exact proportions over long runs
    assert by_power == [100, 200, 300, 400], by_power


def test_new_validator_does_not_immediately_propose():
    """A freshly-added validator starts with a priority penalty and must
    wait its turn (reference TestValidatorSetUpdatePriorityOrder)."""
    vals, _ = _mkset([10, 10, 10])
    newcomer = priv_key_from_seed(b"\xee" * 32)
    vals.update_with_change_set(
        [Validator(pub_key=newcomer.pub_key(), voting_power=10)]
    )
    assert len(vals.validators) == 4
    # the newcomer is not the first proposer after joining
    first_proposers = []
    for _ in range(3):
        first_proposers.append(vals.get_proposer().address)
        vals.increment_proposer_priority(1)
    assert newcomer.pub_key().address() not in first_proposers


def test_priorities_stay_centered_and_bounded():
    vals, _ = _mkset([5, 10, 200])
    total = vals.total_voting_power()
    for _ in range(500):
        vals.increment_proposer_priority(1)
        pris = [v.proposer_priority for v in vals.validators]
        # centering: sum stays near zero; bound: |pri| <= 2*total
        assert abs(sum(pris)) <= total, pris
        assert all(abs(p) <= 2 * total for p in pris), pris


# ---------------------------------------------------------------------------
# the memoised verify columns (public keys, powers) follow every change
# ---------------------------------------------------------------------------

COL_CHAIN = "columns-update-chain"


def _signed_by(vals, key_of, height=9):
    """A commit for `height` in which every validator of `vals` signs
    with the key `key_of` gives for its address."""
    from tendermint_tpu.types.basic import BlockID, PartSetHeader
    from helpers import sign_commit

    block_id = BlockID(hash=b"\x51" * 32,
                       part_set_header=PartSetHeader(total=1, hash=b"\x52" * 32))
    by_addr = {v.address: key_of[v.address] for v in vals.validators}
    return block_id, sign_commit(COL_CHAIN, height, 0, block_id, vals, by_addr,
                                 1_700_000_000 * 10**9 + height)


def _assert_columns_are_the_sets(vals):
    pubs, powers = vals.verify_columns()
    assert pubs == [v.pub_key.bytes_() for v in vals.validators]
    assert powers == [v.voting_power for v in vals.validators]
    assert sum(powers) == vals.total_voting_power()


CHANGES = ("key_replaced", "power_changed", "validator_removed", "validator_added",
           "copy_then_change", "priorities_moved")


@pytest.mark.parametrize("change", CHANGES)
def test_verify_columns_follow_the_set(change):
    """A commit signed by the NEW set verifies; one signed with a replaced
    key is refused with the row named.  Each check reads the columns
    first, so a memo that outlived a change would answer for the old set."""
    vals, keys = _mkset([10] * 70)           # 70 rows: the bulk sign-bytes path
    key_of = {k.pub_key().address(): k for k in keys}
    old_key_of = dict(key_of)
    block_id, commit = _signed_by(vals, key_of)
    vals.verify_commit(COL_CHAIN, block_id, 9, commit)
    vals.verify_commit_light(COL_CHAIN, block_id, 9, commit)
    _assert_columns_are_the_sets(vals)
    victim = vals.validators[3]
    target = vals.copy() if change == "copy_then_change" else vals
    newcomer = priv_key_from_seed(b"\xee" * 32)

    if change in ("key_replaced", "copy_then_change"):
        # the same address, another key (what an ABCI update of a key is)
        target.update_with_change_set([Validator(
            pub_key=newcomer.pub_key(), voting_power=10, address=victim.address)])
        key_of[victim.address] = newcomer
    elif change == "power_changed":
        target.update_with_change_set([Validator(
            pub_key=victim.pub_key, voting_power=500, address=victim.address)])
    elif change == "validator_removed":
        target.update_with_change_set([Validator(
            pub_key=victim.pub_key, voting_power=0, address=victim.address)])
    elif change == "validator_added":
        target.update_with_change_set([Validator(pub_key=newcomer.pub_key(), voting_power=7)])
        key_of[newcomer.pub_key().address()] = newcomer
    else:
        before = vals.verify_columns()
        target.increment_proposer_priority(3)
        target.get_proposer()
        rotated = target.copy_increment_proposer_priority(2)
        # neither a key nor a power moved: the memo is kept, and shared
        assert target.verify_columns() is before and rotated.verify_columns() is before
        _assert_columns_are_the_sets(rotated)

    _assert_columns_are_the_sets(target)
    assert len(target.validators) == {"validator_removed": 69, "validator_added": 71}.get(change, 70)
    new_block_id, new_commit = _signed_by(target, key_of)
    target.verify_commit(COL_CHAIN, new_block_id, 9, new_commit)
    target.verify_commit_light(COL_CHAIN, new_block_id, 9, new_commit)
    assert target.verify_commit_light_trusting(COL_CHAIN, new_commit, Fraction(1, 3)) >= 1

    if change == "copy_then_change":
        # the original still verifies its own commit and refuses the copy's
        _assert_columns_are_the_sets(vals)
        vals.verify_commit(COL_CHAIN, block_id, 9, commit)
    if change in ("key_replaced", "copy_then_change"):
        # signed by the OLD key at the replaced row: refused, the row named
        row = [v.address for v in target.validators].index(victim.address)
        _, stale = _signed_by(target, {**key_of, victim.address: old_key_of[victim.address]})
        with pytest.raises(ValueError, match=rf"wrong signature \(#{row}\) in commit for height 9"):
            target.verify_commit(COL_CHAIN, new_block_id, 9, stale)
        with pytest.raises(ValueError, match=rf"wrong signature \(#{row}\) in commit for height 9"):
            target.verify_commit_light(COL_CHAIN, new_block_id, 9, stale)
        with pytest.raises(ValueError, match=rf"wrong signature \(#{row}\)$"):
            target.verify_commit_light_trusting(COL_CHAIN, stale, Fraction(1, 3))
    if change == "power_changed":
        # the light cut moved with the power: 500 of 1,190 and 30 more rows
        # of 10 carry more than two thirds, so row 31 is never consulted
        # and row 30 is (stale powers of 10 a row would never reach the cut)
        assert target.validators[0].address == victim.address
        for row, refused in ((31, False), (30, True)):
            sig = new_commit.signatures[row].signature
            new_commit.signatures[row].signature = sig[:-1] + bytes([sig[-1] ^ 1])
            if refused:
                with pytest.raises(ValueError, match=r"wrong signature \(#30\)"):
                    target.verify_commit_light(COL_CHAIN, new_block_id, 9, new_commit)
            else:
                target.verify_commit_light(COL_CHAIN, new_block_id, 9, new_commit)


# ---------------------------------------------------------------------------
# consensus-driven set change (ABCI EndBlock → H+2)
# ---------------------------------------------------------------------------

def test_validator_set_change_through_consensus(tmp_path):
    async def run():
        key = priv_key_from_seed(b"\xa5" * 32)
        gen = GenesisDoc(
            chain_id="valup-chain",
            genesis_time_ns=1_700_000_000 * 10**9,
            validators=[GenesisValidator(pub_key=key.pub_key(), power=10)],
        )
        cfg = make_test_config(str(tmp_path))
        cfg.base.fast_sync = False
        node = Node(cfg, genesis=gen)
        node.priv_validator.priv_key = key
        node.consensus.priv_validator = node.priv_validator
        await node.start()
        try:
            await node.wait_for_height(1, timeout=30)
            # add a second validator (offline; power below 1/3 so the
            # chain keeps committing) via the kvstore val: tx
            new_key = priv_key_from_seed(b"\xa6" * 32)
            tx = b"val:" + new_key.pub_key().bytes_().hex().encode() + b"!3"
            res = node.mempool.check_tx(tx)
            assert res.code == 0, res.log

            # find the height that included the tx
            deadline = asyncio.get_running_loop().time() + 30
            included = None
            while included is None:
                for h in range(1, node.block_store.height() + 1):
                    b = node.block_store.load_block(h)
                    if b and any(bytes(t) == tx for t in b.data.txs):
                        included = h
                if included is None:
                    if asyncio.get_running_loop().time() > deadline:
                        raise AssertionError("val tx never committed")
                    await asyncio.sleep(0.1)

            await node.wait_for_height(included + 3, timeout=30)

            # effective H+2 (reference state/execution.go:406: updates
            # land in NextValidators, used at H+2)
            before = node.state_store.load_validators(included + 1)
            after = node.state_store.load_validators(included + 2)
            assert len(before.validators) == 1
            assert len(after.validators) == 2
            _, v = after.get_by_address(new_key.pub_key().address())
            assert v is not None and v.voting_power == 3

            # headers advertise the change one height ahead
            meta = node.block_store.load_block_meta(included + 1)
            assert meta.header.next_validators_hash == after.hash()

            # remove the validator again (power 0)
            tx2 = b"val:" + new_key.pub_key().bytes_().hex().encode() + b"!0"
            assert node.mempool.check_tx(tx2).code == 0
            h0 = node.block_store.height()
            await node.wait_for_height(h0 + 4, timeout=30)
            final = node.state_store.load_validators(node.block_store.height())
            assert len(final.validators) == 1
        finally:
            await node.stop()

    asyncio.run(run())
